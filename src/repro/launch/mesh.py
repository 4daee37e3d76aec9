"""Mesh construction — every mesh in the repo is built here.

Meshes use **Auto** axes: the sharding code (``with mesh:`` contexts,
GSPMD-propagated jits, eager slicing of sharded results) is written for
them.  ``jax.make_mesh`` defaults to Explicit axes, under which eager
indexing of a sharded array and ``jnp.take`` on a row-sharded table
raise ``ShardingTypeError``.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import os
from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (see the module
    docstring); ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def force_host_device_count(n: int) -> None:
    """Give CPU boxes ``n`` XLA host devices for mesh tests/demos.

    Appends ``--xla_force_host_platform_device_count`` to XLA_FLAGS;
    an existing setting is left alone.  Must run before the process's
    first jax call (backend init reads XLA_FLAGS once); inert when
    real accelerators are attached.  Shared by ``launch/serve.py
    --mesh`` and ``benchmarks/kernel_bench.py``.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4, *,
                    multi_pod: bool = False):
    """Small mesh for CI-grade sharding tests (8 host devices)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
