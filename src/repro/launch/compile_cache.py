"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/kernel_bench.py``) call
:func:`setup_compile_cache` first thing in ``main``; importing this
module changes nothing.

A run finds what an earlier run compiled only if both use the same
directory: ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
itself, so nothing else is set), otherwise the cache sits at the fixed
``<repo>/.jax_cache`` — never a name built from a temporary directory,
a pid or the time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Put the persistent compilation cache in place; returns its
    directory.  Call before the first compilation of the process."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["DEFAULT_DIR", "ENV_VAR", "setup_compile_cache"]
