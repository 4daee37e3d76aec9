"""Compile every Pallas kernel for a TPU v5e chip, without the chip.

The TPU compiler ships with libtpu, so each kernel is lowered and
compiled here for a *described* v5e device at real widths and its
default block sizes: Mosaic refuses a block that breaks the (8, 128)
tiling, or a kernel that outgrows scoped VMEM, exactly as it would on
the chip.  Interpret-mode parity tests cannot see either failure.

The topology is described inside a module fixture, never at import:
only one process may hold libtpu, and pytest-xdist workers all import
this file.  A compile that passes is not a chip run; nothing here runs
a kernel.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dpq_assign.dpq_assign import dpq_assign
from repro.kernels.embedding_bag.embedding_bag import embedding_bag
from repro.kernels.mgqe_decode.mgqe_decode import (mgqe_decode,
                                                   rq_decode_stages)
from repro.kernels.packed_decode.packed_decode import packed_decode
from repro.kernels.pq_score.pq_score import (pq_score, pq_score_batched,
                                             pq_topk)

U8, I32, F32, BF16 = jnp.uint8, jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


# name -> (kernel at its default block sizes, operand shapes)
CASES = {
    "mgqe_decode-deepfm": (
        mgqe_decode, [((512, 5), U8), ((5, 256, 2), F32)]),
    "mgqe_decode-d64-f32": (
        mgqe_decode, [((4096, 8), U8), ((8, 256, 8), F32)]),
    "mgqe_decode-d64-bf16": (
        mgqe_decode, [((4096, 8), U8), ((8, 256, 8), BF16)]),
    "dpq_assign-deepfm": (
        dpq_assign,
        [((65536, 5, 2), F32), ((5, 256, 2), F32), ((65536,), I32)]),
    "rq_decode_stages": (
        rq_decode_stages, [((4096, 4), U8), ((4, 256, 64), F32)]),
    "packed_decode-8bit": (
        functools.partial(packed_decode, bits=8),
        [((4096, 8), U8), ((8, 256, 8), F32)]),
    "packed_decode-4bit": (
        functools.partial(packed_decode, bits=4),
        [((4096, 4), U8), ((8, 16, 8), F32)]),
    "packed_decode-2bit": (
        functools.partial(packed_decode, bits=2),
        [((4096, 2), U8), ((8, 4, 8), F32)]),
    "embedding_bag": (
        functools.partial(embedding_bag, num_bags=512),
        [((100000, 10), F32), ((4096,), I32), ((4096,), I32)]),
    "pq_score": (pq_score, [((8, 256), F32), ((65536, 8), U8)]),
    "pq_score_batched": (
        pq_score_batched, [((16, 8, 256), F32), ((65536, 8), U8)]),
    "pq_topk": (
        functools.partial(pq_topk, k=100),
        [((16, 8, 256), F32), ((65536, 8), U8)]),
}

# kernels the v5e compiler still refuses, with its reason
REFUSED = {
    "pq_topk": "Unimplemented primitive in Pallas TPU lowering for "
               "KernelType.TC: top_k",
}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.xfail(strict=True, reason=REFUSED[n]))
    if n in REFUSED else n for n in CASES])
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
