"""Pallas TPU kernel: MGQE/DPQ serving decode (codes -> embeddings).

Roofline story (DESIGN.md §3): serving FE reads ``B*d*4`` bytes of
embedding rows from HBM; MGQE reads ``B*D`` bytes of uint8 codes plus a
one-time ``D*K*S*4``-byte centroid table that *fits in VMEM* (64 KB at
d=64, K=256).  Fusing the decode keeps the 4x-32x byte reduction —
doing it as take_along_axis in HBM would read the centroids once per
row and defeat the point.

TPU adaptation: per-row dynamic gathers vectorize poorly on the VPU,
so the gather is re-expressed as a **one-hot matmul** — one 2-D MXU
matmul per subspace, written straight into that subspace's lanes of
the output block:

    onehot_j:  (Bblk, K)  from a broadcasted iota compare on codes[:, j]
    out[:, j*S:(j+1)*S] = onehot_j @ centroids[j]         (K, S)

A single batched ``einsum('bdk,dks->bds')`` is the textbook form, but
Mosaic lays the (Bblk, D, K) one-hot out tile-padded and transposed per
subspace: at Bblk=256 its scoped VMEM reached 33 MB against v5e's
16 MB limit.  The per-subspace form keeps one (Bblk, K) one-hot live.

Exactness: each output element is ONE centroid value times 1 plus
zeros, so the matmul is exact provided the MXU does not round its
operands — f32 runs at ``Precision.HIGHEST`` (the default rounds to
bf16), and narrower tables are widened to f32 first (XLA:CPU, which
runs interpret mode, has no bf16 x bf16 -> f32 dot).  Every backend
therefore returns the centroid values bit-for-bit.

Block layout: grid over B; codes block (Bblk, D) and output block
(Bblk, d) stream through VMEM; the centroid table is mapped whole into
VMEM every step (index_map returns the same block).

``rq_decode_stages`` is the residual-quantization variant (DESIGN.md
§11): codes (B, M) against M stacked full-width codebooks (M, K, d),
summed over stages in one pass (see its docstring).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def onehot_dot(onehot: jax.Array, table: jax.Array) -> jax.Array:
    """``onehot @ table`` in f32, exact (see the module docstring):
    the one-hot gather of every kernel in this package family."""
    return jnp.dot(onehot.astype(jnp.float32), table.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _decode_kernel(codes_ref, cent_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)          # (Bblk, D)
    n_sub, k, s = cent_ref.shape
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    for j in range(n_sub):
        onehot = codes[:, j:j + 1] == slot            # (Bblk, K)
        dec = onehot_dot(onehot, cent_ref[j])         # (Bblk, S)
        out_ref[:, j * s:(j + 1) * s] = dec.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mgqe_decode(codes: jax.Array, centroids: jax.Array,
                block_b: int = 256, interpret: bool = False) -> jax.Array:
    """codes (B, D) int; centroids (D, K, S) -> (B, D*S) float32.

    block_b: rows per grid step.  VMEM working set per step =
    Bblk*D codes + D*K*S centroids (lane-padded) + Bblk*K*4 one-hot +
    Bblk*d*4 out; at Bblk=256, K=256 the 256 KB one-hot dominates.
    """
    b, d = codes.shape
    n_sub, k, s = centroids.shape
    if d != n_sub:
        raise ValueError(f"codes have {d} subspaces, centroids {n_sub}")
    pad = (-b) % block_b
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _decode_kernel,
        grid=((b + pad) // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, n_sub), lambda i: (i, 0)),
            pl.BlockSpec((n_sub, k, s), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_sub * s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((b + pad), n_sub * s),
                                       centroids.dtype),
        interpret=interpret,
    )(codes, centroids)
    return out[:b]


def _staged_kernel(codes_ref, cb_ref, out_ref):
    stage = pl.program_id(2)                          # innermost grid dim
    codes = codes_ref[...].astype(jnp.int32)          # (Bblk, M)
    # this stage's column, picked by a lane mask: the codes block spans
    # all M stages (a (Bblk, 1) block breaks the TPU's (8, 128) tiling)
    lane = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)
    col = jnp.sum(jnp.where(lane == stage, codes, 0), axis=1,
                  keepdims=True)                      # (Bblk, 1)
    cb = cb_ref[0]                                    # (K, dblk)
    k = cb.shape[0]
    onehot = col == jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    dec = onehot_dot(onehot, cb)                      # (Bblk, dblk)

    @pl.when(stage == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += dec.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_d", "interpret"))
def rq_decode_stages(codes: jax.Array, codebooks: jax.Array,
                     block_b: int = 256, block_d: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """codes (B, M) int; stacked codebooks (M, K, d) -> (B, d) float:
    single-pass residual-stage decode, ``sum_m codebooks[m, codes[:, m]]``.

    Grid (B/block_b, d/block_d, M) with the stage index innermost: the
    (block_b, block_d) output block is revisited across all M stages
    and accumulates the one-hot-matmul stage decodes in VMEM — Pallas
    only flushes a revisited block when its index changes, so the stage
    sum never round-trips HBM.  Codes stay at their stored dtype
    (uint8) until the per-block int32 widening in the body.

    ``block_d`` tiles the output width (None = full d; values that do
    not divide d fall back to full width).  VMEM working set per step:
    block_b codes + K*block_d codebook slice + block_b*K onehot +
    block_b*block_d out — 256*256*4 = 256 KB onehot dominates.
    """
    b, m = codes.shape
    m2, k, d = codebooks.shape
    if m != m2:
        raise ValueError(f"codes have {m} layers, codebooks {m2}")
    if block_d is None or d % block_d:
        block_d = d
    pad = (-b) % block_b
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _staged_kernel,
        grid=((b + pad) // block_b, d // block_d, m),
        in_specs=[
            pl.BlockSpec((block_b, m), lambda i, j, s: (i, 0)),
            pl.BlockSpec((1, k, block_d), lambda i, j, s: (s, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b + pad, d), codebooks.dtype),
        interpret=interpret,
    )(codes, codebooks)
    return out[:b]
