"""Pallas TPU kernels: ADC scoring of a PQ-coded candidate corpus.

The beyond-paper serving win (DESIGN.md §3): scoring one query against
N=1M candidates with full d=64 fp32 embeddings reads 256 MB from HBM;
with PQ codes it reads N*D = 8 MB of uint8 codes and a (D, K) LUT that
lives in VMEM (8 KB).  Memory-roofline speedup ≈ 32x on the dominant
stream.

Three kernels share the layout (grid over candidate blocks, codes
block (Nblk, D) in VMEM, LUTs pinned whole):

  ``pq_score``          one query: LUT (D, K) -> scores (N,).
  ``pq_score_batched``  B queries share one pass over the code stream:
                        LUTs (B, D, K) -> scores (B, N).  The corpus
                        bytes are read ONCE for the whole batch instead
                        of once per query — the retrieval subsystem's
                        hot path (DESIGN.md §8).
  ``pq_topk``           batched scoring fused with block-wise top-k
                        accumulation: the (B, N) score matrix never
                        reaches HBM; only (B, k) scores + ids leave the
                        kernel.  The running top-k rides in the output
                        block, revisited every grid step (the TPU grid
                        is sequential).

The gather ``lut[d, codes[n, d]]`` is one-hot matmul form in all
three: per subspace j, the LUT slice (B, K) contracts with
``onehot(codes[:, j])`` (Nblk, K) over K — D plain 2-D MXU matmuls
per block, accumulated in f32.  (A single contraction over both D and
K is refused by Mosaic: its matmul takes one contracting dim.)  The
LUTs enter flattened to (B, D*K) so each subspace is a lane slice.

Tie-breaking contract (shared with ``repro.retrieval.topk``): top-k
entries are ordered by (score desc, id asc); masked/padded slots carry
``score = -inf, id = INVALID_ID`` so every implementation — fused
kernel, XLA reference, sharded merge — emits bit-identical output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INVALID_ID = jnp.iinfo(jnp.int32).max


def _onehot_scores(codes, luts):
    """codes (Nblk, D) int; luts (B, D*K) -> scores (B, Nblk) f32."""
    d = codes.shape[1]
    k = luts.shape[1] // d
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    scores = None
    for j in range(d):
        onehot = (codes[:, j:j + 1] == slot).astype(jnp.float32)
        part = jax.lax.dot_general(
            luts[:, j * k:(j + 1) * k].astype(jnp.float32), onehot,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # (B, Nblk)
        scores = part if scores is None else scores + part
    return scores


def _score_kernel(codes_ref, lut_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)          # (Nblk, D)
    scores = _onehot_scores(codes, lut_ref[...])      # (1, Nblk)
    out_ref[...] = scores.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_score(lut: jax.Array, codes: jax.Array, block_n: int = 1024,
             interpret: bool = False) -> jax.Array:
    """lut (D, K) f32; codes (N, D) int -> scores (N,) f32."""
    n, d = codes.shape
    n_sub, k = lut.shape
    if d != n_sub:
        raise ValueError(f"codes have {d} subspaces, LUT {n_sub}")
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _score_kernel,
        grid=((n + pad) // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, n_sub), lambda i: (i, 0)),
            pl.BlockSpec((1, n_sub * k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
        interpret=interpret,
    )(codes, lut.reshape(1, n_sub * k))
    return out[0, :n]


def _score_batched_kernel(codes_ref, luts_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)          # (Nblk, D)
    out_ref[...] = _onehot_scores(codes, luts_ref[...]
                                  ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_score_batched(luts: jax.Array, codes: jax.Array,
                     block_n: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """luts (B, D, K) f32; codes (N, D) int -> scores (B, N) f32."""
    n, d = codes.shape
    b, n_sub, k = luts.shape
    if d != n_sub:
        raise ValueError(f"codes have {d} subspaces, LUT {n_sub}")
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _score_batched_kernel,
        grid=((n + pad) // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, n_sub), lambda i: (i, 0)),
            pl.BlockSpec((b, n_sub * k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n + pad), jnp.float32),
        interpret=interpret,
    )(codes, luts.reshape(b, n_sub * k))
    return out[:, :n]


def _topk_kernel(codes_ref, luts_ref, out_s_ref, out_i_ref, *,
                 block_n: int, k: int, n: int):
    i = pl.program_id(0)
    codes = codes_ref[...].astype(jnp.int32)          # (Nblk, D)
    scores = _onehot_scores(codes, luts_ref[...])     # (B, Nblk)
    ids = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)                   # (1, Nblk)
    valid = ids < n
    scores = jnp.where(valid, scores, -jnp.inf)
    ids = jnp.broadcast_to(jnp.where(valid, ids, INVALID_ID), scores.shape)

    @pl.when(i == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref[...], -jnp.inf)
        out_i_ref[...] = jnp.full_like(out_i_ref[...], INVALID_ID)

    # merge the running (B, k) state with this block.  lax.top_k keeps
    # the EARLIEST position among ties; running entries (lower ids,
    # already (score desc, id asc)-ordered) precede the block's
    # ascending ids, so the ordering contract holds inductively.
    cat_s = jnp.concatenate([out_s_ref[...], scores], axis=1)
    cat_i = jnp.concatenate([out_i_ref[...], ids], axis=1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    out_s_ref[...] = top_s.astype(out_s_ref.dtype)
    out_i_ref[...] = jnp.take_along_axis(cat_i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def pq_topk(luts: jax.Array, codes: jax.Array, k: int,
            block_n: int = 1024, interpret: bool = False):
    """Fused batched score + top-k: luts (B, D, K), codes (N, D) ->
    (scores (B, k) f32, ids (B, k) int32).

    The (B, N) score matrix stays in VMEM block-by-block; HBM only
    sees the (B, k) running state.
    """
    n, d = codes.shape
    b, n_sub, kk = luts.shape
    if d != n_sub:
        raise ValueError(f"codes have {d} subspaces, LUT {n_sub}")
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    scores, ids = pl.pallas_call(
        functools.partial(_topk_kernel, block_n=block_n, k=k, n=n),
        grid=((n + pad) // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, n_sub), lambda i: (i, 0)),
            pl.BlockSpec((b, n_sub * kk), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((b, k), lambda i: (0, 0)),
                   pl.BlockSpec((b, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)),
        interpret=interpret,
    )(codes, luts.reshape(b, n_sub * kk))
    return scores, ids
