"""Pallas TPU kernel: fused EmbeddingBag (ragged gather + segment-sum).

JAX has no native EmbeddingBag; the jnp path (take -> segment_sum)
round-trips the gathered (nnz, d) rows through HBM.  This kernel fuses
the reduction so each table row is read once and each bag row written
once — the FBGEMM-TBE pattern adapted to TPU.

TPU adaptation — gather/scatter via *scalar-prefetched index maps*
(PrefetchScalarGridSpec): TPUs can't do per-lane random access into an
HBM table from inside a kernel body, but Pallas lets the BlockSpec
``index_map`` read prefetched scalar arrays.  So:

  * grid = (nnz,): one id per step
  * blocks are 8 rows tall — the smallest row block the TPU's (8, 128)
    tiling admits (a (1, d) block is refused)
  * the INPUT block of the table is the 8-row group holding row
    ``ids[i]`` — the gather happens in the automatic block DMA, and the
    body picks the row out with a dynamic sublane slice
  * the OUTPUT block is the 8-row group holding bag ``segment_ids[i]``
    — consecutive steps in the same group revisit the same VMEM block,
    so the body accumulates in place.  Pallas keeps a revisited output
    block resident (it only flushes when the index changes), which is
    exactly the CSR contract: segment_ids sorted ascending.
  * when the step enters a new group (its group differs from the
    previous step's) the body zeroes the block with @pl.when.

Empty bags are zero-filled by a pre-pass (out init to zeros via
first-visit reset + a final jnp scatter for untouched bags is avoided
by initializing with input_output_aliasing on a zeros buffer).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROWS = 8    # sublane tile height: the smallest legal row block


def _bag_kernel(ids_ref, seg_ref, w_ref, rows_ref, out_ref):
    i = pl.program_id(0)
    seg = seg_ref[i]
    prev_seg = seg_ref[jnp.maximum(i, 1) - 1]
    is_first = (i == 0) | (seg // _ROWS != prev_seg // _ROWS)

    @pl.when(is_first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = rows_ref[pl.ds(ids_ref[i] % _ROWS, 1), :]           # (1, d)
    dst = pl.ds(seg % _ROWS, 1)
    out_ref[dst, :] += row * w_ref[i].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_bags", "interpret"))
def embedding_bag(table: jax.Array, ids: jax.Array, segment_ids: jax.Array,
                  num_bags: int, weights: Optional[jax.Array] = None,
                  interpret: bool = False) -> jax.Array:
    """table (V, d); ids (nnz,); segment_ids (nnz,) sorted ascending ->
    pooled (num_bags, d).

    Bags not present in segment_ids come back zero (the scatter-style
    jnp epilogue below merges kernel output with a zeros base).
    """
    nnz = ids.shape[0]
    v, d = table.shape
    if weights is None:
        weights = jnp.ones((nnz,), table.dtype)
    ids = ids.astype(jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)
    bags_padded = num_bags + (-num_bags) % _ROWS

    out = pl.pallas_call(
        _bag_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # ids, segment_ids, weights
            grid=(nnz,),
            in_specs=[
                # gather: the row group of ids[i] is THE block of step i
                pl.BlockSpec((_ROWS, d),
                             lambda i, ids, seg, w: (ids[i] // _ROWS, 0)),
            ],
            out_specs=pl.BlockSpec(
                (_ROWS, d), lambda i, ids, seg, w: (seg[i] // _ROWS, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bags_padded, d), table.dtype),
        interpret=interpret,
    )(ids, segment_ids, weights, table)

    # zero-fill bags that never appear (kernel leaves them undefined)
    present = jnp.zeros((num_bags,), jnp.bool_).at[segment_ids].set(True)
    return jnp.where(present[:, None], out[:num_bags], 0)
