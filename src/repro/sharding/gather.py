"""Model-parallel embedding row gather via shard_map (§Perf hillclimb).

The naive pjit path for ``take(table, ids)`` with a row-sharded table
and a data-sharded batch makes XLA materialize / all-reduce *dense
table-sized* tensors in the backward (the two-tower train_batch
baseline shows ~100 s of collective term from exactly this).  The
shard_map formulation keeps everything proportional to the BATCH:

  forward:  all-gather ids over data (KBs) -> each model shard gathers
            the rows it owns (zeros elsewhere) -> psum over model of the
            (B_global, d) partials -> slice the local data-shard batch.
  backward: transpose of the psum+slice replays output grads to every
            model shard (one (B_global, d) all-gather-sized collective),
            and the scatter-add into the table shard is LOCAL.

Wire bytes per table per step: O(B_global * d), independent of vocab.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def ambient_mesh():
    """The mesh installed by ``with mesh:`` (read at trace time), or
    None outside one."""
    from jax.interpreters import pxla
    mesh = pxla.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def data_shard_index(mesh, data_axes) -> jax.Array:
    """Linearized index of this device's data shard — shard_map-body
    helper shared by the dense row gather and the quantized gather
    (sharding/quantized.py), so their batch-slice arithmetic is one
    implementation."""
    idx = jnp.int32(0)
    for a in data_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def row_gather(table: jax.Array, ids: jax.Array,
               sharded: bool = False, model_axis: str = "model"
               ) -> jax.Array:
    """take(table, ids, axis=0) — shard_map path when ``sharded``.

    Falls back to plain take when no usable mesh is ambient or shapes
    don't divide (single-device tests, serving export, etc.).
    """
    if not sharded:
        return jnp.take(table, ids, axis=0)
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1 or model_axis not in mesh.axis_names:
        return jnp.take(table, ids, axis=0)

    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    model_n = mesh.shape[model_axis]
    data_n = int(np.prod([mesh.shape[a] for a in data_axes]))
    v, d = table.shape
    lead = ids.shape
    flat = int(np.prod(lead))
    if v % model_n or flat % data_n:
        return jnp.take(table, ids, axis=0)
    rows_local = v // model_n
    b_local = flat // data_n

    def _local_ids(ids_loc):
        ids_all = ids_loc.reshape(-1)
        if data_axes:
            ids_all = jax.lax.all_gather(ids_all, data_axes, tiled=True)
        shard = jax.lax.axis_index(model_axis)
        local = ids_all - shard * rows_local
        hit = (local >= 0) & (local < rows_local)
        return jnp.clip(local, 0, rows_local - 1), hit

    def fwd_body(table_loc, ids_loc):
        local, hit = _local_ids(ids_loc)
        rows = jnp.take(table_loc, local, axis=0)
        rows = rows * hit[:, None].astype(rows.dtype)
        full = jax.lax.psum(rows, model_axis)          # (B_global, d)
        # slice this data shard's batch back out
        if data_axes:
            idx = data_shard_index(mesh, data_axes)
            full = jax.lax.dynamic_slice_in_dim(full, idx * b_local,
                                                b_local, axis=0)
        return full

    def bwd_body(ids_loc, dout_loc):
        """Table gradient computed COMPLETE on every shard: all-gather
        the (batch-sized) output grads over data, scatter-add into the
        local row shard.  Wire cost O(B_global x d) instead of the
        table-sized psum the generic transpose would emit — the whole
        point of this path (§Perf hillclimb C)."""
        local, hit = _local_ids(ids_loc)
        dout = dout_loc
        if data_axes:
            dout = jax.lax.all_gather(dout, data_axes, tiled=True)
        dt = jnp.zeros((rows_local, d), dout.dtype)
        dt = dt.at[local].add(dout * hit[:, None].astype(dout.dtype))
        return dt

    gather_sm = jax.shard_map(
        fwd_body, mesh=mesh,
        in_specs=(P(model_axis, None), P(data_axes or None)),
        out_specs=P(data_axes or None, None),
        check_vma=False)
    scatter_sm = jax.shard_map(
        bwd_body, mesh=mesh,
        in_specs=(P(data_axes or None), P(data_axes or None, None)),
        out_specs=P(model_axis, None),      # identical across data: no psum
        check_vma=False)

    @jax.custom_vjp
    def _gather(table, ids_flat):
        return gather_sm(table, ids_flat)

    def _fwd(table, ids_flat):
        return gather_sm(table, ids_flat), ids_flat

    def _bwd(ids_flat, dout):
        return scatter_sm(ids_flat, dout), None

    _gather.defvjp(_fwd, _bwd)
    out = _gather(table, ids.reshape(-1))
    return out.reshape(lead + (d,))
