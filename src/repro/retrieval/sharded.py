"""Sharded batched top-k: distributed corpus rows, merged partials.

Corpus-sized artifact leaves (``Index.rows_leaves`` — flat codes, IVF
list tables) are row-sharded over the ``model`` mesh axis exactly like
the quantized code tables in ``sharding/quantized.py``; everything
else (codebooks, the coarse table) is KBs and replicated.  One
shard_map per search:

  forward: all-gather queries over the data axes (KBs) -> each model
           shard runs the index's OWN ``local_topk`` on the rows it
           holds (global ids, (B_global, k) partials) -> all-gather the
           partials over model -> two-key ``merge_topk`` -> slice the
           local data-shard batch back out.

Wire bytes per search: O(B · k · (model_n + 1) · 8) — scores + ids,
independent of the corpus size; versus O(B · N · 4) to centralize the
score matrix, or O(N · D) to move codes.  The merge is bit-identical
to the single-device scan: per-candidate scores do not depend on block
or shard boundaries, and the (score desc, id asc) total order makes
truncation-by-k associative (retrieval/topk.py).

Placement comes from the index registry
(``Index.artifact_shard_specs`` via ``sharding/rules.py``), so a new
index kind distributes with zero edits here — mirroring how the scheme
registry feeds ``sharding/quantized.py`` (DESIGN.md §6/§8).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.retrieval.base import Index
from repro.retrieval.topk import merge_topk
from repro.sharding.gather import ambient_mesh, data_shard_index


def sharded_topk(index: Index, artifact: Dict, queries: jax.Array,
                 k: int, model_axis: str = "model",
                 mesh: Optional[jax.sharding.Mesh] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Distributed ``index.search``: queries (B, d) -> (scores (B, k),
    ids (B, k)) over row-sharded corpus artifacts.

    Falls back to the single-device search when no usable mesh is
    ambient or the row counts don't divide — call sites never branch.
    """
    mesh = mesh or ambient_mesh()
    if mesh is None or mesh.size == 1 or model_axis not in mesh.axis_names:
        return index.search(artifact, queries, k)
    if not index.supports_sharded:
        raise ValueError(
            f"index kind {index.kind!r} cannot be distributed")

    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    model_n = mesh.shape[model_axis]
    data_n = int(np.prod([mesh.shape[a] for a in data_axes])) or 1
    rows = {name: artifact[name].shape[0] for name in index.rows_leaves}
    b, d = queries.shape
    if model_n == 1 or b == 0 or any(r % model_n for r in rows.values()):
        # indivisible corpora (or empty batches) take the exact path;
        # on an actually-sharded artifact XLA would all-gather the
        # codes here — correct but slow, so engines reject those
        # configurations up front.
        return index.search(artifact, queries, k)
    pad = (-b) % data_n
    if pad:
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
    b_local = (b + pad) // data_n

    def body(art_loc, q_loc):
        q_all = q_loc
        if data_axes:
            q_all = jax.lax.all_gather(q_all, data_axes, tiled=True)
        shard = jax.lax.axis_index(model_axis)
        s, tb, i = index.local_topk(art_loc, q_all, k, shard=shard,
                                    num_shards=model_n)  # (B_global, k)
        # gather every shard's partial top-k and merge — O(B·k) wire
        bg = s.shape[0]

        def cat(x):
            x_all = jax.lax.all_gather(x, model_axis)    # (model_n, B, k)
            return jnp.moveaxis(x_all, 0, 1).reshape(bg, model_n * k)
        ms, mi = merge_topk(cat(s), cat(i), k, tiebreak=cat(tb))
        if data_axes:
            idx = data_shard_index(mesh, data_axes)
            ms = jax.lax.dynamic_slice_in_dim(ms, idx * b_local,
                                              b_local, axis=0)
            mi = jax.lax.dynamic_slice_in_dim(mi, idx * b_local,
                                              b_local, axis=0)
        return ms, mi

    art_specs = index.artifact_shard_specs(artifact, model_axis=model_axis)
    topk_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(art_specs, P(data_axes or None, None)),
        out_specs=(P(data_axes or None, None), P(data_axes or None, None)),
        check_vma=False)
    scores, ids = topk_sm(artifact, queries)
    return scores[:b], ids[:b]


__all__ = ["sharded_topk"]
