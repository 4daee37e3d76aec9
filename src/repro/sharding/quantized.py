"""Sharded quantized-table serving: distributed codes + replicated
codebooks (DESIGN.md §6).

MGQE's production story (paper §2, Fig. 1) is that after export only
integer codes ``(n, D)`` and tiny centroid tables remain.  The codes
are still O(vocab) — at billion-row vocabs they outgrow one chip's HBM
— so this module row-shards the *code* tables over the ``model`` mesh
axis exactly like ``sharding/gather.py`` row-shards dense tables, while
the codebooks (KBs each; they fit in VMEM, let alone HBM) are simply
replicated on every device.

The lookup is a shard_map with the same wire-cost shape as the dense
``row_gather`` path:

  forward: all-gather ids over the data axes (KBs) -> each model shard
           decodes the rows it owns through the *fused* decode kernel
           on its local code block (zeros elsewhere) -> psum over
           model of the (B_global, d) partials -> slice the local
           data-shard batch back out.

Wire bytes per lookup: O(B_global · d · 4), independent of vocab —
versus the table-sized all-reduces a naive pjit of ``take`` over a
row-sharded code table makes XLA emit.  There is no backward pass:
codes are a frozen export artifact.

Which schemes can be distributed, the per-scheme artifact placement,
and the per-shard local decode all come from the scheme registry
(``Scheme.supports_sharded_codes`` / ``artifact_shard_specs`` /
``QuantizedScheme.decode`` — core/schemes/), so the ServingEngine, the
benches, the tests, and any new scheme plugin all place and decode
artifacts the same way with zero edits here.  That routing is how the
rq scheme's single-pass fused ``rq_decode_stages`` decode (DESIGN.md
§11) reaches each shard with no sharding-layer changes: the per-shard
``scheme.decode(art_loc, local, ...)`` call below IS the fused path.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.schemes import get_scheme, registered_kinds, scheme_class
from repro.core.types import EmbeddingConfig
from repro.sharding.gather import ambient_mesh, data_shard_index


def supports_sharding(kind: str, variant: str = "-") -> bool:
    """True when :func:`quantized_gather` can distribute this scheme's
    codes — the source of truth for the README support matrix
    (tools/gen_tables.py)."""
    del variant  # every variant of a shardable scheme is supported
    try:
        cls = scheme_class(kind)
    except KeyError:
        return False
    return cls.supports_sharded_codes


def sharded_variants():
    """(kind, variant) pairs the sharded gather supports — enumerated
    from the scheme registry."""
    return [(kind, v)
            for kind in registered_kinds()
            if supports_sharding(kind)
            for v in scheme_class(kind).variants()]


def _codes_rows(artifact: dict) -> int:
    """Vocab row count of the (possibly per-tier list of) code tables."""
    codes = artifact["codes"]
    if isinstance(codes, (list, tuple)):
        ns = {c.shape[0] for c in codes}
        if len(ns) != 1:
            raise ValueError(
                f"per-tier code tables disagree on vocab rows: {sorted(ns)}")
        return ns.pop()
    return codes.shape[0]


def quantized_gather(artifact: dict, ids: jax.Array, cfg: EmbeddingConfig,
                     model_axis: str = "model",
                     mesh: Optional[jax.sharding.Mesh] = None,
                     decode_block_b: Optional[int] = None) -> jax.Array:
    """Sharded serving decode: ``Embedding.serve`` for distributed codes.

    Falls back to the single-device fused decode when no usable mesh is
    ambient or the shapes don't divide (single-device tests, export
    tooling) — call sites never branch.

    ``decode_block_b`` is the batch block of each shard's local decode
    kernel.  The default ``None`` defers to the autotune cache
    (DESIGN.md §11) — the shard-local batch is the all-gathered global
    batch, a shape the engine's ``cfg.decode_block_b`` pin was never
    sized for (pinning it here bypassed the tuner and measured 8x
    slower in ``BENCH_kernels.json`` sharded_decode).  Pass an int to
    pin explicitly.
    """
    scheme = get_scheme(cfg)
    if not scheme.supports_sharded_codes:
        raise ValueError(f"cannot shard codes of kind={cfg.kind!r}")
    mesh = mesh or ambient_mesh()
    if mesh is None or mesh.size == 1 or model_axis not in mesh.axis_names:
        return scheme.decode(artifact, ids)

    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    model_n = mesh.shape[model_axis]
    data_n = int(np.prod([mesh.shape[a] for a in data_axes]))
    v = _codes_rows(artifact)
    lead = ids.shape
    flat = int(np.prod(lead))
    if model_n == 1 or v % model_n or flat == 0:
        # NOTE: on an actually-sharded artifact this fallback makes
        # XLA all-gather the O(vocab) code table — correct but slow.
        # Only reachable for indivisible vocabs (the engine rejects
        # those up front) or empty batches; indivisible *batches* are
        # padded below instead of falling back.
        return scheme.decode(artifact, ids)
    # pad the flat batch up to the data-shard granularity (id 0 is
    # always valid) so odd request sizes keep the O(B·d) wire path
    flat_ids = ids.reshape(-1)
    pad = (-flat) % data_n
    if pad:
        flat_ids = jnp.pad(flat_ids, (0, pad))
    rows_local = v // model_n
    b_local = (flat + pad) // data_n
    d_out = cfg.dim

    def body(art_loc, ids_loc):
        ids_all = ids_loc
        if data_axes:
            ids_all = jax.lax.all_gather(ids_all, data_axes, tiled=True)
        shard = jax.lax.axis_index(model_axis)
        local = ids_all - shard * rows_local
        hit = (local >= 0) & (local < rows_local)
        local = jnp.clip(local, 0, rows_local - 1)
        # decode against the LOCAL code shard; any frequency-dependent
        # blending (MGQE tiers) keys on the GLOBAL id, not the shard
        # offset — the scheme's decode hook takes both
        rows = scheme.decode(art_loc, local, tier_ids=ids_all,
                             block_b=decode_block_b)  # (B_global, d)
        rows = rows * hit[:, None].astype(rows.dtype)
        full = jax.lax.psum(rows, model_axis)
        if data_axes:
            idx = data_shard_index(mesh, data_axes)
            full = jax.lax.dynamic_slice_in_dim(full, idx * b_local,
                                                b_local, axis=0)
        return full

    art_specs = scheme.artifact_shard_specs(model_axis=model_axis)
    gather_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(art_specs, P(data_axes or None)),
        out_specs=P(data_axes or None, None),
        check_vma=False)
    out = gather_sm(artifact, flat_ids)[:flat]
    return out.reshape(lead + (d_out,))


__all__ = ["quantized_gather", "sharded_variants", "supports_sharding"]
