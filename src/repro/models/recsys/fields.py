"""Multi-field categorical embedding collection + EmbeddingBag.

JAX has no native EmbeddingBag or CSR sparse; sum/mean CSR pooling
routes through the fused ``embedding_bag`` Pallas kernel via the
backend dispatch layer (each table row read once, each bag written
once — the FBGEMM-TBE pattern), with the take+segment_sum jnp path as
the XLA fallback and for max mode.  Large-vocab fields are compressed
with the paper's MGQE; small fields stay full (quantizing a 100-row
table is pure overhead — same reasoning as DESIGN.md §4 MACE note).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RecsysConfig
from repro.core import Embedding, EmbeddingConfig
from repro.core.partition import frequency_boundaries


def field_embedding_config(cfg: RecsysConfig, vocab: int) -> EmbeddingConfig:
    """Per-field embedding spec: MGQE/DPQ for big fields, full for small."""
    kind = cfg.embed_kind
    sharded = cfg.sharded_embedding and vocab >= cfg.mgqe_min_vocab
    kb = cfg.kernel_backend
    if vocab < cfg.mgqe_min_vocab or kind == "full":
        return EmbeddingConfig(vocab_size=vocab, dim=cfg.embed_dim,
                               sharded_rows=sharded, kernel_backend=kb)
    if kind == "dpq":
        return EmbeddingConfig(
            vocab_size=vocab, dim=cfg.embed_dim, kind="dpq",
            num_subspaces=cfg.num_subspaces, num_centroids=cfg.num_centroids,
            sharded_rows=sharded, kernel_backend=kb)
    if kind == "mgqe":
        bounds = frequency_boundaries(vocab, (cfg.tier_head_fraction,))
        return EmbeddingConfig(
            vocab_size=vocab, dim=cfg.embed_dim, kind="mgqe",
            num_subspaces=cfg.num_subspaces, num_centroids=cfg.num_centroids,
            tier_boundaries=bounds,
            tier_num_centroids=(cfg.num_centroids, cfg.tier_tail_centroids),
            sharded_rows=sharded, kernel_backend=kb)
    if kind == "rq":
        # residual-quantization plugin: num_subspaces doubles as the
        # stage count M (same code-bytes-per-row knob as PQ's D)
        return EmbeddingConfig(
            vocab_size=vocab, dim=cfg.embed_dim, kind="rq",
            num_levels=cfg.num_subspaces, num_centroids=cfg.num_centroids,
            sharded_rows=sharded, kernel_backend=kb)
    # baselines for the comparison sweeps
    if kind == "lrf":
        return EmbeddingConfig(vocab_size=vocab, dim=cfg.embed_dim,
                               kind="lrf", rank=max(2, cfg.embed_dim // 4))
    if kind == "sq":
        return EmbeddingConfig(vocab_size=vocab, dim=cfg.embed_dim,
                               kind="sq", sq_bits=8)
    if kind == "hash":
        return EmbeddingConfig(vocab_size=vocab, dim=cfg.embed_dim,
                               kind="hash", hash_buckets=max(64, vocab // 4))
    raise ValueError(kind)


class FieldEmbeddings:
    """One embedding table per sparse field."""

    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg
        if len(cfg.field_vocab_sizes) != cfg.n_sparse:
            raise ValueError(
                f"{len(cfg.field_vocab_sizes)} field vocab sizes for "
                f"n_sparse={cfg.n_sparse} fields")
        self.embs: List[Embedding] = [
            Embedding(field_embedding_config(cfg, v))
            for v in cfg.field_vocab_sizes]

    def init(self, key, dtype=jnp.float32) -> Dict:
        keys = jax.random.split(key, len(self.embs))
        return {f"f{i}": e.init(k, dtype=dtype)
                for i, (e, k) in enumerate(zip(self.embs, keys))}

    @jax.named_scope("fields")
    def apply(self, params: Dict, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """ids (B, F) -> ((B, F, d), aux_loss), under the named scope
        ``fields`` (its gradient: ``transpose(jvp(fields))``)."""
        outs, aux = [], jnp.float32(0.0)
        for i, e in enumerate(self.embs):
            o, a = e.apply(params[f"f{i}"], ids[:, i])
            outs.append(o)
            aux = aux + a
        return jnp.stack(outs, axis=1), aux

    def export(self, params: Dict) -> Dict:
        return {f"f{i}": e.export(params[f"f{i}"])
                for i, e in enumerate(self.embs)}

    @jax.named_scope("fields")
    def serve(self, artifacts: Dict, ids: jax.Array) -> jax.Array:
        outs = [e.serve(artifacts[f"f{i}"], ids[:, i])
                for i, e in enumerate(self.embs)]
        return jnp.stack(outs, axis=1)

    def artifact_struct(self) -> Dict:
        """ShapeDtypeStruct pytree of the serving artifacts (dry-run)."""
        return {f"f{i}": e.serving_artifact_struct()
                for i, e in enumerate(self.embs)}

    def serving_size_bits(self) -> int:
        return sum(e.serving_size_bits() for e in self.embs)

    def full_size_bits(self) -> int:
        return sum(v * self.cfg.embed_dim * 32
                   for v in self.cfg.field_vocab_sizes)


# ----------------------------------------------------------------------
# EmbeddingBag: ragged multi-hot pooled lookup.
# ----------------------------------------------------------------------

def embedding_bag(table: jax.Array, ids: jax.Array, segment_ids: jax.Array,
                  num_bags: int, weights: Optional[jax.Array] = None,
                  mode: str = "sum",
                  backend: Optional[str] = None) -> jax.Array:
    """CSR-style bag: ids (nnz,), segment_ids (nnz,) sorted ascending,
    -> pooled (num_bags, d).  mode: sum | mean | max.

    sum/mean run through the dispatched fused kernel (gather +
    segment-sum in one pass); max has no fused kernel and stays on the
    jnp path.
    """
    if mode == "max":
        rows = jnp.take(table, ids, axis=0)               # (nnz, d)
        if weights is not None:
            rows = rows * weights[:, None]
        return jax.ops.segment_max(rows, segment_ids, num_segments=num_bags)
    from repro.kernels.embedding_bag import bag
    pooled = bag(table, ids, segment_ids, num_bags, weights, backend=backend)
    if mode == "mean":
        counts = jax.ops.segment_sum(
            jnp.ones_like(ids, dtype=pooled.dtype), segment_ids,
            num_segments=num_bags)
        pooled = pooled / jnp.maximum(counts, 1.0)[:, None]
    return pooled


def embedding_bag_padded(table: jax.Array, ids: jax.Array,
                         mode: str = "mean") -> jax.Array:
    """Dense padded bag: ids (B, L) with -1 padding -> (B, d)."""
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    rows = jnp.take(table, safe, axis=0)                  # (B, L, d)
    rows = rows * valid[..., None].astype(rows.dtype)
    pooled = jnp.sum(rows, axis=1)
    if mode == "mean":
        n = jnp.maximum(jnp.sum(valid, axis=1, keepdims=True), 1)
        pooled = pooled / n.astype(pooled.dtype)
    return pooled
