"""DeepFM [arXiv:1703.04247]: FM interaction branch + deep MLP sharing
the same field embeddings.  n_sparse=39, embed_dim=10, MLP 400-400-400.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RecsysConfig
from repro.core import Embedding, EmbeddingConfig
from repro.models.recsys.fields import FieldEmbeddings
from repro.nn.mlp import mlp, mlp_init


class DeepFM:
    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg
        self.fields = FieldEmbeddings(cfg)
        # first-order weights: one scalar per categorical value — these
        # stay full (dim-1 tables are already minimal).
        self.first_order = [
            Embedding(EmbeddingConfig(vocab_size=v, dim=1))
            for v in cfg.field_vocab_sizes]

    def init(self, key, dtype=jnp.float32) -> Dict:
        cfg = self.cfg
        k_emb, k_fo, k_mlp = jax.random.split(key, 3)
        fo_keys = jax.random.split(k_fo, len(self.first_order))
        d_in = cfg.n_sparse * cfg.embed_dim
        return {
            "fields": self.fields.init(k_emb, dtype),
            "first_order": {f"f{i}": e.init(k, dtype=dtype)
                            for i, (e, k) in
                            enumerate(zip(self.first_order, fo_keys))},
            "mlp": mlp_init(k_mlp, (d_in,) + tuple(cfg.mlp_dims) + (1,),
                            dtype=dtype),
            "bias": jnp.zeros((), dtype),
        }

    @staticmethod
    def _fm(x: jax.Array) -> jax.Array:
        """Second-order FM term via the sum-square trick.
        x: (B, F, d) -> (B,)   0.5 * ((Σv)² − Σv²) summed over d."""
        s = jnp.sum(x, axis=1)
        sq = jnp.sum(jnp.square(x), axis=1)
        return 0.5 * jnp.sum(jnp.square(s) - sq, axis=-1)

    @jax.named_scope("tower")
    def _logit(self, params: Dict, x: jax.Array, fo: jax.Array) -> jax.Array:
        b = x.shape[0]
        fm = self._fm(x)
        deep = mlp(params["mlp"], x.reshape(b, -1), act="relu")[:, 0]
        return fm + deep + fo + params["bias"]

    @jax.named_scope("first_order")
    def _first_order(self, params: Dict, ids: jax.Array) -> jax.Array:
        total = jnp.zeros((ids.shape[0],), jnp.float32)
        for i, e in enumerate(self.first_order):
            o, _ = e.apply(params["first_order"][f"f{i}"], ids[:, i])
            total = total + o[:, 0]
        return total

    def apply(self, params: Dict, batch: Dict) -> Tuple[jax.Array, jax.Array]:
        ids = batch["sparse_ids"]
        x, aux = self.fields.apply(params["fields"], ids)
        fo = self._first_order(params, ids)
        return self._logit(params, x, fo), aux

    def serve(self, params: Dict, artifacts: Dict, batch: Dict) -> jax.Array:
        ids = batch["sparse_ids"]
        x = self.fields.serve(artifacts, ids)
        fo = self._first_order(params, ids)
        return self._logit(params, x, fo)

    def loss(self, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
        logits, aux = self.apply(params, batch)
        with jax.named_scope("tower"):
            y = batch["label"].astype(jnp.float32)
            bce = jnp.mean(jnp.maximum(logits, 0) - logits * y
                           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
            loss = bce + aux
        return loss, {"loss": loss, "bce": bce, "aux": aux}
