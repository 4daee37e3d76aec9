"""AutoInt [arXiv:1810.11921]: multi-head self-attention over field
embeddings.  n_sparse=39, embed_dim=16, 3 attn layers, 2 heads, d_attn=32.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RecsysConfig
from repro.models.recsys.fields import FieldEmbeddings
from repro.nn import initializers as init


def _interact_layer_init(key, d_in: int, n_heads: int, d_attn: int,
                         dtype=jnp.float32) -> dict:
    kq, kk, kv, kr = jax.random.split(key, 4)
    s = d_in ** -0.5
    return {
        "wq": init.normal(kq, (d_in, n_heads * d_attn), s, dtype),
        "wk": init.normal(kk, (d_in, n_heads * d_attn), s, dtype),
        "wv": init.normal(kv, (d_in, n_heads * d_attn), s, dtype),
        "wres": init.normal(kr, (d_in, n_heads * d_attn), s, dtype),
    }


def _interact_layer(p: dict, x: jax.Array, n_heads: int,
                    d_attn: int) -> jax.Array:
    """x (B, F, d_in) -> (B, F, n_heads*d_attn); full bidirectional attn
    over the (tiny) field axis."""
    b, f, _ = x.shape
    q = (x @ p["wq"]).reshape(b, f, n_heads, d_attn)
    k = (x @ p["wk"]).reshape(b, f, n_heads, d_attn)
    v = (x @ p["wv"]).reshape(b, f, n_heads, d_attn)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d_attn ** -0.5)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, f, -1)
    return jax.nn.relu(o + x @ p["wres"])


class AutoInt:
    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg
        self.fields = FieldEmbeddings(cfg)

    def init(self, key, dtype=jnp.float32) -> Dict:
        cfg = self.cfg
        k_emb, k_layers, k_out = jax.random.split(key, 3)
        d_attn_out = cfg.n_attn_heads * cfg.d_attn
        layer_keys = jax.random.split(k_layers, cfg.n_attn_layers)
        layers = []
        d_in = cfg.embed_dim
        for lk in layer_keys:
            layers.append(_interact_layer_init(lk, d_in, cfg.n_attn_heads,
                                               cfg.d_attn, dtype))
            d_in = d_attn_out
        return {
            "fields": self.fields.init(k_emb, dtype),
            "layers": layers,
            "w_out": init.dense_init(k_out, cfg.n_sparse * d_attn_out, 1,
                                     dtype=dtype),
        }

    @jax.named_scope("tower")
    def _interact(self, params: Dict, x: jax.Array) -> jax.Array:
        for p in params["layers"]:
            x = _interact_layer(p, x, self.cfg.n_attn_heads, self.cfg.d_attn)
        b = x.shape[0]
        return init.dense(params["w_out"], x.reshape(b, -1))[:, 0]

    def apply(self, params: Dict, batch: Dict) -> Tuple[jax.Array, jax.Array]:
        """batch["sparse_ids"] (B, F) -> (logits (B,), aux)."""
        x, aux = self.fields.apply(params["fields"], batch["sparse_ids"])
        return self._interact(params, x), aux

    def serve(self, params: Dict, artifacts: Dict, batch: Dict) -> jax.Array:
        x = self.fields.serve(artifacts, batch["sparse_ids"])
        return self._interact(params, x)

    def loss(self, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
        logits, aux = self.apply(params, batch)
        with jax.named_scope("tower"):
            y = batch["label"].astype(jnp.float32)
            bce = jnp.mean(jnp.maximum(logits, 0) - logits * y
                           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
            loss = bce + aux
        return loss, {"loss": loss, "bce": bce, "aux": aux}
