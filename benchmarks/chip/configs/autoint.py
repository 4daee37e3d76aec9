"""Plain reference of AutoInt (arXiv:1810.11921) over MGQE fields.

Each interacting layer, per head h (eq. 5-8 of the paper):

    a_h = softmax_k(<Wq_h x_m, Wk_h x_k> / sqrt(d_attn))
    o   = concat_h(sum_k a_h[m, k] Wv_h x_k)
    x'  = ReLU(o + Wres x)

``logit = w_out . concat_f(x_f) + b_out`` after the last layer.  One
departure from the paper, kept because the repository's AutoInt makes
it: the attention scores are divided by ``sqrt(d_attn)``.
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax

import reference as R


def make_params(key, model: Mapping) -> Dict:
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    out = model["n_attn_heads"] * model["d_attn"]
    layers, d_in = [], model["embed_dim"]
    for kl in jax.random.split(k_layers, model["n_attn_layers"]):
        ks = jax.random.split(kl, 4)
        layers.append({n: jax.random.normal(k, (d_in, out)) * d_in ** -0.5
                       for n, k in zip(("wq", "wk", "wv", "wres"), ks)})
        d_in = out
    return {"fields": R.init_fields(k_emb, model), "layers": layers,
            "w_out": R.dense_init(k_out, model["n_sparse"] * out, 1)}


def _logit(params, x, model: Mapping, prec: R.Prec):
    h, da = model["n_attn_heads"], model["d_attn"]
    b, f, _ = x.shape
    for p in params["layers"]:
        w = {n: prec.round(v, "float32") for n, v in p.items()}
        q = prec.dot(x, w["wq"]).reshape(b, f, h, da)
        k = prec.dot(x, w["wk"]).reshape(b, f, h, da)
        v = prec.dot(x, w["wv"]).reshape(b, f, h, da)
        s = prec.einsum("bqhd,bkhd->bhqk", q, k, "default") * da ** -0.5
        a = jax.nn.softmax(s, axis=-1)
        o = prec.einsum("bhqk,bkhd->bqhd", a, v, "default").reshape(b, f, -1)
        x = jax.nn.relu(o + prec.dot(x, w["wres"]))
    return R.dense(params["w_out"], x.reshape(b, -1), prec)[:, 0]


def serve_logits(params, ids, model: Mapping, prec: R.Prec,
                 tie: float = 0.0, swap: bool = False):
    """-> (logits (N,), tied lookups per example (N,))."""
    x, n_tied = R.serve_fields(params["fields"], ids, model, prec, tie, swap)
    return _logit(params, x, model, prec), n_tied


def train_loss(params, batch, model: Mapping, prec: R.Prec):
    params = prec.store(params)
    x, aux = R.train_fields(params["fields"], batch["sparse_ids"], model,
                            prec)
    return R.bce(_logit(params, x, model, prec), batch["label"]) + aux


def forward_flops_per_example(model: Mapping) -> int:
    """The dense part of the forward pass per example, a multiply-add
    counted as 2 (embedding lookups are bytes, not operations): per
    interacting layer the Q, K, V and residual projections, QK^T and
    the attention-weighted sum of V; then the output unit."""
    f, h, da = model["n_sparse"], model["n_attn_heads"], model["d_attn"]
    out = h * da
    d_in, total = model["embed_dim"], 0
    for _ in range(model["n_attn_layers"]):
        total += 4 * 2 * f * d_in * out       # wq, wk, wv, wres
        total += 2 * 2 * h * f * f * da       # scores and probs @ v
        d_in = out
    return total + 2 * f * out
