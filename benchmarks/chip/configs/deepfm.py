"""Plain reference of DeepFM (arXiv:1703.04247) over MGQE fields.

    logit = FM(x) + MLP(concat(x)) + sum_f w_f[id_f] + bias
    FM(x) = 1/2 * sum_d ((sum_f x_fd)^2 - sum_f x_fd^2)

``x`` are the 39 field embeddings (``reference.serve_fields`` when
serving, ``reference.train_fields`` when training), ``w_f`` the
first-order weights (one float per id, full tables), the MLP has ReLU
between its layers and one output unit.  The output is the logit; the
sigmoid of the paper's eq. (1) is left to the loss.
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp

import reference as R
import work


def make_params(key, model: Mapping) -> Dict:
    k_emb, k_fo, k_mlp, k_b = jax.random.split(key, 4)
    dims = ((model["n_sparse"] * model["embed_dim"],)
            + tuple(model["mlp_dims"]) + (1,))
    fo_keys = jax.random.split(k_fo, model["n_sparse"])
    return {
        "fields": R.init_fields(k_emb, model),
        "first_order": {
            f"f{i}": {"emb": 0.01 * jax.random.normal(k, (v, 1))}
            for i, (v, k) in enumerate(zip(model["field_vocab_sizes"],
                                           fo_keys))},
        "mlp": [R.dense_init(k, a, b) for k, a, b in
                zip(jax.random.split(k_mlp, len(dims) - 1), dims, dims[1:])],
        "bias": 0.1 * jax.random.normal(k_b, ()),
    }


def _logit(params, x, ids, prec: R.Prec):
    s = jnp.sum(x, axis=1)
    fm = 0.5 * jnp.sum(jnp.square(s) - jnp.sum(jnp.square(x), axis=1), -1)
    h = x.reshape(x.shape[0], -1)
    for i, layer in enumerate(params["mlp"]):
        h = R.dense(layer, h, prec)
        if i < len(params["mlp"]) - 1:
            h = jax.nn.relu(h)
    fo = sum(prec.store(params["first_order"][f"f{i}"]["emb"])[ids[:, i], 0]
             for i in range(ids.shape[1]))
    return fm + h[:, 0] + fo + params["bias"]


def serve_logits(params, ids, model: Mapping, prec: R.Prec,
                 tie: float = 0.0, swap: bool = False):
    """-> (logits (N,), tied lookups per example (N,))."""
    x, n_tied = R.serve_fields(params["fields"], ids, model, prec, tie, swap)
    return _logit(params, x, ids, prec), n_tied


def train_loss(params, batch, model: Mapping, prec: R.Prec):
    params = prec.store(params)
    x, aux = R.train_fields(params["fields"], batch["sparse_ids"], model,
                            prec)
    logits = _logit(params, x, batch["sparse_ids"], prec)
    return R.bce(logits, batch["label"]) + aux


def forward_flops_per_example(model: Mapping) -> int:
    """The dense part of the forward pass per example, a multiply-add
    counted as 2 (embedding lookups are bytes, not operations): the FM
    second-order term (sum, squares, sum of squares, the final combine
    over d) + the MLP over the concatenated fields."""
    f, d = model["n_sparse"], model["embed_dim"]
    fm = 3 * f * d + 3 * d
    mlp = work.mlp_flops((f * d,) + tuple(model["mlp_dims"]) + (1,))
    return fm + mlp
