"""Plain reference of what the CTR configurations share: the weights
made from the seed, the 39 field lookups (MGQE fields and full
tables), the loss and the adagrad step.

It imports nothing of the program.  The program's parameter layout
(``{"fields": {"f0": {"emb", "centroids"}, ...}, ...}``) is written out
here because the benchmark hands the same weights to the program and
to the reference; ``run.py`` checks the layout against the program's
own ``init`` before it hands them over.

MGQE (arXiv:2002.08530), shared-centroid variant: a field of at least
``mgqe_min_vocab`` ids keeps a float table ``(V, d)`` and one codebook
``(D, K, S)``.  Ids are frequency-sorted; the first
``round(V * tier_head_fraction)`` ids may use all ``K`` centroids, the
rest only the first ``tier_tail_centroids``.  Serving keeps the codes
``argmin_k |e_sub - c_k|^2`` over the allowed ``k`` and decodes them to
the centroids.  Where the two nearest allowed centroids lie within the
configuration's ``tie_eps`` of each other, rounding decides which one
a float32 argmin picks; the comparison then accepts either (see
``harness.check_scores``).  Training forwards the centroid with a straight-through
gradient to ``e`` and adds ``|sg(e) - c|^2 + beta |e - sg(c)|^2``,
each a mean over rows and subspaces of the sum over the subspace.

:class:`Prec` says how the arithmetic is rounded.  The reference is
float32 with every product exact (``Precision.HIGHEST``).  The control
lowers each precision the configuration states by one step: float32
storage to bfloat16, products stated at HIGHEST to bfloat16, products
stated at the TPU's default (bfloat16 inputs) to float8 e4m3.
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LOWER = {"float32": jnp.bfloat16, "highest": jnp.bfloat16,
         "default": jnp.float8_e4m3fn}


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also beyond 32 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


class Prec:
    def __init__(self, control: bool = False):
        self.control = control

    def round(self, x, stated: str):
        if not self.control:
            return x
        return x.astype(LOWER[stated]).astype(jnp.float32)

    def store(self, tree):
        return jax.tree.map(lambda x: self.round(x, "float32"), tree)

    def einsum(self, spec: str, a, b, stated: str):
        out = jnp.einsum(spec, self.round(a, stated), self.round(b, stated),
                         precision=HIGHEST)
        return self.round(out, stated) if stated == "highest" else out

    def dot(self, a, b, stated: str = "default"):
        return jnp.matmul(self.round(a, stated), self.round(b, stated),
                          precision=HIGHEST)


def is_quantized(vocab: int, model: Mapping) -> bool:
    return model["embed_kind"] == "mgqe" and vocab >= model["mgqe_min_vocab"]


def head_ids(vocab: int, model: Mapping) -> int:
    b = int(round(vocab * model["tier_head_fraction"]))
    return max(1, min(b, vocab - 1))


def k_limit(ids, vocab: int, model: Mapping):
    return jnp.where(ids < head_ids(vocab, model), model["num_centroids"],
                     model["tier_tail_centroids"]).astype(jnp.int32)


# --------------------------------------------------------------- weights

def init_fields(key, model: Mapping) -> Dict:
    d = model["embed_dim"]
    n_sub = model["num_subspaces"]
    out = {}
    for i, (v, k) in enumerate(zip(model["field_vocab_sizes"],
                                   jax.random.split(
                                       key, model["n_sparse"]))):
        ke, kc = jax.random.split(k)
        p = {"emb": jax.random.normal(ke, (v, d)) * d ** -0.5}
        if is_quantized(v, model):
            p["centroids"] = jax.random.normal(
                kc, (n_sub, model["num_centroids"], d // n_sub)) * d ** -0.5
        out[f"f{i}"] = p
    return out


def dense_init(key, d_in: int, d_out: int) -> Dict:
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (d_in, d_out)) * d_in ** -0.5,
            "b": 0.1 * jax.random.normal(kb, (d_out,))}


def dense(p: Dict, x, prec: Prec):
    return prec.dot(x, prec.round(p["w"], "float32")) + p["b"]


# --------------------------------------------------------------- lookups

def assign(e_sub, cent, klim, prec: Prec, tie: float, swap: bool):
    """Nearest allowed centroid per subspace: (N, D, S) -> codes (N, D)
    and whether the two nearest allowed lie within ``tie`` of each other
    (N, D).  With ``swap``, such a subspace takes the runner-up."""
    dots = prec.einsum("nds,dks->ndk", e_sub, cent, "highest")
    c_sq = jnp.sum(jnp.square(cent), axis=-1)[None]
    dist = prec.round(c_sq - 2.0 * dots, "highest")
    slot = jnp.arange(cent.shape[1])[None, None, :]
    dist = jnp.where(slot >= klim[:, None, None], jnp.inf, dist)
    neg, idx = jax.lax.top_k(-dist, 2)
    tied = neg[..., 0] - neg[..., 1] < tie
    codes = jnp.argmin(dist, axis=-1)
    if swap:
        codes = jnp.where(tied, idx[..., 1], codes)
    return codes, tied


def train_assign(e_sub, cent, klim, prec: Prec):
    """The training lookup's assignment: products at the TPU default."""
    dots = prec.einsum("nds,dks->ndk", e_sub, cent, "default")
    dist = jnp.sum(jnp.square(cent), axis=-1)[None] - 2.0 * dots
    slot = jnp.arange(cent.shape[1])[None, None, :]
    dist = jnp.where(slot >= klim[:, None, None], jnp.inf, dist)
    return jnp.argmin(dist, axis=-1)


def decode(codes, cent):
    """codes (N, D) -> (N, D*S): the chosen centroid of each subspace."""
    n_sub = cent.shape[0]
    rows = cent[jnp.arange(n_sub)[None, :], codes]          # (N, D, S)
    return rows.reshape(codes.shape[0], -1)


def serve_fields(fields: Dict, ids, model: Mapping, prec: Prec,
                 tie: float = 0.0, swap: bool = False):
    """ids (N, F) -> (served field embeddings (N, F, d), number of
    lookups per example whose code is a tie within ``tie`` (N,)).  With
    ``swap`` every tied lookup takes its runner-up code."""
    n_sub = model["num_subspaces"]
    outs, n_tied = [], jnp.zeros(ids.shape[0], jnp.int32)
    for i, v in enumerate(model["field_vocab_sizes"]):
        p = prec.store(fields[f"f{i}"])
        col = ids[:, i]
        e = p["emb"][col]
        if is_quantized(v, model):
            e_sub = e.reshape(e.shape[0], n_sub, -1)
            codes, tied = assign(e_sub, p["centroids"],
                                 k_limit(col, v, model), prec, tie, swap)
            n_tied = n_tied + jnp.sum(tied, axis=-1)
            e = decode(codes, p["centroids"])
        outs.append(e)
    return jnp.stack(outs, axis=1), n_tied


def train_fields(fields: Dict, ids, model: Mapping, prec: Prec):
    """ids (N, F) -> (straight-through field embeddings (N, F, d),
    summed auxiliary loss)."""
    n_sub = model["num_subspaces"]
    sg = jax.lax.stop_gradient
    outs, aux = [], jnp.float32(0.0)
    for i, v in enumerate(model["field_vocab_sizes"]):
        p = fields[f"f{i}"]
        col = ids[:, i]
        e = p["emb"][col]
        if is_quantized(v, model):
            cent = p["centroids"]
            e_sub = e.reshape(e.shape[0], n_sub, -1)
            codes = train_assign(e_sub, cent, k_limit(col, v, model), prec)
            c = decode(codes, cent).reshape(e_sub.shape)
            q = e_sub + sg(c - e_sub)
            codebook = jnp.mean(jnp.sum(jnp.square(sg(e_sub) - c), -1))
            commit = jnp.mean(jnp.sum(jnp.square(e_sub - sg(c)), -1))
            aux = aux + codebook + model["beta"] * commit
            e = q.reshape(e.shape)
        outs.append(e)
    return jnp.stack(outs, axis=1), aux


# --------------------------------------------------------------- training

def bce(logits, y):
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def adagrad(params, acc, grads, opt: Mapping):
    """Global-norm clip, then adagrad (accumulator starts at 0);
    returns the new params, accumulator and the clipped gradients."""
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(jnp.sqrt(sq),
                                                           1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    acc = jax.tree.map(lambda a, g: a + jnp.square(g), acc, grads)
    params = jax.tree.map(
        lambda p, a, g: p - opt["lr"] * g / (jnp.sqrt(a) + opt["eps"]),
        params, acc, grads)
    return params, acc, grads


def leaf_norms(tree) -> jax.Array:
    """(n_leaves,) float32 L2 norm of each leaf."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])
