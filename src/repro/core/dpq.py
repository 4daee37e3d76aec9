"""Differentiable Product Quantization (DPQ) — VQ variant (paper §1.1).

Training keeps a full embedding table ``emb`` of shape (n, d).  Each row
is viewed as D subvectors of dim S = d/D.  Per subspace there are K
learnable centroids.  The forward pass snaps each subvector to its
nearest centroid (argmin over L2 distance), with a straight-through
estimator so gradients flow to the full table, and VQ-VAE-style
auxiliary losses so gradients flow to the centroids:

    out      = e + sg(c - e)                      (STE)
    aux_loss = mean ||sg(e) - c||^2  +  beta * mean ||e - sg(c)||^2

At serving time the full table is discarded; only the integer codes and
the centroid tables remain (see serving.py).

MGQE (mgqe.py) reuses every function here via the ``k_limit`` argument:
items restricted to the first K_i centroids simply mask distance slots
k >= K_i to +inf before the argmin.  This masked single pass is the
TPU-native replacement for the paper's dynamic group-split lookup
(DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def init_centroids(key: jax.Array, num_subspaces: int, num_centroids: int,
                   subspace_dim: int, scale: float = 1.0,
                   dtype=jnp.float32) -> jax.Array:
    """Centroid tables, shape (D, K, S)."""
    return (jax.random.normal(key, (num_subspaces, num_centroids, subspace_dim),
                              dtype=dtype) * scale)


def init_full_table(key: jax.Array, vocab_size: int, dim: int,
                    scale: Optional[float] = None, dtype=jnp.float32) -> jax.Array:
    if scale is None:
        scale = dim ** -0.5
    return jax.random.normal(key, (vocab_size, dim), dtype=dtype) * scale


# ----------------------------------------------------------------------
# Quantization primitives (shape-polymorphic over leading batch dims).
# ----------------------------------------------------------------------

def subspace_distances(e_sub: jax.Array, centroids: jax.Array) -> jax.Array:
    """Squared-L2 distances from subvectors to centroids, MXU-friendly.

    e_sub:     (..., D, S)
    centroids: (D, K, S)
    returns    (..., D, K)

    ||e - c||^2 = ||e||^2 - 2 e.c + ||c||^2; the ||e||^2 term is
    constant w.r.t. the argmin so it is dropped — what remains is a
    batched matmul plus a bias, exactly what the MXU wants.
    """
    dots = jnp.einsum("...ds,dks->...dk", e_sub, centroids)
    c_sq = jnp.sum(jnp.square(centroids), axis=-1)  # (D, K)
    # explicit rank match (sanitizer lane runs rank_promotion='raise')
    c_sq = c_sq.reshape((1,) * (dots.ndim - 2) + c_sq.shape)
    return c_sq - 2.0 * dots


def assign_codes(e_sub: jax.Array, centroids: jax.Array,
                 k_limit: Optional[jax.Array] = None) -> jax.Array:
    """Nearest-centroid codes, shape (..., D), int32.

    k_limit: optional per-item centroid budget (broadcastable to the
    leading dims of e_sub).  Slots k >= k_limit are masked to +inf —
    the MGQE shared-variable-K rule ("use only the first K_i
    centroids").
    """
    dist = subspace_distances(e_sub, centroids)
    if k_limit is not None:
        k = dist.shape[-1]
        # explicit rank match (sanitizer lane runs rank_promotion=
        # 'raise'): slot (..1.., K) vs limits broadcast to (..., 1, 1)
        slot = jnp.arange(k, dtype=jnp.int32).reshape(
            (1,) * (dist.ndim - 1) + (k,))
        lim = jnp.broadcast_to(k_limit, dist.shape[:-2])[..., None, None]
        dist = jnp.where(slot >= lim, jnp.inf, dist)
    return jnp.argmin(dist, axis=-1).astype(jnp.int32)


# Lane width of a TPU vector register and MXU tile.
LANES = 128


def decode_codes(codes: jax.Array, centroids: jax.Array) -> jax.Array:
    """codes (..., D) -> concatenated centroid vectors (..., D*S).

    Bit-identical to a gather of the selected centroids, but computed
    as a one-hot product on the MXU (:func:`_onehot_decode`), whose
    gradient is a second product.  A gather and its scatter-add
    gradient run serially on a TPU, one row of S floats at a time.
    """
    num_sub, _, sub_dim = centroids.shape
    lead = codes.shape[:-1]
    with jax.named_scope("codebook"):
        out = _onehot_decode(codes.reshape(-1, num_sub), centroids)
    return out.reshape(lead + (num_sub * sub_dim,))


def _bf16_pieces(x: jax.Array) -> jax.Array:
    """f32 (..., n) -> bf16 (..., 3n): pieces hi | mid | lo with
    hi + mid + lo == x exactly (each takes the next 8 significant bits
    of a normal f32), side by side on the last axis."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    rest = x - hi
    mid = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
    return jnp.concatenate([hi, mid, rest - mid],
                           axis=-1).astype(jnp.bfloat16)


def _sum_pieces(p: jax.Array) -> jax.Array:
    """(..., 3n) f32 products of :func:`_bf16_pieces` -> (..., n)."""
    n = p.shape[-1] // 3
    return (p[..., :n] + p[..., n:2 * n]) + p[..., 2 * n:]


def _group(num_sub: int, sub_dim: int) -> int:
    """Subspaces per block of the block-diagonal codebook: the most
    that divide D and keep a block's three pieces within one lane tile
    (all of them for narrow rows; one for wide subspaces, where a
    block-diagonal would multiply by zeros D times over)."""
    return max(g for g in range(1, num_sub + 1)
               if num_sub % g == 0 and (g == 1 or 3 * g * sub_dim <= LANES))


def _onehot(codes: jax.Array, k: int) -> jax.Array:
    """codes (B, G) -> bf16 one-hot (B, G, K), exact in bf16.  Kept 3-D
    (the products contract G and K together): a reshape to (B, G*K)
    here made the TPU compiler write the broadcast codes to HBM."""
    # explicit rank match (sanitizer lane runs rank_promotion='raise')
    slot = jnp.arange(k, dtype=codes.dtype).reshape(1, 1, k)
    return (codes[:, :, None] == slot).astype(jnp.bfloat16)


@jax.custom_vjp
def _onehot_decode(codes: jax.Array, centroids: jax.Array) -> jax.Array:
    """codes (B, D) int, centroids (D, K, S) f32 -> (B, D*S) f32.

    Per block of G subspaces: the (B, G, K) one-hot times the
    block-diagonal (G, K, G*S) codebook, cut into three bf16 pieces side
    by side, in one bf16 product with f32 accumulation.  Every output
    is one piece times 1 plus zeros, and the pieces sum to the centroid
    exactly: the arithmetic of ``Precision.HIGHEST`` less its passes on
    the one-hot, which bf16 holds exactly.  Only ``codes`` (and the
    small codebook) are kept for the gradient, which rebuilds the
    one-hot.
    """
    num_sub, k, sub_dim = centroids.shape
    grp = _group(num_sub, sub_dim)
    eye = jnp.eye(grp, dtype=centroids.dtype)
    outs = []
    for j in range(0, num_sub, grp):
        block = (centroids[j:j + grp, :, None, :] * eye[:, None, :, None]
                 ).reshape(grp, k, grp * sub_dim)
        p = jax.lax.dot_general(
            _onehot(codes[:, j:j + grp], k), _bf16_pieces(block),
            (((1, 2), (0, 1)), ((), ())), preferred_element_type=jnp.float32)
        outs.append(_sum_pieces(p))
    return jnp.concatenate(outs, axis=-1).astype(centroids.dtype)


def _onehot_decode_fwd(codes, centroids):
    return _onehot_decode(codes, centroids), (codes, centroids)


def _onehot_decode_bwd(res, g_out):
    """Centroid gradient ``one_hot^T @ g`` per block, ``g`` cut into
    bf16 pieces as the forward cuts the codebook: it differs from the
    gather's scatter-add only in the order of its f32 sums."""
    codes, centroids = res
    num_sub, k, sub_dim = centroids.shape
    grp = _group(num_sub, sub_dim)
    grads = []
    for j in range(0, num_sub, grp):
        cols = g_out[:, j * sub_dim:(j + grp) * sub_dim]
        p = jax.lax.dot_general(
            _onehot(codes[:, j:j + grp], k), _bf16_pieces(cols),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        full = _sum_pieces(p).reshape(grp, k, grp, sub_dim)
        grads += [full[i, :, i, :] for i in range(grp)]
    return None, jnp.stack(grads).astype(centroids.dtype)


_onehot_decode.defvjp(_onehot_decode_fwd, _onehot_decode_bwd)


def quantize(e: jax.Array, centroids: jax.Array,
             k_limit: Optional[jax.Array] = None,
             beta: float = 0.25) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full DPQ forward for pre-gathered rows.

    e: (..., d) full-table rows;  centroids: (D, K, S) with D*S == d.
    Returns (quantized (..., d), codes (..., D), aux_loss scalar).
    """
    num_sub, _, sub_dim = centroids.shape
    lead = e.shape[:-1]
    e_sub = e.reshape(lead + (num_sub, sub_dim))
    codes = assign_codes(e_sub, centroids, k_limit)
    c_sel = decode_codes(codes, centroids)        # (..., d)
    # Straight-through: forward value is the centroid, gradient hits e.
    q = e + jax.lax.stop_gradient(c_sel - e)
    # Codebook + commitment losses (gradients: codebook term -> centroids
    # through the decode; commitment -> e), each the mean over the
    # subvectors of their squared distance.
    def sub_mean(diff):
        sq = jnp.square(diff).reshape(lead + (num_sub, sub_dim))
        return jnp.mean(jnp.sum(sq, axis=-1))
    codebook = sub_mean(jax.lax.stop_gradient(e) - c_sel)
    commit = sub_mean(e - jax.lax.stop_gradient(c_sel))
    aux = codebook + beta * commit
    return q, codes, aux


# ----------------------------------------------------------------------
# Table-level API used by the model layers.
# ----------------------------------------------------------------------

def init(key: jax.Array, vocab_size: int, dim: int, num_subspaces: int,
         num_centroids: int, dtype=jnp.float32) -> dict:
    k_emb, k_cent = jax.random.split(key)
    emb = init_full_table(k_emb, vocab_size, dim, dtype=dtype)
    # Centroids init'd at the scale of the embeddings so early argmins
    # spread over the codebook rather than collapsing to one centroid.
    cent = init_centroids(k_cent, num_subspaces, num_centroids,
                          dim // num_subspaces, scale=dim ** -0.5, dtype=dtype)
    return {"emb": emb, "centroids": cent}


def lookup_train(params: dict, ids: jax.Array,
                 k_limit: Optional[jax.Array] = None,
                 beta: float = 0.25,
                 sharded_rows: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Training-path lookup: gather full rows, quantize, STE.

    ids: (...,) int; returns (emb (..., d), aux_loss scalar).
    """
    from repro.sharding.gather import row_gather
    e = row_gather(params["emb"], ids, sharded=sharded_rows)
    q, _, aux = quantize(e, params["centroids"], k_limit=k_limit, beta=beta)
    return q, aux


def export_codes(params: dict, k_limit_per_row: Optional[jax.Array] = None,
                 batch: int = 65536,
                 backend: Optional[str] = None) -> jax.Array:
    """Materialize serving codes for the whole vocab, shape (n, D) int32.

    Batched over rows so exporting a 10M-row table doesn't allocate a
    (n, D, K) distance tensor at once.  The nearest-centroid search
    runs through the dispatched ``dpq_assign`` kernel.
    """
    from repro.kernels.dpq_assign import assign
    emb = params["emb"]
    centroids = params["centroids"]
    n = emb.shape[0]
    num_sub, _, sub_dim = centroids.shape

    @jax.jit
    def one(rows, lim):
        # backend resolution happens at trace time (static per export)
        e_sub = rows.reshape(rows.shape[0], num_sub, sub_dim)
        return assign(e_sub, centroids, lim, backend=backend)

    outs = []
    for start in range(0, n, batch):
        rows = emb[start:start + batch]
        lim = None
        if k_limit_per_row is not None:
            lim = k_limit_per_row[start:start + batch]
        outs.append(one(rows, lim))
    return jnp.concatenate(outs, axis=0)


def serving_lookup(codes_table: jax.Array, centroids: jax.Array,
                   ids: jax.Array, backend: Optional[str] = None,
                   block_b: Optional[int] = None) -> jax.Array:
    """Serving-path lookup: codes + centroids only (full table gone).

    The decode runs through the kernel dispatch layer (DESIGN.md §5):
    the fused Pallas ``mgqe_decode`` kernel on TPU — one-hot matmul in
    VMEM instead of a per-row HBM gather — with the jnp reference as
    the XLA fallback.  ``backend``/``block_b`` usually come from
    ``EmbeddingConfig.kernel_backend`` / ``decode_block_b``; left as
    None, ``block_b`` resolves through the autotune cache.
    """
    from repro.kernels.mgqe_decode import decode
    # gather at the STORED dtype (uint8 for K<=256); the kernels widen
    # per block in VMEM — an int32 batch here quadruples gather traffic
    codes = jnp.take(codes_table, ids, axis=0)        # (..., D)
    d = codes.shape[-1]
    flat = decode(codes.reshape(-1, d), centroids,
                  block_b=block_b, backend=backend)
    return flat.reshape(ids.shape + (centroids.shape[0] * centroids.shape[-1],))
