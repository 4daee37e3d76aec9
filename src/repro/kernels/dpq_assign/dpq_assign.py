"""Pallas TPU kernel: nearest-centroid search (the DPQ/MGQE encoder).

The compute hot-spot of DPQ *training* and of serving-code export: for
every row, squared-L2 argmin against K centroids in each of D
subspaces.  In matmul form (||e-c||^2 = ||e||^2 - 2e.c + ||c||^2, the
||e||^2 term constant w.r.t. the argmin) the distance tensor is one
MXU batched-matmul:  -2 * e_sub @ centroids^T + ||c||^2.

MGQE's tier rule rides along as a per-item mask: slots k >= k_limit[b]
get +inf before the argmin — the masked single-pass lookup that
replaces the paper's dynamic group-split (DESIGN.md §3).

Block layout: rows on LANES.  The wrapper hands the kernel the
subvectors transposed, (D*S, B), so a grid step over B takes a
(D*S, Bblk) block; per subspace j the distances are one MXU matmul
centroids[j] (K, S) @ e_j (S, Bblk) -> (K, Bblk), masked, and reduced
by an argmin over the sublane (K) axis straight into row j of the
(D, Bblk) int32 codes block.  Every block is lane-dense and obeys the
TPU's (8, 128) tiling, which a (Bblk, 1, S) row-major block breaks.
The distances never leave VMEM — the fusion win over XLA's unfused path,
which round-trips the (B, D, K) distance tensor through HBM.

The dot runs at ``Precision.HIGHEST``: at the default precision the
MXU rounds f32 operands to bf16, which moves near-tied argmins.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(e_ref, cent_ref, klim_ref, codes_ref):
    n_sub, k, s = cent_ref.shape
    klim = klim_ref[...]                               # (1, Bblk)
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    for j in range(n_sub):
        cent = cent_ref[j].astype(jnp.float32)         # (K, S)
        e = e_ref[j * s:(j + 1) * s, :].astype(jnp.float32)  # (S, Bblk)
        dots = jnp.dot(cent, e, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        c_sq = jnp.sum(jnp.square(cent), axis=1, keepdims=True)
        dist = c_sq - 2.0 * dots                       # (K, Bblk)
        dist = jnp.where(slot >= klim, jnp.inf, dist)
        codes_ref[j:j + 1, :] = jnp.argmin(dist, axis=0
                                           ).astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def dpq_assign(e_sub: jax.Array, centroids: jax.Array,
               k_limit: Optional[jax.Array] = None,
               block_b: int = 512, interpret: bool = False) -> jax.Array:
    """e_sub (B, D, S); centroids (D, K, S); k_limit (B,) or None
    -> codes (B, D) int32.  On TPU ``block_b`` must be a multiple of
    128 (rows sit on lanes)."""
    b, d, s = e_sub.shape
    n_sub, k, s2 = centroids.shape
    if (d, s) != (n_sub, s2):
        raise ValueError(f"e_sub subspaces {(d, s)} do not match "
                         f"centroids {(n_sub, s2)}")
    if k_limit is None:
        k_limit = jnp.full((b,), k, jnp.int32)
    k_limit = k_limit.astype(jnp.int32)
    pad = (-b) % block_b
    if pad:
        e_sub = jnp.pad(e_sub, ((0, pad), (0, 0), (0, 0)))
        k_limit = jnp.pad(k_limit, (0, pad), constant_values=k)
    e_t = e_sub.reshape(b + pad, d * s).T              # (D*S, B)
    codes = pl.pallas_call(
        _assign_kernel,
        grid=((b + pad) // block_b,),
        in_specs=[
            pl.BlockSpec((d * s, block_b), lambda i: (0, i)),
            pl.BlockSpec((n_sub, k, s), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, block_b), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((d, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((d, b + pad), jnp.int32),
        interpret=interpret,
    )(e_t, centroids, k_limit.reshape(1, b + pad))
    return codes.T[:b]
