"""Public wrapper for the DPQ nearest-centroid assignment kernel."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import dispatch
from repro.kernels.dispatch import Tunable
from repro.kernels.dpq_assign.dpq_assign import dpq_assign
from repro.kernels.dpq_assign.ref import (dpq_assign_blocked_ref,
                                          dpq_assign_ref)

# The xla entry honours block_b too (scan-blocked so the per-block
# distance slab stays cache-resident — see ref.py); 64/128 win on CPU,
# the larger blocks on the MXU-fed paths.  The pallas kernel puts rows
# on lanes, so on TPU it needs a multiple of 128: a sweep there skips 64.
dispatch.register_op(
    "dpq_assign",
    pallas=lambda e_sub, cent, k_limit=None, block_b=512: dpq_assign(
        e_sub, cent, k_limit, block_b=block_b),
    xla=lambda e_sub, cent, k_limit=None, block_b=512:
        dpq_assign_blocked_ref(e_sub, cent, k_limit, block_b=block_b),
    interpret=lambda e_sub, cent, k_limit=None, block_b=512: dpq_assign(
        e_sub, cent, k_limit, block_b=block_b, interpret=True),
    tunables={"block_b": Tunable(512, (64, 128, 256, 512, 1024))},
)


def assign(e_sub: jax.Array, centroids: jax.Array,
           k_limit: Optional[jax.Array] = None,
           block_b: Optional[int] = None,
           backend: Optional[str] = None) -> jax.Array:
    """Nearest-centroid codes (B, D) for subvectors (B, D, S)."""
    return dispatch.dispatch("dpq_assign", e_sub, centroids, k_limit,
                             block_b=block_b, backend=backend)


__all__ = ["assign", "dpq_assign", "dpq_assign_blocked_ref",
           "dpq_assign_ref"]
