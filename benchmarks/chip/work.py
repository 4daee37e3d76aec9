"""Work counts of the chip benchmark: bytes and operations computed
from shapes, the same whatever implements them.

* :func:`mgqe_decode_bytes` — the algorithmic bytes of one
  ``mgqe_decode`` call: the gathered codes read, the centroid table
  read once, the decoded rows written.  A fused or a split decode is
  read against the same count.
* :func:`forward_flops_per_example` — the dense part of a CTR model's
  forward pass per example, counting a multiply-add as 2 operations.
  Embedding lookups are not counted (they are bytes, not operations).
"""
from __future__ import annotations

import json
import os
from typing import Mapping

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Mapping[str, float]:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(path)}; known: {sorted(table)}")
    return table[device_kind]


def mgqe_decode_bytes(batch: int, num_subspaces: int, num_centroids: int,
                      subspace_dim: int, code_bytes: int = 1,
                      value_bytes: int = 4) -> int:
    """codes (batch, D) read + centroids (D, K, S) read + rows
    (batch, D*S) written."""
    d = num_subspaces * subspace_dim
    return (batch * num_subspaces * code_bytes
            + num_subspaces * num_centroids * subspace_dim * value_bytes
            + batch * d * value_bytes)


def _mlp_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))


def deepfm_flops(model: Mapping) -> int:
    """FM second-order term (sum, squares, sum of squares, the final
    combine over d) + the MLP over the concatenated fields."""
    f, d = model["n_sparse"], model["embed_dim"]
    fm = 3 * f * d + 3 * d
    mlp = _mlp_flops((f * d,) + tuple(model["mlp_dims"]) + (1,))
    return fm + mlp


def autoint_flops(model: Mapping) -> int:
    """Per interacting layer: the Q, K, V and residual projections,
    QK^T and the attention-weighted sum of V; then the output unit."""
    f, h, da = model["n_sparse"], model["n_attn_heads"], model["d_attn"]
    out = h * da
    d_in, total = model["embed_dim"], 0
    for _ in range(model["n_attn_layers"]):
        total += 4 * 2 * f * d_in * out       # wq, wk, wv, wres
        total += 2 * 2 * h * f * f * da       # scores and probs @ v
        d_in = out
    return total + 2 * f * out


FLOPS = {"deepfm": deepfm_flops, "autoint": autoint_flops}


def forward_flops_per_example(model: Mapping) -> int:
    return FLOPS[model["model"]](model)
