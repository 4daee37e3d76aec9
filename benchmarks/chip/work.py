"""Work counts of the chip benchmark: bytes and operations computed
from shapes, the same whatever implements them.

* :func:`mgqe_decode_bytes` — the algorithmic bytes of one
  ``mgqe_decode`` call: the gathered codes read, the centroid table
  read once, the decoded rows written.  A fused or a split decode is
  read against the same count.
* :func:`mlp_flops` — the operations of a dense stack's products.

Each configuration states its own forward operations per example in
its reference, ``configs/<config>.py``, as
``forward_flops_per_example(model)``; these are what several share.
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


def mlp_flops(dims) -> int:
    """Products of a dense stack of widths ``dims``, a multiply-add
    counted as 2 operations."""
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))
