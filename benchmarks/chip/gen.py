"""Traffic generators of the chip benchmark, all driven by ``--seed``.

The program under test receives only the arrays made here.

* :func:`arrivals` — open-loop arrival times.  Every seed gets the same
  multiset of inter-arrival gaps (the ``n`` quantiles of the
  exponential law at the cell's rate) in a seed-drawn order, so the
  offered work and the window's length do not move with the seed; only
  the order of short and long gaps does.  The gaps are those of a
  Poisson process, stratified.
* :func:`zipf_ids` — ids of a power law *truncated and renormalised*
  to ``[0, vocab)``: the continuous law ``p(x) ~ x**-s`` on
  ``[1, vocab + 1)``, floored.  No mass is clipped onto the last id
  (``repro.data.synthetic.zipf_ids`` clips its overflow onto
  ``vocab - 1``, which makes the rarest id a hot row).
* :func:`teacher_labels` — the planted logistic teacher of
  ``repro.data.synthetic.CTRStream``, vectorised: hashed per-field
  teacher embeddings, a per-field weight, ``p = sigmoid(logit/2 - 1)``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

TEACHER_DIM = 8
TEACHER_ROWS = 4096


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use (``stream``) of one seed; any
    non-negative seed, also beyond 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def arrivals(rate_rps: float, seconds: float,
             gen: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate*seconds)``
    requests; the last is due at ``seconds``."""
    if not rate_rps > 0 or not seconds > 0:
        raise ValueError(f"rate {rate_rps} and seconds {seconds} must be > 0")
    n = max(1, int(round(rate_rps * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_rps
    gaps *= seconds / gaps.sum()
    return np.cumsum(gen.permutation(gaps))


def zipf_cdf(k, vocab: int, s: float):
    """P(id <= k) of :func:`zipf_ids`."""
    k = np.asarray(k, np.float64)
    a = 1.0 - s
    return (1.0 - (k + 2.0) ** a) / (1.0 - (vocab + 1.0) ** a)


def zipf_ids(gen: np.random.Generator, shape, vocab: int,
             s: float) -> np.ndarray:
    """int32 ids in ``[0, vocab)`` with ``P(id <= k) = zipf_cdf(k)``;
    id 0 is the most frequent (the vocabularies are frequency-sorted)."""
    if not s > 1.0:
        raise ValueError(f"zipf exponent must be > 1, got {s}")
    a = 1.0 - s
    u = gen.random(shape)
    x = (1.0 - u * (1.0 - (vocab + 1.0) ** a)) ** (1.0 / a)
    ids = np.floor(x).astype(np.int64) - 1
    # u -> 1 rounds x up to vocab + 1 at most; keep the support exact
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


def request_ids(gen: np.random.Generator, n: int, batch: int,
                vocabs: Sequence[int], s: float) -> np.ndarray:
    """(n, batch, n_fields) int32: one zipf id per field per example."""
    out = np.empty((n, batch, len(vocabs)), np.int32)
    for f, v in enumerate(vocabs):
        out[:, :, f] = zipf_ids(gen, (n, batch), v, s)
    return out


class Teacher:
    """Planted logistic teacher (copied from ``CTRStream``)."""

    def __init__(self, vocabs: Sequence[int], gen: np.random.Generator):
        self.emb = [gen.normal(size=(min(v, TEACHER_ROWS), TEACHER_DIM))
                    .astype(np.float32) for v in vocabs]
        self.w = gen.normal(size=(len(vocabs), TEACHER_DIM)).astype(
            np.float32)

    def labels(self, ids: np.ndarray, gen: np.random.Generator) -> np.ndarray:
        """ids (B, F) -> float32 labels (B,) in {0, 1}."""
        logit = np.zeros(ids.shape[0], np.float32)
        for f, e in enumerate(self.emb):
            logit += e[ids[:, f] % e.shape[0]] @ self.w[f]
        p = 1.0 / (1.0 + np.exp(-(logit * 0.5 - 1.0)))
        return (gen.random(ids.shape[0]) < p).astype(np.float32)
