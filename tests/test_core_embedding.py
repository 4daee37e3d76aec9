"""Core DPQ/MGQE correctness + the paper's serving-size accounting."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import Embedding, EmbeddingConfig
from repro.core import dpq, mgqe
from repro.core.partition import (frequency_boundaries, rank_by_frequency,
                                  tier_of_ids, validate_partition)


def _mk(kind="dpq", vocab=120, dim=16, D=4, K=8, **kw):
    if kind == "mgqe":
        kw.setdefault("tier_boundaries", (12,))
        kw.setdefault("tier_num_centroids", (K, max(2, K // 2)))
    return EmbeddingConfig(vocab_size=vocab, dim=dim, kind=kind,
                           num_subspaces=D, num_centroids=K, **kw)


# ----------------------------------------------------------------- DPQ

def test_dpq_forward_equals_decode_of_codes(key):
    cfg = _mk("dpq")
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.arange(37)
    out, aux = emb.apply(p, ids)
    # forward value must equal the decoded nearest-centroid embedding
    e = jnp.take(p["emb"], ids, axis=0)
    e_sub = e.reshape(37, 4, 4)
    codes = dpq.assign_codes(e_sub, p["centroids"])
    dec = dpq.decode_codes(codes, p["centroids"]).reshape(37, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dec), atol=1e-6)
    assert float(aux) >= 0.0


def test_dpq_serving_matches_training_forward(key):
    cfg = _mk("dpq")
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.asarray([0, 5, 5, 119])
    out, _ = emb.apply(p, ids)
    art = emb.export(p)
    assert art["codes"].dtype == jnp.uint8
    sv = emb.serve(art, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sv), atol=1e-5)


def test_dpq_straight_through_gradients(key):
    cfg = _mk("dpq")
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.arange(16)

    def loss(p):
        out, aux = emb.apply(p, ids)
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(p)
    # STE: gradient reaches the full table rows that were looked up
    g_emb = np.asarray(g["emb"])
    assert np.abs(g_emb[:16]).sum() > 0
    assert np.abs(g_emb[16:]).sum() == 0          # untouched rows: no grad
    assert np.abs(np.asarray(g["centroids"])).sum() > 0


def test_dpq_multi_dim_ids(key):
    cfg = _mk("dpq")
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.zeros((3, 5), jnp.int32)
    out, _ = emb.apply(p, ids)
    assert out.shape == (3, 5, 16)


# ------------------------------------------------ codebook decode paths

def _gather_decode(codes, centroids):
    """The row gather the one-hot decode replaces: codes (..., D) ->
    (..., D*S)."""
    num_sub, _, sub_dim = centroids.shape
    rows = jnp.take_along_axis(
        centroids[None], codes.reshape(-1, num_sub)[:, :, None, None], axis=2)
    return rows.reshape(codes.shape[:-1] + (num_sub * sub_dim,))


def _decode_case(k, with_limit, n=96, num_sub=5, sub_dim=2):
    """Rows, random-normal f32 centroids (every mantissa bit used) and
    per-row budgets (a quarter of K on odd rows) for a K-centroid
    codebook."""
    ke, kc = jax.random.split(jax.random.PRNGKey(k + with_limit))
    e = jax.random.normal(ke, (n, num_sub * sub_dim), jnp.float32)
    cent = jax.random.normal(kc, (num_sub, k, sub_dim), jnp.float32)
    limit = None
    if with_limit:
        limit = jnp.where(jnp.arange(n) % 2 == 1, max(1, k // 4), k)
    return e, cent, limit


@pytest.mark.parametrize(
    "k,with_limit,num_sub,sub_dim",
    [(k, lim, 5, 2) for k in (4, 64, 256, 512) for lim in (False, True)]
    + [(k, True, n, s) for k in (64, 512) for n, s in ((8, 16), (2, 96))])
def test_onehot_decode_matches_the_gather(k, with_limit, num_sub, sub_dim,
                                          monkeypatch):
    """The one-hot decode is bit-identical to the gather, and the
    gradients of ``quantize`` (output plus aux loss) match the gather
    form's but for the order of f32 sums.  The shapes take every
    grouping of subspaces: all five in one block (d=10 as in deepfm),
    blocks of two, and one subspace a block for wide rows."""
    e, cent, limit = _decode_case(k, with_limit, num_sub=num_sub,
                                  sub_dim=sub_dim)
    codes = dpq.assign_codes(e.reshape(-1, num_sub, sub_dim), cent, limit)
    assert int(jnp.max(codes)) > 0
    np.testing.assert_array_equal(
        np.asarray(jax.jit(dpq.decode_codes)(codes, cent)),
        np.asarray(_gather_decode(codes, cent)))

    w = jax.random.normal(jax.random.PRNGKey(7), e.shape)

    def loss(e, cent):
        q, _, aux = dpq.quantize(e, cent, k_limit=limit)
        return jnp.sum(q * w) + aux

    got = jax.grad(loss, argnums=(0, 1))(e, cent)
    monkeypatch.setattr(dpq, "decode_codes", _gather_decode)
    want = jax.grad(loss, argnums=(0, 1))(e, cent)
    for g, r in zip(got, want):
        # sums that cancel leave a relative gap of order n * 2^-24 on
        # their smallest entries; the floor is 1e-6 of the largest
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6,
                                   atol=1e-6 * float(jnp.max(jnp.abs(r))))


@pytest.mark.parametrize("k", [256, 512])
def test_decode_is_a_product_at_every_codebook_size(k):
    """The decode and its gradient are products on the centroids, with
    no gather or scatter, within and beyond the uint8 code range."""
    _, cent, _ = _decode_case(k, False)
    codes = jnp.zeros((96, 5), jnp.int32)
    text = str(jax.make_jaxpr(jax.vjp(dpq.decode_codes, codes, cent)[1])(
        jnp.ones((96, 10), jnp.float32)))
    text += str(jax.make_jaxpr(dpq.decode_codes)(codes, cent))
    assert "dot_general" in text, text
    assert "gather" not in text and "scatter" not in text, text


def test_quantize_keeps_codes_not_the_onehot_for_its_gradient():
    """Across the forward/backward boundary ``quantize`` keeps the int
    codes; no (B, D*K) or (B, D, K) one-hot is a residual."""
    n, num_sub, k = 64, 5, 256
    e, cent, limit = _decode_case(k, True, n=n, num_sub=num_sub)

    def loss(e, cent):
        q, _, aux = dpq.quantize(e, cent, k_limit=limit)
        return jnp.sum(q) + aux

    _, vjp = jax.vjp(jax.jit(loss), e, cent)
    kept = {(x.shape, str(x.dtype)) for x in jax.tree.leaves(vjp)}
    assert ((n, num_sub), "int32") in kept, kept
    assert not any(s in ((n, num_sub * k), (n, num_sub, k)) for s, _ in kept)
    assert sum(math.prod(s) for s, _ in kept) < n * num_sub * k
    # the decode's own gradient keeps its inputs, the codes among them
    codes = dpq.assign_codes(e.reshape(n, num_sub, 2), cent, limit)
    _, res = dpq._onehot_decode_fwd(codes, cent)
    assert res[0] is codes


# ---------------------------------------------------------------- MGQE

def test_mgqe_tier_budget_respected(key):
    """Tail items may only use the first K_i centroids (paper §2.2)."""
    cfg = _mk("mgqe", K=8)
    emb = Embedding(cfg)
    p = emb.init(key)
    art = emb.export(p)
    codes = np.asarray(art["codes"])
    # head tier: ids < 12 can use all 8; tail: only first 4
    assert codes[:12].max() <= 7
    assert codes[12:].max() <= 3


def test_mgqe_serving_matches_training(key):
    cfg = _mk("mgqe")
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.asarray([0, 11, 12, 119, 63])
    out, _ = emb.apply(p, ids)
    sv = emb.serve(emb.export(p), ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sv), atol=1e-5)


@pytest.mark.parametrize("variant", ["private_k", "private_d"])
def test_mgqe_private_variants(key, variant):
    kw = dict(mgqe_variant=variant, tier_boundaries=(12,))
    if variant == "private_k":
        kw["tier_num_centroids"] = (8, 4)
    else:
        kw["tier_num_subspaces"] = (4, 2)
    cfg = EmbeddingConfig(vocab_size=120, dim=16, kind="mgqe",
                          num_subspaces=4, num_centroids=8, **kw)
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.asarray([0, 50, 119])
    out, aux = emb.apply(p, ids)
    assert out.shape == (3, 16)
    assert np.isfinite(float(aux))
    sv = emb.serve(emb.export(p), ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sv), atol=1e-5)


def test_mgqe_head_equals_dpq_when_single_tier(key):
    """One tier with K_1 = K must reduce exactly to DPQ."""
    c_dpq = _mk("dpq")
    c_mgqe = EmbeddingConfig(vocab_size=120, dim=16, kind="mgqe",
                             num_subspaces=4, num_centroids=8,
                             tier_boundaries=(), tier_num_centroids=(8,))
    e1, e2 = Embedding(c_dpq), Embedding(c_mgqe)
    p = e1.init(key)            # identical param structure
    ids = jnp.arange(120)
    o1, a1 = e1.apply(p, ids)
    o2, a2 = e2.apply(p, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


# ------------------------------------------------------------ baselines

@pytest.mark.parametrize("kind,kw", [
    ("full", {}),
    ("lrf", {"rank": 4}),
    ("sq", {"sq_bits": 8}),
    ("hash", {"hash_buckets": 32}),
])
def test_baselines_roundtrip(key, kind, kw):
    cfg = EmbeddingConfig(vocab_size=120, dim=16, kind=kind, **kw)
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.asarray([0, 3, 119])
    out, aux = emb.apply(p, ids)
    assert out.shape == (3, 16) and float(aux) == 0.0
    sv = emb.serve(emb.export(p), ids)
    tol = 0.05 if kind == "sq" else 1e-6     # sq is lossy by design
    np.testing.assert_allclose(np.asarray(out), np.asarray(sv), atol=tol)


def test_sq_export_quantization_error_bounded(key):
    cfg = EmbeddingConfig(vocab_size=200, dim=8, kind="sq", sq_bits=8)
    emb = Embedding(cfg)
    p = emb.init(key)
    ids = jnp.arange(200)
    out, _ = emb.apply(p, ids)
    sv = emb.serve(emb.export(p), ids)
    rng = np.asarray(out).max(0) - np.asarray(out).min(0)
    err = np.abs(np.asarray(out) - np.asarray(sv))
    assert (err <= rng / 255 + 1e-6).all()


# ------------------------------------------------------- size accounting

def test_serving_sizes_match_paper_formulas():
    n, d, D, K = 100_000, 64, 8, 256
    full = EmbeddingConfig(vocab_size=n, dim=d)
    assert full.serving_size_bits() == n * d * 32
    dq = EmbeddingConfig(vocab_size=n, dim=d, kind="dpq",
                         num_subspaces=D, num_centroids=K)
    assert dq.serving_size_bits() == n * D * 8 + 32 * K * d  # §1.1 exactly
    mg = EmbeddingConfig(vocab_size=n, dim=d, kind="mgqe",
                         num_subspaces=D, num_centroids=K,
                         tier_boundaries=(n // 10,),
                         tier_num_centroids=(256, 64))
    head, tail = n // 10, n - n // 10
    expected = head * D * 8 + tail * D * 6 + 32 * K * d
    assert mg.serving_size_bits() == expected
    # the paper's headline: MGQE ~20% of full at these settings
    assert mg.serving_size_bits() / full.serving_size_bits() < 0.25
    assert mg.serving_size_bits() < dq.serving_size_bits()


def test_paper_default_compression_ratio():
    """d=64, D=8, K=256/64 two-tier 10/90 — the §3.4 configuration."""
    for n in (10_000, 100_000, 1_000_000):
        mg = EmbeddingConfig(
            vocab_size=n, dim=64, kind="mgqe", num_subspaces=8,
            num_centroids=256, tier_boundaries=(n // 10,),
            tier_num_centroids=(256, 64))
        ratio = mg.serving_size_bits() / (n * 64 * 32)
        assert ratio < 0.30, (n, ratio)


# ------------------------------------------------------------ partition

def test_rank_by_frequency():
    counts = np.asarray([5, 100, 7, 100, 1])
    remap, inverse = rank_by_frequency(counts)
    assert list(inverse[:2]) == [1, 3]            # ties stable by old id
    assert counts[inverse[0]] >= counts[inverse[-1]]
    assert (remap[inverse] == np.arange(5)).all()


def test_frequency_boundaries_and_validation():
    b = frequency_boundaries(1000, (0.1,))
    assert b == (100,)
    validate_partition(1000, b)
    b3 = frequency_boundaries(1000, (0.05, 0.25))
    assert b3 == (50, 250)
    validate_partition(1000, b3)


@given(st.integers(10, 10_000), st.lists(
    st.floats(0.01, 0.9), min_size=1, max_size=4))
@settings(max_examples=50, deadline=None)
def test_tier_of_ids_matches_searchsorted(vocab, fracs):
    fracs = sorted(set(round(f, 3) for f in fracs))
    bounds = frequency_boundaries(vocab, fracs)
    validate_partition(vocab, bounds)
    ids = np.arange(vocab)
    tiers = tier_of_ids(ids, bounds)
    expected = np.searchsorted(np.asarray(bounds), ids, side="right")
    np.testing.assert_array_equal(np.asarray(tiers), expected)


# ------------------------------------------------- hypothesis invariants

@given(
    vocab=st.integers(20, 300),
    dim_d=st.sampled_from([(8, 2), (16, 4), (32, 8), (24, 3)]),
    k=st.sampled_from([4, 8, 16]),
)
@settings(max_examples=20, deadline=None)
def test_dpq_roundtrip_property(vocab, dim_d, k):
    dim, D = dim_d
    cfg = EmbeddingConfig(vocab_size=vocab, dim=dim, kind="dpq",
                          num_subspaces=D, num_centroids=k)
    emb = Embedding(cfg)
    p = emb.init(jax.random.PRNGKey(vocab))
    ids = jnp.arange(min(vocab, 50))
    out, _ = emb.apply(p, ids)
    sv = emb.serve(emb.export(p), ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sv), atol=1e-5)
    # every code within range
    art = emb.export(p)
    assert int(np.asarray(art["codes"]).max()) < k


@given(
    vocab=st.integers(40, 400),
    head_frac=st.floats(0.05, 0.5),
    k_pair=st.sampled_from([(16, 4), (8, 8), (16, 2), (32, 8)]),
)
@settings(max_examples=20, deadline=None)
def test_mgqe_size_never_exceeds_dpq_property(vocab, head_frac, k_pair):
    """shared-K MGQE is never bigger than same-K DPQ (paper's point)."""
    k1, k2 = k_pair
    bounds = frequency_boundaries(vocab, (head_frac,))
    mg = EmbeddingConfig(vocab_size=vocab, dim=16, kind="mgqe",
                         num_subspaces=4, num_centroids=k1,
                         tier_boundaries=bounds,
                         tier_num_centroids=(k1, k2))
    dq = EmbeddingConfig(vocab_size=vocab, dim=16, kind="dpq",
                         num_subspaces=4, num_centroids=k1)
    assert mg.serving_size_bits() <= dq.serving_size_bits()


def test_k_limit_monotone_distance(key):
    """Masked assign with smaller budget can't find a closer centroid."""
    e = jax.random.normal(key, (20, 4, 4))
    cent = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 4))
    for klim in (2, 4, 8):
        codes = dpq.assign_codes(e, cent, jnp.full((20,), klim))
        assert int(np.asarray(codes).max()) < klim
