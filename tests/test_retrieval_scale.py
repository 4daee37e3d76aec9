"""Retrieval at scale (DESIGN.md §12): streamed-vs-one-shot build
bit-parity, the bounded chained list layout, host-staged serving, and
the √N nlist heuristic."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.engine import RetrievalEngine
from repro.retrieval import (INVALID_ID, IndexConfig, build_flat_artifact,
                             build_ivf_artifact, get_index, suggest_nlist)
from repro.retrieval.ivf_pq import bounded_list_layout
from tests._hypothesis_compat import given, settings, st

_N, _D = 403, 16            # deliberately not a multiple of any block


def _vectors(n=_N, d=_D, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)) * 2.0
    return (centers[rng.integers(0, 8, n)]
            + 0.2 * rng.normal(size=(n, d))).astype(np.float32)


_VECS = _vectors()


def _cfg(kind, **kw):
    base = dict(num_subspaces=4, num_centroids=16, iters=3)
    if kind == "ivf_pq":
        base |= dict(nlist=8, nprobe=8, coarse_iters=3)
    return IndexConfig(kind=kind, **(base | kw))


_BUILDERS = {"flat_pq": build_flat_artifact, "ivf_pq": build_ivf_artifact}


def _build(cfg, vecs=_VECS):
    return _BUILDERS[cfg.kind](jax.random.PRNGKey(7), vecs, cfg)


# one-shot references, cached per (kind, sample) so the hypothesis
# property does not refit codebooks on every drawn block size
_ONE_SHOT = {}


def _one_shot(kind, sample):
    if (kind, sample) not in _ONE_SHOT:
        art, _ = _build(_cfg(kind, train_sample=sample))
        _ONE_SHOT[(kind, sample)] = art
    return _ONE_SHOT[(kind, sample)]


# --------------------------------------------- satellite: nlist heuristic

def test_suggest_nlist_tracks_sqrt_n():
    # the old serve.py heuristic min(64, n // 64) hard-capped at 64,
    # leaving a 10M corpus with 156k-row lists
    assert suggest_nlist(10_000_000) == 3162
    assert suggest_nlist(1_000_000) == 1000
    assert suggest_nlist(100) == 10
    # clamps: never below nprobe (config validity), never above n
    assert suggest_nlist(100, nprobe=32) == 32
    assert suggest_nlist(10, nprobe=8) == 8
    assert suggest_nlist(4, nprobe=8) == 4
    assert suggest_nlist(0) == 1
    # the suggestion always yields a valid config
    IndexConfig(kind="ivf_pq", nlist=suggest_nlist(5000, 8), nprobe=8)


def test_index_config_rejects_bad_scale_knobs():
    for bad in (dict(train_sample=-1), dict(encode_block=-8),
                dict(list_cap_quantile=0.0), dict(list_cap_quantile=1.5)):
        with pytest.raises(ValueError):
            _cfg("ivf_pq", **bad)


# ------------------------------------------ streamed == one-shot parity

@pytest.mark.parametrize("kind", ["flat_pq", "ivf_pq"])
def test_streamed_build_matches_one_shot(kind):
    """Blocked encode + sampled fit are bit-identical to the one-shot
    build at equal sample settings, for any block size — including
    block=1, non-dividing blocks, and blocks larger than the corpus."""
    for sample in (0, 64):
        ref = _one_shot(kind, sample)
        for block in (1, 3, 64, 100, _N, 5 * _N):
            art, stats = _build(_cfg(kind, train_sample=sample,
                                     encode_block=block))
            assert sorted(art) == sorted(ref)
            for name in ref:
                np.testing.assert_array_equal(
                    np.asarray(art[name]), np.asarray(ref[name]),
                    err_msg=f"{kind}/{name} block={block} sample={sample}")
            assert stats.blocks == -(-_N // min(block, _N))
            assert stats.sample_rows == (sample or _N)


@settings(deadline=None, max_examples=8)
@given(st.integers(min_value=1, max_value=_N + 50),
       st.sampled_from([0, 97]))
def test_streamed_build_parity_property(block, sample):
    ref = _one_shot("ivf_pq", sample)
    art, _ = _build(_cfg("ivf_pq", train_sample=sample,
                         encode_block=block))
    for name in ref:
        np.testing.assert_array_equal(np.asarray(art[name]),
                                      np.asarray(ref[name]))


def test_build_stats_peak_is_block_bounded():
    vecs = _vectors(8192, 16, seed=1)
    cfg = _cfg("ivf_pq", nlist=16, train_sample=1024, encode_block=512)
    art, stats = build_ivf_artifact(jax.random.PRNGKey(0), vecs, cfg)
    assert stats.blocks == 16 and stats.block_rows == 512
    assert stats.sample_rows == 1024
    assert stats.peak_device_ok
    # staged bytes stay below the corpus — the point of streaming
    assert stats.peak_device_bytes < vecs.nbytes
    # the bound is corpus-independent: 4x the rows, same bound
    vecs4 = _vectors(32768, 16, seed=2)
    _, stats4 = build_ivf_artifact(jax.random.PRNGKey(0), vecs4, cfg)
    assert stats4.device_bound_bytes == stats.device_bound_bytes
    # list tables come back host-resident; placement is the caller's
    assert isinstance(art["list_codes"], np.ndarray)
    assert isinstance(art["list_ids"], np.ndarray)


def test_build_rejects_undersized_corpus_or_sample():
    vecs = _vectors(32)
    with pytest.raises(ValueError, match="nlist"):
        build_ivf_artifact(jax.random.PRNGKey(0), vecs,
                           _cfg("ivf_pq", nlist=64, nprobe=8))
    with pytest.raises(ValueError, match="train_sample"):
        build_ivf_artifact(jax.random.PRNGKey(0), vecs,
                           _cfg("ivf_pq", nlist=16, train_sample=8))


# ------------------------------------------------- bounded list layout

def test_bounded_layout_bytes_on_skewed_assignment():
    """On a Zipf-skewed assignment the quantile-capped chained layout
    stays within a constant factor of the ideal bytes; the old
    pad-to-longest layout blows up by the max/mean list ratio."""
    rng = np.random.default_rng(0)
    nlist, n, D = 64, 20_000, 8
    w = 1.0 / np.arange(1, nlist + 1) ** 1.1
    assign = rng.choice(nlist, size=n, p=w / w.sum()).astype(np.int64)
    codes = rng.integers(0, 256, size=(n, D)).astype(np.uint8)
    lay = bounded_list_layout(assign, codes, nlist, 0.9)
    counts = np.bincount(assign, minlength=nlist)
    ideal = n * D
    padded = nlist * int(counts.max()) * D     # the old layout's bytes
    assert padded >= 8 * ideal                 # skew really blows it up
    assert lay["list_codes"].nbytes <= 4 * ideal
    assert lay["list_codes"].nbytes * 2 < padded
    # the layout is a faithful inverse: every corpus row appears exactly
    # once, carrying its own codes
    ids = lay["list_ids"]
    valid = ids != INVALID_ID
    np.testing.assert_array_equal(np.sort(ids[valid]), np.arange(n))
    np.testing.assert_array_equal(lay["list_codes"][valid],
                                  codes[ids[valid]])
    # each base list's chain holds exactly its members
    chain = lay["list_chain"]
    for l in range(nlist):
        rows = chain[l][chain[l] >= 0]
        members = ids[rows][ids[rows] != INVALID_ID]
        assert members.size == counts[l]
        assert (assign[members] == l).all()
    # spill padding keeps the row-sharding divisibility invariant
    assert lay["list_codes"].shape[0] % nlist == 0


def test_quantile_one_reproduces_pad_to_max():
    rng = np.random.default_rng(1)
    nlist, n, D = 8, 500, 4
    assign = rng.integers(0, nlist, n)
    codes = rng.integers(0, 256, size=(n, D)).astype(np.uint8)
    lay = bounded_list_layout(assign, codes, nlist, 1.0)
    counts = np.bincount(assign, minlength=nlist)
    assert lay["list_chain"].shape == (nlist, 1)
    assert lay["list_codes"].shape == (nlist, counts.max(), D)
    np.testing.assert_array_equal(lay["list_chain"][:, 0],
                                  np.arange(nlist))


def test_spilled_layout_search_matches_padded_layout():
    """Tight caps force spill chains; search results must be EXACTLY
    the pad-to-max layout's (same scores, same ids, same order)."""
    vecs = jnp.asarray(_vectors(2048, 16, seed=3))
    q = jnp.asarray(np.random.default_rng(9).normal(
        size=(6, 16)).astype(np.float32))
    outs = {}
    for quant in (1.0, 0.5):
        cfg = _cfg("ivf_pq", nlist=16, nprobe=16,
                   list_cap_quantile=quant)
        idx = get_index(cfg)
        art = idx.build(jax.random.PRNGKey(5), vecs)
        if quant < 1.0:
            assert art["list_chain"].shape[1] > 1   # chains really spill
        outs[quant] = idx.search(art, q, 50)
    np.testing.assert_array_equal(np.asarray(outs[1.0][0]),
                                  np.asarray(outs[0.5][0]))
    np.testing.assert_array_equal(np.asarray(outs[1.0][1]),
                                  np.asarray(outs[0.5][1]))


# ---------------------------------------------------- host-staged serving

def test_host_staged_search_matches_device_search():
    vecs = _vectors(1024, 16, seed=4)
    cfg = _cfg("ivf_pq", nlist=16, nprobe=4)
    idx = get_index(cfg)
    art_host, _ = build_ivf_artifact(jax.random.PRNGKey(1), vecs, cfg)
    art_dev = {name: jnp.asarray(v) for name, v in art_host.items()}
    q = jnp.asarray(np.random.default_rng(2).normal(
        size=(5, 16)).astype(np.float32))
    ref_s, ref_i = idx.search(art_dev, q, 20)
    s, i = idx.search_host_staged(art_host, q, 20)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
    assert idx.staged_bytes > 0


def test_host_staged_engine_bit_identical_and_bounded_upload():
    vecs = _vectors(8192, 16, seed=5)
    cfg = _cfg("ivf_pq", nlist=256, nprobe=2, host_staged=True)
    idx = get_index(cfg)
    art_host, _ = build_ivf_artifact(jax.random.PRNGKey(1), vecs, cfg)
    eng = RetrievalEngine(idx, art_host, k=20, block_q=4)
    assert eng.host_staged
    assert isinstance(eng.artifact["list_codes"], np.ndarray)
    ref_idx = get_index(dataclasses.replace(cfg, host_staged=False))
    ref_eng = RetrievalEngine(
        ref_idx, {name: jnp.asarray(v) for name, v in art_host.items()},
        k=20, block_q=4)
    rng = np.random.default_rng(2)
    reqs = [rng.normal(size=(b, 16)).astype(np.float32) for b in (5, 3)]
    hs = [eng.submit(r) for r in reqs]
    rhs = [ref_eng.submit(r) for r in reqs]
    outs, ref_outs = eng.flush(), ref_eng.flush()
    for h, rh in zip(hs, rhs):
        np.testing.assert_array_equal(np.asarray(outs[h][0]),
                                      np.asarray(ref_outs[rh][0]))
        np.testing.assert_array_equal(np.asarray(outs[h][1]),
                                      np.asarray(ref_outs[rh][1]))
    # the flush staged only probed lists — far below the full tables
    table_mb = (art_host["list_codes"].nbytes
                + art_host["list_ids"].nbytes) / 1e6
    assert 0 < eng.staged_mbytes < table_mb


def test_host_staged_engine_rejects_flat_and_mesh():
    vecs = jnp.asarray(_vectors(256, 16, seed=6))
    fidx = get_index(_cfg("flat_pq"))
    fart = fidx.build(jax.random.PRNGKey(0), vecs)
    with pytest.raises(ValueError, match="host-staged"):
        RetrievalEngine(fidx, fart, k=10, host_staged=True)
    iidx = get_index(_cfg("ivf_pq", host_staged=True))
    iart = iidx.build(jax.random.PRNGKey(0), vecs)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="single-device"):
        RetrievalEngine(iidx, iart, k=10, mesh=mesh)


# --------------------------------------------------------- 1M-row recall

@pytest.mark.slow
def test_one_million_row_recall_and_peak():
    """End-to-end scale check (the bench gate's settings): streamed 1M
    build with bounded peak device bytes, recall@100 >= 0.95 at the
    largest swept nprobe."""
    from repro.data.synthetic import pq_clustered_corpus
    n = 1_000_000
    vecs, q = pq_clustered_corpus(n=n, n_clusters=1024,
                                  cluster_zipf_a=1.3)
    nlist = suggest_nlist(n, 128)
    cfg = IndexConfig(kind="ivf_pq", num_subspaces=8, num_centroids=128,
                      iters=10, coarse_iters=10, nlist=nlist, nprobe=128,
                      train_sample=131_072, encode_block=131_072,
                      list_cap_quantile=0.9)
    art, stats = build_ivf_artifact(jax.random.PRNGKey(42), vecs, cfg)
    assert stats.peak_device_ok
    assert stats.peak_device_bytes < vecs.nbytes // 2
    idx = get_index(cfg)
    art_dev = {name: jnp.asarray(v) for name, v in art.items()}
    _, ids = jax.jit(lambda a, qq: idx.search(a, qq, 100))(
        art_dev, jnp.asarray(q))
    ids = np.asarray(ids)
    exact = np.argsort(-(q @ vecs.T), axis=1)[:, :100]
    recall = float(np.mean([np.isin(ids[b], exact[b]).mean()
                            for b in range(q.shape[0])]))
    assert recall >= 0.95, f"recall@100 {recall:.3f} at nprobe=128"
