"""Retrieval subsystem (DESIGN.md §8): index registry conformance,
deterministic top-k merging, ADC exactness, IVF recall, engines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.retrieval import (INVALID_ID, IndexConfig, get_index,
                             index_class, merge_topk, register_index,
                             registered_index_kinds, topk_by_position)
from tests._hypothesis_compat import given, settings, st


def _corpus(n=512, d=16, seed=0):
    k = jax.random.PRNGKey(seed)
    centers = jax.random.normal(k, (16, d)) * 2.0
    assign = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0, 16)
    return centers[assign] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (n, d))


# ------------------------------------------------------------ registry

@pytest.mark.parametrize("kind", registered_index_kinds())
def test_index_conformance_build_search(kind):
    """Every registered kind: build -> batched search returns (B, k)
    descending scores with in-range (or pad) ids matching a re-scan."""
    cfg = index_class(kind).probe_config()
    index = get_index(cfg)
    vecs = _corpus()
    art = index.build(jax.random.PRNGKey(0), vecs)
    q = jax.random.normal(jax.random.PRNGKey(3), (5, vecs.shape[1]))
    s, i = index.search(art, q, 7)
    assert s.shape == (5, 7) and i.shape == (5, 7)
    s_np, i_np = np.asarray(s), np.asarray(i)
    assert (np.diff(s_np, axis=1) <= 1e-6).all(), "scores must descend"
    valid = i_np != INVALID_ID
    assert valid.all()                     # 512 candidates >> k
    assert ((i_np >= 0) & (i_np < vecs.shape[0])).all()
    # no duplicate candidates within a query's result list
    for row in i_np:
        assert len(set(row.tolist())) == row.size


def test_index_registry_errors():
    with pytest.raises(KeyError):
        IndexConfig(kind="nope")
    with pytest.raises(ValueError):
        IndexConfig(kind="ivf_pq", nprobe=0)
    with pytest.raises(ValueError):
        IndexConfig(kind="ivf_pq", nlist=4, nprobe=8)
    with pytest.raises(ValueError):       # duplicate registration
        from repro.retrieval.base import Index

        @register_index("flat_pq")
        class Impostor(Index):
            pass


def test_index_artifact_shard_specs_rows_only():
    from jax.sharding import PartitionSpec as P
    vecs = _corpus()
    for kind in registered_index_kinds():
        index = get_index(index_class(kind).probe_config())
        art = index.build(jax.random.PRNGKey(0), vecs)
        specs = index.artifact_shard_specs(art)
        assert set(specs) == set(art)
        for name, spec in specs.items():
            if name in index.rows_leaves:
                assert spec[0] == "model", (kind, name)
            else:
                assert spec == P(), (kind, name)


# -------------------------------------------------- ADC exactness (sat)

def test_flat_pq_scores_equal_decoded_lut_summation():
    """flat_pq batched scores == dense dot products against the
    DECODED corpus to 1e-5 — ADC's LUT summation is exact for the dot
    product, per subspace, up to float error."""
    from repro.kernels.mgqe_decode.ref import mgqe_decode_ref
    vecs = _corpus(n=300)
    index = get_index(IndexConfig(kind="flat_pq", num_subspaces=4,
                                  num_centroids=32, iters=5))
    art = index.build(jax.random.PRNGKey(0), vecs)
    q = jax.random.normal(jax.random.PRNGKey(3), (6, vecs.shape[1]))
    scores = np.asarray(index.scores(art, q))                 # (B, N)
    decoded = mgqe_decode_ref(art["codes"].astype(jnp.int32),
                              art["centroids"])               # (N, d)
    ref = np.asarray(q @ decoded.T)
    np.testing.assert_allclose(scores, ref, atol=1e-5)
    # and search() is exactly the top-k of that matrix
    s, i = index.search(art, q, 9)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :9]
    np.testing.assert_array_equal(np.asarray(i), order)


def test_pq_score_ops_accept_stored_uint8_codes():
    """The dispatch layer takes codes at their stored dtype — no
    eager int32 upcast of the O(vocab) table on the hot path (sat)."""
    from repro.kernels.pq_score import (score_candidates,
                                        score_candidates_batched,
                                        topk_candidates)
    cent = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 4))
    codes8 = jax.random.randint(jax.random.PRNGKey(1), (100, 4), 0, 16
                                ).astype(jnp.uint8)
    q = jax.random.normal(jax.random.PRNGKey(2), (3, 16))
    for backend in ("xla", "interpret"):
        a = score_candidates(q[0], cent, codes8, backend=backend,
                             block_n=32)
        b = score_candidates(q[0], cent, codes8.astype(jnp.int32),
                             backend=backend, block_n=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        ab = score_candidates_batched(q, cent, codes8, backend=backend,
                                      block_n=32)
        np.testing.assert_allclose(np.asarray(ab[0]), np.asarray(a),
                                   atol=1e-5)
        ts, ti = topk_candidates(q, cent, codes8, 5, backend=backend,
                                 block_n=32)
        assert ts.shape == (3, 5) and ti.dtype == jnp.int32


def test_pq_topk_kernel_matches_ref_and_pads():
    from repro.kernels.pq_score import pq_topk, pq_topk_ref
    cent_k = 16
    luts = jax.random.normal(jax.random.PRNGKey(0), (4, 6, cent_k))
    codes = jax.random.randint(jax.random.PRNGKey(1), (257, 6), 0,
                               cent_k).astype(jnp.uint8)
    ks, ki = pq_topk(luts.astype(jnp.float32), codes, 10, block_n=64,
                     interpret=True)
    rs, ri = pq_topk_ref(luts.astype(jnp.float32), codes, 10)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(rs), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    # k > N: both pad with (-inf, INVALID_ID)
    ks, ki = pq_topk(luts.astype(jnp.float32), codes[:3], 5,
                     block_n=64, interpret=True)
    assert (np.asarray(ks)[:, 3:] == -np.inf).all()
    assert (np.asarray(ki)[:, 3:] == INVALID_ID).all()


# ------------------------------------------------- top-k merge property

def _reference_topk(scores, k):
    """Single-device canonical top-k: (score desc, id asc)."""
    ids = jnp.broadcast_to(jnp.arange(scores.shape[-1]), scores.shape)
    return merge_topk(scores, ids, k)


def _sharded_merge(scores, splits, k):
    """Split the candidate axis arbitrarily, local top-k per shard
    (ids global), then merge — the sharded driver's algebra."""
    parts, start = [], 0
    for size in splits:
        part = scores[..., start:start + size]
        ids = jnp.broadcast_to(
            jnp.arange(start, start + size), part.shape)
        parts.append(merge_topk(part, ids, k))
        start += size
    s_cat = jnp.concatenate([s for s, _ in parts], axis=-1)
    i_cat = jnp.concatenate([i for _, i in parts], axis=-1)
    return merge_topk(s_cat, i_cat, k)


def test_sharded_merge_equals_topk_seeded():
    """Seeded splits incl. tie-heavy inputs: merged per-shard top-k ==
    single-device top-k, bit for bit."""
    rng = np.random.default_rng(0)
    for trial in range(8):
        n = int(rng.integers(3, 60))
        k = int(rng.integers(1, n + 5))
        # half the trials draw from 4 discrete values: dense ties
        if trial % 2:
            scores = jnp.asarray(
                rng.choice([0.0, 1.0, -1.0, 0.5], size=(3, n)),
                jnp.float32)
        else:
            scores = jnp.asarray(rng.normal(size=(3, n)), jnp.float32)
        cuts = sorted(rng.choice(n + 1, size=int(rng.integers(0, 4))))
        splits = np.diff([0] + list(cuts) + [n]).astype(int)
        splits = [int(s) for s in splits if s > 0] or [n]
        ref_s, ref_i = _reference_topk(scores, k)
        out_s, out_i = _sharded_merge(scores, splits, k)
        np.testing.assert_array_equal(np.asarray(out_s),
                                      np.asarray(ref_s), err_msg=str(
                                          (trial, splits, k)))
        np.testing.assert_array_equal(np.asarray(out_i),
                                      np.asarray(ref_i))
        # lax.top_k agrees wherever it defines the same contract
        # (ids ascend along the axis -> position tiebreak == id)
        if k <= n:
            ts, _, ti = topk_by_position(scores, jnp.broadcast_to(
                jnp.arange(n), scores.shape), k)
            np.testing.assert_array_equal(np.asarray(ts),
                                          np.asarray(ref_s))
            np.testing.assert_array_equal(np.asarray(ti),
                                          np.asarray(ref_i))


@settings(deadline=None, max_examples=40)
@given(st.lists(st.floats(min_value=-100, max_value=100, width=32)
                .map(lambda x: round(x, 1)),   # rounded -> frequent ties
                min_size=1, max_size=40),
       st.integers(min_value=1, max_value=45),
       st.data())
def test_sharded_merge_equals_topk_property(values, k, data):
    """Hypothesis: for ANY scores (ties included) and ANY shard split,
    merging per-shard top-k lists == the single-device top-k."""
    n = len(values)
    cut_count = data.draw(st.integers(min_value=0, max_value=min(4, n)))
    cuts = sorted(data.draw(st.lists(
        st.integers(min_value=0, max_value=n), min_size=cut_count,
        max_size=cut_count)))
    splits = [int(s) for s in np.diff([0] + cuts + [n]) if s > 0] or [n]
    scores = jnp.asarray(values, jnp.float32)[None]
    ref = _reference_topk(scores, k)
    out = _sharded_merge(scores, splits, k)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


# -------------------------------------------------------------- recall

def _recall(ids, ex_ids, k):
    ids = np.asarray(ids)
    return float(np.mean([
        len(set(ids[b].tolist()) & set(ex_ids[b].tolist())) / k
        for b in range(ids.shape[0])]))


def _recall_vs_dense(n, nlist, nprobe, k=100):
    from repro.data.synthetic import pq_clustered_corpus
    vecs_np, q_np = pq_clustered_corpus(n=n, n_clusters=nlist)
    vecs, q = jnp.asarray(vecs_np), jnp.asarray(q_np)
    ex_ids = np.argsort(-(q_np @ vecs_np.T), axis=1)[:, :k]
    out = {}
    for kind, kw in (("flat_pq", {}),
                     ("ivf_pq", dict(nlist=nlist, nprobe=nprobe))):
        index = get_index(IndexConfig(kind=kind, num_subspaces=8,
                                      num_centroids=128, iters=15,
                                      coarse_iters=15, **kw))
        art = index.build(jax.random.PRNGKey(42), vecs)
        _, ids = index.search(art, q, k)
        out[kind] = _recall(ids, ex_ids, k)
    return out


def test_retrieval_recall_vs_dense_scan():
    """flat_pq is (near-)exact on a PQ-representable corpus; ivf_pq at
    nprobe = nlist/8 keeps recall@100 >= 0.95 vs the dense scan."""
    rec = _recall_vs_dense(n=20_000, nlist=64, nprobe=8)
    assert rec["flat_pq"] >= 0.99, rec
    assert rec["ivf_pq"] >= 0.95, rec


@pytest.mark.slow
def test_retrieval_recall_100k_acceptance():
    """The acceptance-scale run: 100k-item corpus, nprobe = nlist/8."""
    rec = _recall_vs_dense(n=100_000, nlist=64, nprobe=8)
    assert rec["flat_pq"] >= 0.99, rec
    assert rec["ivf_pq"] >= 0.95, rec


# -------------------------------------------------------------- engine

def test_retrieval_engine_microbatches_and_returns_right_request():
    from repro.launch.engine import RetrievalEngine
    vecs = _corpus()
    index = get_index(IndexConfig(kind="ivf_pq", num_subspaces=4,
                                  num_centroids=16, nlist=8, nprobe=8,
                                  iters=5))
    art = index.build(jax.random.PRNGKey(0), vecs)
    eng = RetrievalEngine(index, art, k=10, block_q=8)
    rng = np.random.default_rng(0)
    q_a = rng.normal(size=(3, 16)).astype(np.float32)
    q_b = rng.normal(size=(16,)).astype(np.float32)   # 1-D request
    h_a = eng.submit(q_a)
    s_b, i_b = eng.search(q_b)        # queue non-empty: must return b's
    assert s_b.shape == (1, 10)
    ref_s, ref_i = index.search(art, jnp.asarray(q_b)[None], 10)
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(ref_i))
    # h_a was flushed in the same micro-batch
    assert eng.pending == 0
    st_ = eng.stats()
    assert st_.requests == 2 and st_.lookups == 4 and st_.flushes == 1
    assert st_.padded_lookups % eng.pad_multiple == 0
    ref_a = index.search(art, jnp.asarray(q_a), 10)
    h_c = eng.submit(q_a)
    outs = eng.flush()
    np.testing.assert_array_equal(np.asarray(outs[h_c][1]),
                                  np.asarray(ref_a[1]))
    del h_a


def test_engine_stats_zero_guard():
    """Empty/instant streams report 0.0 lookups/s, never divide by
    zero (sat)."""
    from repro.launch.engine import EngineStats
    st_ = EngineStats()
    assert st_.lookups_per_s == 0.0
    assert st_.as_dict()["lookups_per_s"] == 0.0
    st_.lookups, st_.seconds = 100, 0.0     # instant stream
    assert st_.lookups_per_s == 0.0


def test_retrieval_engine_rejects_bad_mesh_configs():
    from repro.launch.engine import RetrievalEngine
    vecs = _corpus(n=96)
    index = get_index(IndexConfig(kind="flat_pq", num_subspaces=4,
                                  num_centroids=16, iters=3))
    art = index.build(jax.random.PRNGKey(0), vecs)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="model"):
        RetrievalEngine(index, art, k=5, mesh=mesh)


# ------------------------------------------------------- two-tower wire

def test_two_tower_retrieval_topk_matches_dense_order():
    from repro.configs.registry import get_arch
    from repro.models.recsys.two_tower import TwoTower
    _, cfg = get_arch("two-tower-retrieval", smoke=True)
    model = TwoTower(cfg)
    params = model.init(jax.random.PRNGKey(0))
    item_ids = jnp.arange(400, dtype=jnp.int32)
    index, art = model.build_index(
        jax.random.PRNGKey(1), params, item_ids,
        IndexConfig(kind="flat_pq", num_subspaces=8, num_centroids=64,
                    iters=10))
    users = jnp.arange(4, dtype=jnp.int32)
    scores, ids = model.retrieval_topk(params, index, art, users, 20)
    assert scores.shape == (4, 20) and ids.shape == (4, 20)
    # high overlap with the exact dense scan (quantization-limited)
    vecs = model.encode_items(params, item_ids)
    u, _ = model.user_vec(params, users)
    ex = np.argsort(-np.asarray(u @ vecs.T), axis=1)[:, :20]
    assert _recall(ids, ex, 20) >= 0.5
    # and the single-query compat path still serves
    s1 = model.retrieval_scores_adc(params, art, users[:1])
    assert s1.shape == (400,)
