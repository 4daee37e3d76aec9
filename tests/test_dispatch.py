"""Backend dispatch layer: resolution rules + pallas(interpret) == xla
parity for every serving hot-path op, across dtypes and ragged
(non-multiple-of-block) batch sizes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Embedding, EmbeddingConfig
from repro.kernels import dispatch

PARITY = dict(rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ resolution

def test_registry_has_all_serving_ops():
    ops = dispatch.registered_ops()
    for name in ("mgqe_decode", "embedding_bag", "pq_score", "dpq_assign",
                 "flash_attention"):
        assert name in ops
        assert set(ops[name]) == {"pallas", "xla", "interpret"}


def test_resolve_precedence(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    on_cpu = jax.default_backend() != "tpu"
    # auto falls back to xla off-TPU; an explicit pallas ask raises
    # there instead of quietly running something else
    if on_cpu:
        assert dispatch.resolve_backend() == "xla"
        with pytest.raises(RuntimeError, match="needs a TPU"):
            dispatch.resolve_backend("pallas")
    assert dispatch.resolve_backend("interpret") == "interpret"
    assert dispatch.resolve_backend("xla") == "xla"
    # env var overrides "auto"/unset but not an explicit concrete choice
    monkeypatch.setenv(dispatch.ENV_VAR, "interpret")
    assert dispatch.resolve_backend() == "interpret"
    assert dispatch.resolve_backend("auto") == "interpret"
    assert dispatch.resolve_backend("xla") == "xla"
    monkeypatch.delenv(dispatch.ENV_VAR)
    # process default is lowest precedence; "auto" arg defers to it
    with dispatch.use_backend("interpret"):
        assert dispatch.resolve_backend() == "interpret"
        assert dispatch.resolve_backend("auto") == "interpret"
        assert dispatch.resolve_backend("xla") == "xla"


def test_resolve_rejects_unknown():
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda")
    with pytest.raises(ValueError):
        dispatch.set_default_backend("nope")
    with pytest.raises(KeyError):
        dispatch.get_impl("not_an_op")


def test_config_rejects_unknown_backend():
    with pytest.raises(ValueError):
        EmbeddingConfig(vocab_size=10, dim=4, kernel_backend="cuda")


# ------------------------------------------------- mgqe_decode parity

@pytest.mark.parametrize("b", [1, 37, 64, 257])   # ragged + exact blocks
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mgqe_decode_backend_parity(b, dtype):
    from repro.kernels.mgqe_decode import decode
    k = jax.random.PRNGKey(b)
    codes = jax.random.randint(k, (b, 4), 0, 16).astype(jnp.uint8)
    cent = jax.random.normal(k, (4, 16, 8)).astype(dtype)
    out_i = decode(codes, cent, block_b=64, backend="interpret")
    out_x = decode(codes, cent, block_b=64, backend="xla")
    assert out_i.shape == out_x.shape == (b, 32)
    np.testing.assert_allclose(np.asarray(out_i, np.float32),
                               np.asarray(out_x, np.float32), **PARITY)


# ----------------------------------------------- embedding_bag parity

@pytest.mark.parametrize("nnz,bags", [(7, 5), (64, 64), (201, 13)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [False, True])
def test_embedding_bag_backend_parity(nnz, bags, dtype, weighted):
    from repro.kernels.embedding_bag import bag
    rng = np.random.default_rng(nnz)
    table = jnp.asarray(rng.normal(size=(50, 8))).astype(dtype)
    ids = jnp.asarray(rng.integers(0, 50, nnz), jnp.int32)
    seg = jnp.asarray(np.sort(rng.integers(0, bags, nnz)), jnp.int32)
    w = (jnp.asarray(rng.uniform(0.5, 2.0, nnz)).astype(dtype)
         if weighted else None)
    out_i = bag(table, ids, seg, bags, w, backend="interpret")
    out_x = bag(table, ids, seg, bags, w, backend="xla")
    assert out_i.shape == out_x.shape == (bags, 8)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else PARITY
    np.testing.assert_allclose(np.asarray(out_i, np.float32),
                               np.asarray(out_x, np.float32), **tol)


# --------------------------------------------------- pq_score parity

@pytest.mark.parametrize("n", [1, 33, 512, 1025])  # ragged + exact blocks
@pytest.mark.parametrize("cdtype", [jnp.uint8, jnp.int32])
def test_pq_score_backend_parity(n, cdtype):
    from repro.kernels import dispatch as dp
    k = jax.random.PRNGKey(n)
    codes = jax.random.randint(k, (n, 8), 0, 32).astype(cdtype)
    lut = jax.random.normal(k, (8, 32))
    out_i = dp.dispatch("pq_score", lut, codes.astype(jnp.int32),
                        block_n=512, backend="interpret")
    out_x = dp.dispatch("pq_score", lut, codes.astype(jnp.int32),
                        block_n=512, backend="xla")
    assert out_i.shape == out_x.shape == (n,)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_x),
                               **PARITY)


def test_score_candidates_backend_kwarg():
    from repro.kernels.pq_score import score_candidates
    k = jax.random.PRNGKey(0)
    cent = jax.random.normal(k, (4, 8, 4))
    codes = jax.random.randint(k, (100, 4), 0, 8)
    q = jax.random.normal(jax.random.PRNGKey(1), (16,))
    a = score_candidates(q, cent, codes, block_n=32, backend="interpret")
    b = score_candidates(q, cent, codes, block_n=32, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **PARITY)


# -------------------------------------------------- dpq_assign parity

@pytest.mark.parametrize("b", [1, 100, 513])
def test_dpq_assign_backend_parity(b):
    from repro.kernels.dpq_assign import assign
    k = jax.random.PRNGKey(b)
    e = jax.random.normal(k, (b, 4, 8))
    cent = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 8))
    klim = jax.random.randint(jax.random.PRNGKey(2), (b,), 1, 17)
    for lim in (None, klim):
        out_i = assign(e, cent, lim, block_b=128, backend="interpret")
        out_x = assign(e, cent, lim, block_b=128, backend="xla")
        np.testing.assert_array_equal(np.asarray(out_i), np.asarray(out_x))


# ------------------------------------------- Embedding.serve invariance

def _serve_cfgs():
    common = dict(vocab_size=120, dim=16, num_subspaces=4, num_centroids=8,
                  decode_block_b=32)
    return [
        EmbeddingConfig(kind="dpq", **common),
        EmbeddingConfig(kind="mgqe", tier_boundaries=(12,),
                        tier_num_centroids=(8, 4), **common),
        EmbeddingConfig(kind="mgqe", mgqe_variant="private_k",
                        tier_boundaries=(12,), tier_num_centroids=(8, 4),
                        **common),
        EmbeddingConfig(kind="mgqe", mgqe_variant="private_d",
                        tier_boundaries=(12,), tier_num_subspaces=(4, 2),
                        **common),
    ]


@pytest.mark.parametrize("cfg", _serve_cfgs(),
                         ids=lambda c: f"{c.kind}-{c.mgqe_variant}")
def test_embedding_serve_invariant_across_backends(cfg):
    """serve() output must be bitwise-comparable (1e-5) under every
    backend — the dispatch layer must never change model outputs."""
    ids = jnp.asarray([[0, 5, 11], [12, 63, 119]])   # ragged B=6 decode
    outs = {}
    backends = ("xla", "interpret", "auto", "pallas")
    if jax.default_backend() != "tpu":
        backends = backends[:-1]          # pallas raises off-TPU
    for be in backends:
        emb = Embedding(dataclasses.replace(cfg, kernel_backend=be))
        params = emb.init(jax.random.PRNGKey(0))
        art = emb.export(params)
        outs[be] = np.asarray(emb.serve(art, ids))
        assert outs[be].shape == (2, 3, 16)
    for be, out in outs.items():
        np.testing.assert_allclose(out, outs["xla"], err_msg=be, **PARITY)


def test_embedding_serve_respects_env_override(monkeypatch):
    """REPRO_KERNEL_BACKEND must steer a default ("auto") config
    end-to-end through Embedding.serve."""
    calls = {}
    orig = dispatch.get_impl

    def spy(name, backend=None):
        calls.setdefault(name, []).append(dispatch.resolve_backend(backend))
        return orig(name, backend)

    cfg = _serve_cfgs()[0]                       # kernel_backend="auto"
    emb = Embedding(cfg)
    params = emb.init(jax.random.PRNGKey(0))
    art = emb.export(params)
    monkeypatch.setenv(dispatch.ENV_VAR, "interpret")
    monkeypatch.setattr(dispatch, "get_impl", spy)
    emb.serve(art, jnp.arange(8))
    assert "interpret" in calls.get("mgqe_decode", [])


# ---------------------------------------- fields bag through dispatch

@pytest.mark.parametrize("mode", ["sum", "mean", "max"])
def test_fields_embedding_bag_backend_parity(mode):
    from repro.models.recsys.fields import embedding_bag
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.normal(size=(30, 8)), jnp.float32)
    nnz = 23                                          # ragged
    ids = jnp.asarray(rng.integers(0, 30, nnz), jnp.int32)
    seg = jnp.asarray(np.sort(rng.integers(0, 7, nnz)), jnp.int32)
    a = embedding_bag(table, ids, seg, 7, mode=mode, backend="interpret")
    b = embedding_bag(table, ids, seg, 7, mode=mode, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **PARITY)
