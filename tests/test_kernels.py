"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes and
dtypes, in interpret mode (kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dpq_assign import dpq_assign, dpq_assign_ref
from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.flash_attention import (attend, flash_attention,
                                           flash_attention_ref)
from repro.kernels.mgqe_decode import (decode_stages, mgqe_decode,
                                       mgqe_decode_ref, rq_decode_stages,
                                       rq_decode_stages_ref)
from repro.kernels.pq_score import build_lut_ref, pq_score, pq_score_ref


# ----------------------------------------------------------- mgqe_decode

@pytest.mark.parametrize("b,d,k,s", [
    (1, 4, 8, 4), (100, 8, 256, 8), (257, 16, 64, 4), (64, 4, 16, 32),
])
@pytest.mark.parametrize("cdtype", [jnp.uint8, jnp.int32])
def test_mgqe_decode_matches_ref(b, d, k, s, cdtype):
    if k > 256 and cdtype == jnp.uint8:
        pytest.skip("uint8 can't hold K>256")
    kk = jax.random.PRNGKey(b * 7 + d)
    codes = jax.random.randint(kk, (b, d), 0, k).astype(cdtype)
    cent = jax.random.normal(kk, (d, k, s))
    out = mgqe_decode(codes, cent, block_b=64, interpret=True)
    ref = mgqe_decode_ref(codes, cent)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mgqe_decode_dtypes(dtype):
    kk = jax.random.PRNGKey(0)
    codes = jax.random.randint(kk, (33, 4), 0, 16).astype(jnp.uint8)
    cent = jax.random.normal(kk, (4, 16, 8)).astype(dtype)
    out = mgqe_decode(codes, cent, block_b=16, interpret=True)
    ref = mgqe_decode_ref(codes, cent)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-2)


# ------------------------------------------------------ rq_decode_stages

@pytest.mark.parametrize("b,m,k,d", [
    (1, 1, 8, 4),          # M=1 degenerate: single stage, no summing
    (37, 3, 16, 8),        # odd batch (block padding path)
    (256, 4, 256, 64),     # exact block, full uint8 code range
    (100, 2, 8, 16),
])
def test_rq_decode_stages_matches_ref(b, m, k, d):
    kk = jax.random.PRNGKey(b * 13 + m)
    codes = jax.random.randint(kk, (b, m), 0, k).astype(jnp.uint8)
    cbs = jax.random.normal(kk, (m, k, d))
    out = rq_decode_stages(codes, cbs, block_b=64, interpret=True)
    ref = rq_decode_stages_ref(codes, cbs)
    assert out.shape == (b, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("block_d", [None, 8, 16, 7])   # 7: non-divisor
def test_rq_decode_stages_block_d_tiling(block_d):
    kk = jax.random.PRNGKey(5)
    codes = jax.random.randint(kk, (70, 3), 0, 8).astype(jnp.uint8)
    cbs = jax.random.normal(kk, (3, 8, 16))
    out = rq_decode_stages(codes, cbs, block_b=32, block_d=block_d,
                           interpret=True)
    ref = rq_decode_stages_ref(codes, cbs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("b", [1, 37, 64, 257])
def test_decode_stages_backend_parity(b):
    """Fused (dispatched) vs unfused-per-stage at 1e-5 on every backend
    this host can run (pallas needs a TPU; off-TPU it raises)."""
    kk = jax.random.PRNGKey(b)
    codes = jax.random.randint(kk, (b, 3), 0, 16).astype(jnp.uint8)
    cbs = jax.random.normal(kk, (3, 16, 8))
    unfused = sum(np.asarray(jnp.take(cbs[i], codes[:, i].astype(jnp.int32),
                                      axis=0))
                  for i in range(3))
    backends = ("xla", "interpret")
    if jax.default_backend() == "tpu":
        backends += ("pallas",)
    for backend in backends:
        out = decode_stages(codes, cbs, block_b=64, backend=backend)
        assert out.shape == (b, 8)
        np.testing.assert_allclose(np.asarray(out), unfused, atol=1e-5,
                                   err_msg=backend)


def test_decode_stages_uint8_codes_end_to_end():
    """Codes must keep their stored dtype through the wrapper — the
    widening happens per block inside the backends, never as an eager
    O(B·M) int32 copy at the call site."""
    kk = jax.random.PRNGKey(1)
    codes = jax.random.randint(kk, (64, 2), 0, 8).astype(jnp.uint8)
    cbs = jax.random.normal(kk, (2, 8, 4))
    assert codes.dtype == jnp.uint8
    seen = {}
    from repro.kernels import dispatch as dp
    orig = dp.get_impl

    def spy(name, backend=None):
        impl = orig(name, backend)
        if name != "rq_decode_stages":
            return impl

        def wrapped(c, cb, **kw):
            seen["dtype"] = c.dtype
            return impl(c, cb, **kw)
        return wrapped
    dp_get_impl = dp.get_impl
    dp.get_impl = spy
    try:
        out_i = decode_stages(codes, cbs, backend="interpret")
        assert seen["dtype"] == jnp.uint8
        out_x = decode_stages(codes, cbs, backend="xla")
        assert seen["dtype"] == jnp.uint8
    finally:
        dp.get_impl = dp_get_impl
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_x),
                               atol=1e-5)


def test_rq_decode_stages_m1_equals_plain_gather():
    """M=1 is exactly one codebook row-gather."""
    kk = jax.random.PRNGKey(2)
    codes = jax.random.randint(kk, (33, 1), 0, 8).astype(jnp.uint8)
    cbs = jax.random.normal(kk, (1, 8, 4))
    out = rq_decode_stages(codes, cbs, block_b=16, interpret=True)
    ref = jnp.take(cbs[0], codes[:, 0].astype(jnp.int32), axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ------------------------------------------------------------ dpq_assign

@pytest.mark.parametrize("b,d,k,s", [
    (1, 4, 8, 4), (100, 8, 256, 8), (513, 4, 64, 16),
])
def test_dpq_assign_matches_ref(b, d, k, s):
    kk = jax.random.PRNGKey(b + d)
    e = jax.random.normal(kk, (b, d, s))
    cent = jax.random.normal(jax.random.PRNGKey(1), (d, k, s))
    out = dpq_assign(e, cent, None, block_b=128, interpret=True)
    ref = dpq_assign_ref(e, cent, None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_dpq_assign_k_limit():
    kk = jax.random.PRNGKey(3)
    e = jax.random.normal(kk, (50, 4, 8))
    cent = jax.random.normal(jax.random.PRNGKey(4), (4, 32, 8))
    klim = jax.random.randint(jax.random.PRNGKey(5), (50,), 1, 33)
    out = dpq_assign(e, cent, klim, block_b=32, interpret=True)
    ref = dpq_assign_ref(e, cent, klim)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert (np.asarray(out) < np.asarray(klim)[:, None]).all()


# -------------------------------------------------------------- pq_score

@pytest.mark.parametrize("n,d,k", [(10, 4, 8), (1000, 8, 256), (2049, 16, 64)])
def test_pq_score_matches_ref(n, d, k):
    kk = jax.random.PRNGKey(n)
    codes = jax.random.randint(kk, (n, d), 0, k)
    lut = jax.random.normal(kk, (d, k))
    out = pq_score(lut, codes, block_n=512, interpret=True)
    ref = pq_score_ref(lut, codes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_adc_identity_property():
    """score via LUT == <q, decode(codes)> exactly (ADC correctness)."""
    kk = jax.random.PRNGKey(0)
    d, k, s, n = 8, 32, 8, 200
    codes = jax.random.randint(kk, (n, d), 0, k)
    cent = jax.random.normal(kk, (d, k, s))
    q = jax.random.normal(jax.random.PRNGKey(1), (d * s,))
    lut = build_lut_ref(q, cent)
    scores = pq_score_ref(lut, codes)
    decoded = mgqe_decode_ref(codes, cent)
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(decoded @ q), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- embedding_bag

def test_embedding_bag_matches_ref():
    kk = jax.random.PRNGKey(0)
    table = jax.random.normal(kk, (40, 8))
    ids = jnp.asarray([1, 2, 2, 7, 39, 0, 5], jnp.int32)
    seg = jnp.asarray([0, 0, 2, 2, 2, 4, 4], jnp.int32)
    out = embedding_bag(table, ids, seg, 6, interpret=True)
    ref = embedding_bag_ref(table, ids, seg, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_embedding_bag_weighted_and_empty_bags():
    kk = jax.random.PRNGKey(1)
    table = jax.random.normal(kk, (20, 4))
    ids = jnp.asarray([3, 3, 3], jnp.int32)
    seg = jnp.asarray([1, 1, 3], jnp.int32)
    w = jnp.asarray([0.5, 1.5, 2.0])
    out = embedding_bag(table, ids, seg, 5, w, interpret=True)
    ref = embedding_bag_ref(table, ids, seg, 5, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    assert np.abs(np.asarray(out)[[0, 2, 4]]).sum() == 0


@pytest.mark.parametrize("nnz,bags,vocab,dim", [(50, 10, 100, 16),
                                                (200, 7, 30, 32)])
def test_embedding_bag_random_sweep(nnz, bags, vocab, dim):
    rng = np.random.default_rng(nnz)
    table = jnp.asarray(rng.normal(size=(vocab, dim)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, bags, nnz)), jnp.int32)
    ids = jnp.asarray(rng.integers(0, vocab, nnz), jnp.int32)
    out = embedding_bag(table, ids, seg, bags, interpret=True)
    ref = embedding_bag_ref(table, ids, seg, bags)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- flash_attention

@pytest.mark.parametrize("b,sq,skv,h,hkv,hd,win", [
    (2, 256, 256, 4, 2, 64, 1 << 30),     # GQA, causal
    (1, 128, 128, 4, 4, 32, 64),          # MHA, sliding window
    (2, 128, 384, 8, 2, 64, 1 << 30),     # cross-length
    (1, 256, 256, 2, 1, 128, 300),        # window > block
])
def test_flash_attention_matches_ref(b, sq, skv, h, hkv, hd, win):
    ks = jax.random.split(jax.random.PRNGKey(sq + h), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd)) * 0.3
    k = jax.random.normal(ks[1], (b, skv, hkv, hd)) * 0.3
    v = jax.random.normal(ks[2], (b, skv, hkv, hd))
    out = flash_attention(q, k, v, window=win, block_q=128, block_k=128,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_ref_grad():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32)) * 0.3
    k = jax.random.normal(ks[1], (1, 128, 2, 32)) * 0.3
    v = jax.random.normal(ks[2], (1, 128, 2, 32))

    def f_kernel(q, k, v):
        return jnp.sum(attend(q, k, v, 1 << 30) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(flash_attention_ref(q, k, v, 1 << 30) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = (jax.random.normal(ks[0], (1, 128, 2, 32)) * 0.3).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], (1, 128, 2, 32)) * 0.3).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
