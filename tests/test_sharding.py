"""Multi-device sharding tests.

These need >1 XLA device, so they run in a subprocess with
``--xla_force_host_platform_device_count=8`` (the main test process
keeps the default single device — smoke tests must see 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=520)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_debug_mesh_and_param_specs():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.sharding import rules
        from repro.configs.registry import get_arch
        from repro.models import lm

        mesh = make_debug_mesh(2, 4)
        assert mesh.shape == {"data": 2, "model": 4}
        _, cfg = get_arch("stablelm-3b", smoke=True)
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        spec = rules.spec_tree(params, rules.lm_param_rules(cfg, mesh))
        # vocab rows sharded over model
        assert tuple(spec["embed"]["emb"])[-2:] == ("model", None)
        # lm head columns over model
        assert tuple(spec["lm_head"])[-1] == "model"
        print("OK")
    """)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """One train step under a (2, 4) mesh must match the unsharded step
    bit-for-bit (up to float tolerance) — the SPMD-correctness test."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.sharding import rules
        from repro.configs.registry import get_arch
        from repro.models import lm
        from repro.train import optimizer as opt_lib
        from repro.train.optimizer import TrainState

        _, cfg = get_arch("stablelm-3b", smoke=True)
        ocfg = opt_lib.OptimizerConfig(kind="adamw", lr=1e-3)
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        state = TrainState.create(ocfg, params)
        step = opt_lib.make_step_fn(
            ocfg, functools.partial(lm.loss_fn, cfg=cfg))
        k = jax.random.PRNGKey(1)
        toks = jax.random.randint(k, (8, 33), 0, cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        ref_state, ref_metrics = jax.jit(step)(state, batch)

        mesh = make_debug_mesh(2, 4)
        p_spec, o_spec = rules.lm_state_specs(
            cfg, mesh, state.params, state.opt_state)
        named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
            t, is_leaf=lambda x: isinstance(x, P) or x is None)
        st_shard = TrainState(named(p_spec), named(o_spec))
        b_shard = named({"tokens": P("data", None), "labels": P("data", None)})
        with mesh:
            sh_state, sh_metrics = jax.jit(
                step, in_shardings=(st_shard, b_shard),
                out_shardings=(st_shard, None))(state, batch)

        np.testing.assert_allclose(
            float(ref_metrics["loss"]), float(sh_metrics["loss"]),
            rtol=1e-4)
        for a, b in zip(jax.tree.leaves(ref_state.params),
                        jax.tree.leaves(sh_state.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-3, atol=2e-3)
        print("OK")
    """)


def test_sharded_mgqe_embedding_lookup_matches():
    """Row-sharded MGQE table lookup == replicated lookup."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.core import Embedding, EmbeddingConfig

        cfg = EmbeddingConfig(vocab_size=128, dim=16, kind="mgqe",
                              num_subspaces=4, num_centroids=8,
                              tier_boundaries=(16,),
                              tier_num_centroids=(8, 4))
        emb = Embedding(cfg)
        p = emb.init(jax.random.PRNGKey(0))
        ids = jnp.arange(64)
        ref, _ = emb.apply(p, ids)

        mesh = make_debug_mesh(2, 4)
        shard = {"emb": NamedSharding(mesh, P("model", None)),
                 "centroids": NamedSharding(mesh, P())}
        p_sharded = jax.device_put(p, shard)
        with mesh:
            out, _ = jax.jit(emb.apply)(p_sharded, ids)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)
        print("OK")
    """)


def test_sharded_quantized_gather_matches_serve_all_variants():
    """Row-sharded codes + replicated codebooks on Mesh(data=2, model=2)
    must serve identically to the single-device fused decode, for DPQ,
    all three MGQE variants, and the rq and mpe plugins
    (DESIGN.md §6/§7)."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Embedding, EmbeddingConfig
        from repro.sharding.rules import shard_quantized_artifact

        variants = [
            dict(kind="dpq", num_subspaces=4, num_centroids=8),
            dict(kind="mgqe", num_subspaces=4, num_centroids=8,
                 tier_boundaries=(16,), tier_num_centroids=(8, 4)),
            dict(kind="mgqe", mgqe_variant="private_k", num_subspaces=4,
                 num_centroids=8, tier_boundaries=(16,),
                 tier_num_centroids=(8, 4)),
            dict(kind="mgqe", mgqe_variant="private_d", num_subspaces=4,
                 num_centroids=8, tier_boundaries=(16,),
                 tier_num_subspaces=(4, 2)),
            dict(kind="rq", num_levels=3, num_centroids=8),
            dict(kind="mpe", num_subspaces=8, tier_boundaries=(16, 48),
                 tier_bits=(8, 4, 2)),
        ]
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        assert dict(mesh.shape) == {"data": 2, "model": 2}
        for kw in variants:
            cfg = EmbeddingConfig(vocab_size=128, dim=16, **kw)
            emb = Embedding(cfg)
            art = emb.export(emb.init(jax.random.PRNGKey(0)))
            ids = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 128)
            ref = emb.serve(art, ids)

            scfg = dataclasses.replace(cfg, sharded_codes=True)
            semb = Embedding(scfg)
            art_s = shard_quantized_artifact(art, scfg, mesh)
            with mesh:
                out = jax.jit(semb.serve)(art_s, ids)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
            # no ambient mesh -> single-device fallback, same result
            np.testing.assert_allclose(np.asarray(semb.serve(art, ids)),
                                       np.asarray(ref), atol=1e-5)
        print("OK")
    """)


def test_sharded_rq_single_pass_decode_bit_identical():
    """The rq scheme's single-pass ``rq_decode_stages`` serve path
    under Mesh(data=2, model=2) must be BIT-identical (array_equal,
    not a tolerance) to the single-device fused decode — the per-shard
    decode routes through the same dispatched op, summed via psum of
    disjoint shard partials, so not even the reduction order differs.
    Covers odd/ragged batch shapes and both kernel backends."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Embedding, EmbeddingConfig
        from repro.sharding.rules import shard_quantized_artifact

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        for backend in ("xla", "interpret"):
            cfg = EmbeddingConfig(vocab_size=128, dim=16, kind="rq",
                                  num_levels=3, num_centroids=8,
                                  decode_block_b=32,
                                  kernel_backend=backend)
            emb = Embedding(cfg)
            art = emb.export(emb.init(jax.random.PRNGKey(0)))
            assert art["codes"].dtype == jnp.uint8
            scfg = dataclasses.replace(cfg, sharded_codes=True)
            semb = Embedding(scfg)
            art_s = shard_quantized_artifact(art, scfg, mesh)
            for shape in [(8, 8), (7,), (1,), (3, 5)]:
                ids = jax.random.randint(
                    jax.random.PRNGKey(sum(shape)), shape, 0, 128)
                ref = emb.serve(art, ids)
                assert ref.shape == shape + (16,)
                with mesh:
                    out = jax.jit(semb.serve)(art_s, ids)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(ref))
        print("OK")
    """)


def test_sharded_mpe_packed_decode_bit_identical():
    """The mpe scheme's fused unpack-and-decode serve path under
    Mesh(data=2, model=2) must be BIT-identical to the single-device
    decode: each shard gathers PACKED rows from its local (n/2, W_i)
    code shards and unpacks inside the dispatched kernel, with tier
    blending keyed on the all-gathered GLOBAL ids.  Covers odd/ragged
    batch shapes and both kernel backends (DESIGN.md §13)."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Embedding, EmbeddingConfig
        from repro.sharding.rules import shard_quantized_artifact

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        for backend in ("xla", "interpret"):
            cfg = EmbeddingConfig(vocab_size=128, dim=16, kind="mpe",
                                  num_subspaces=8,
                                  tier_boundaries=(16, 48),
                                  tier_bits=(8, 4, 2),
                                  decode_block_b=32,
                                  kernel_backend=backend)
            emb = Embedding(cfg)
            art = emb.export(emb.init(jax.random.PRNGKey(0)))
            # stored packed: W_i = ceil(D * bits / 8) bytes per row
            assert [c.shape[1] for c in art["codes"]] == [8, 4, 2]
            assert all(c.dtype == jnp.uint8 for c in art["codes"])
            scfg = dataclasses.replace(cfg, sharded_codes=True)
            semb = Embedding(scfg)
            art_s = shard_quantized_artifact(art, scfg, mesh)
            for shape in [(8, 8), (7,), (1,), (3, 5)]:
                ids = jax.random.randint(
                    jax.random.PRNGKey(sum(shape)), shape, 0, 128)
                ref = emb.serve(art, ids)
                assert ref.shape == shape + (16,)
                with mesh:
                    out = jax.jit(semb.serve)(art_s, ids)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(ref))
        print("OK")
    """)


def test_sharded_engine_matches_single_device():
    """ServingEngine(mesh=...) — per-shard device-resident artifact,
    flushes padded to block_b x data shards — returns the same rows as
    the single-device engine."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Embedding, EmbeddingConfig
        from repro.launch.engine import ServingEngine

        cfg = EmbeddingConfig(vocab_size=256, dim=16, kind="mgqe",
                              num_subspaces=4, num_centroids=8,
                              tier_boundaries=(32,),
                              tier_num_centroids=(8, 4),
                              decode_block_b=32)
        emb = Embedding(cfg)
        art = emb.export(emb.init(jax.random.PRNGKey(0)))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        eng = ServingEngine(emb, art, mesh=mesh)
        ref_eng = ServingEngine(emb, art)
        assert eng.pad_multiple == 32 * 2 and eng.data_shards == 2

        rng = np.random.default_rng(0)
        reqs = [rng.integers(0, 256, n) for n in (5, 40, 1, 17)]
        handles = [eng.submit(r) for r in reqs]
        ref_handles = [ref_eng.submit(r) for r in reqs]
        outs, ref_outs = eng.flush(), ref_eng.flush()
        for h, rh in zip(handles, ref_handles):
            np.testing.assert_allclose(np.asarray(outs[h]),
                                       np.asarray(ref_outs[rh]), atol=1e-5)
        st = eng.stats()
        assert st.padded_lookups % eng.pad_multiple == 0
        print("OK")
    """)


def test_sharded_engine_hot_cache_bit_identical():
    """Hot-row cache under Mesh(data=2, model=2): the hot block is
    re-decoded through the engine's own sharded serve and replicated,
    so cached lookups are BIT-identical to the uncached sharded decode
    — for every quantized scheme (DESIGN.md §9)."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Embedding, EmbeddingConfig
        from repro.launch.engine import ServingEngine

        variants = [
            dict(kind="dpq", num_subspaces=4, num_centroids=8),
            dict(kind="mgqe", mgqe_variant="private_k", num_subspaces=4,
                 num_centroids=8, tier_boundaries=(16,),
                 tier_num_centroids=(8, 4)),
            dict(kind="mgqe", mgqe_variant="private_d", num_subspaces=4,
                 num_centroids=8, tier_boundaries=(16,),
                 tier_num_subspaces=(4, 2)),
            dict(kind="rq", num_levels=3, num_centroids=8),
            dict(kind="mpe", num_subspaces=8, tier_boundaries=(16, 48),
                 tier_bits=(8, 4, 2)),
        ]
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        for kw in variants:
            cfg = EmbeddingConfig(vocab_size=128, dim=16,
                                  decode_block_b=32, hot_rows=32, **kw)
            emb = Embedding(cfg)
            art = emb.export(emb.init(jax.random.PRNGKey(0)))
            assert art["hot"].shape == (32, 16)
            hot_eng = ServingEngine(emb, art, mesh=mesh)
            assert hot_eng.hot_rows == 32
            cold_eng = ServingEngine(emb, art, mesh=mesh, hot_rows=0)
            # mixed hot/cold batch incl. duplicates + boundary ids
            ids = np.r_[np.arange(8), rng.integers(0, 128, 20), 31, 32]
            out = hot_eng.lookup(ids)
            ref = cold_eng.lookup(ids)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref))
            st = hot_eng.stats()
            assert st.hot_hits > 0 and st.decoded_lookups > 0
            # fully-cached flush: zero fused decode on the whole mesh
            before = hot_eng.stats().decoded_lookups
            out2 = hot_eng.lookup(np.arange(16))
            np.testing.assert_array_equal(
                np.asarray(out2), np.asarray(cold_eng.lookup(np.arange(16))))
            assert hot_eng.stats().decoded_lookups == before
            # adaptive refresh keeps bit-parity under the mesh too
            hot_eng.refresh_hot_rows(np.arange(64, 96))
            np.testing.assert_array_equal(
                np.asarray(hot_eng.lookup(ids)), np.asarray(ref))
        print("OK")
    """)


def test_sharded_retrieval_topk_bit_identical_all_kinds():
    """Row-sharded corpus top-k on Mesh(data=2, model=2) must equal the
    single-device batched search EXACTLY (bit-identical scores AND
    ids, not a tolerance) for every registered index kind — the
    deterministic (score, tiebreak) merge contract of DESIGN.md §8 —
    including through the RetrievalEngine and the k > candidates
    padding edge."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.engine import RetrievalEngine
        from repro.retrieval import (IndexConfig, get_index,
                                     index_class, registered_index_kinds,
                                     sharded_topk)
        from repro.sharding.rules import shard_retrieval_artifact

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        vecs = jax.random.normal(jax.random.PRNGKey(0), (2048, 16))
        q = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
        for kind in registered_index_kinds():
            index = get_index(index_class(kind).probe_config())
            art = index.build(jax.random.PRNGKey(2), vecs)
            ref_s, ref_i = index.search(art, q, 50)
            art_s = shard_retrieval_artifact(art, index, mesh)
            with mesh:
                out_s, out_i = jax.jit(
                    lambda a, qq: sharded_topk(index, a, qq, 50))(
                        art_s, q)
            np.testing.assert_array_equal(np.asarray(out_s),
                                          np.asarray(ref_s))
            np.testing.assert_array_equal(np.asarray(out_i),
                                          np.asarray(ref_i))
            # no ambient mesh -> single-device fallback, same result
            fs, fi = sharded_topk(index, art, q, 50)
            np.testing.assert_array_equal(np.asarray(fs),
                                          np.asarray(ref_s))

            # through the engine: mesh vs single-device, odd batches
            eng = RetrievalEngine(index, art, k=13, block_q=4,
                                  mesh=mesh)
            ref_eng = RetrievalEngine(index, art, k=13, block_q=4)
            assert eng.pad_multiple == 4 * 2 and eng.data_shards == 2
            rng = np.random.default_rng(0)
            reqs = [rng.normal(size=(n, 16)).astype(np.float32)
                    for n in (5, 1, 3)]
            hs = [eng.submit(r) for r in reqs]
            ref_hs = [ref_eng.submit(r) for r in reqs]
            outs, ref_outs = eng.flush(), ref_eng.flush()
            for h, rh in zip(hs, ref_hs):
                np.testing.assert_array_equal(
                    np.asarray(outs[h][1]), np.asarray(ref_outs[rh][1]))
                np.testing.assert_array_equal(
                    np.asarray(outs[h][0]), np.asarray(ref_outs[rh][0]))

        # k > valid candidates: pads (-inf, INVALID_ID) identically
        index = get_index(IndexConfig(kind="ivf_pq", num_subspaces=4,
                                      num_centroids=16, iters=3,
                                      nlist=8, nprobe=2))
        art = index.build(jax.random.PRNGKey(2), vecs[:64])
        ref = index.search(art, q, 40)
        art_s = shard_retrieval_artifact(art, index, mesh)
        with mesh:
            out = jax.jit(lambda a, qq: sharded_topk(
                index, a, qq, 40))(art_s, q)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(ref[1]))

        # spilled chained layout (DESIGN.md §12): a skewed corpus under
        # a tight list cap forces multi-chunk chains; the sharded merge
        # must stay bit-identical with spill lists scattered over shards
        cents = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                             (8, 16)))
        g = np.repeat(np.arange(8),
                      [1200, 400, 200, 100, 60, 40, 28, 20])
        vs = jnp.asarray(
            cents[g] + 0.05 * np.random.default_rng(3).normal(
                size=(2048, 16)))
        index = get_index(IndexConfig(kind="ivf_pq", num_subspaces=4,
                                      num_centroids=16, iters=3,
                                      nlist=8, nprobe=8,
                                      list_cap_quantile=0.5))
        art = index.build(jax.random.PRNGKey(2), vs)
        assert art["list_chain"].shape[1] > 1    # chains really spill
        ref = index.search(art, q, 50)
        art_s = shard_retrieval_artifact(art, index, mesh)
        with mesh:
            out = jax.jit(lambda a, qq: sharded_topk(
                index, a, qq, 50))(art_s, q)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(ref[1]))
        print("OK")
    """)


def test_sharded_rows_train_lookup_private_variants():
    """Training-path row gather (sharded_rows) parity for the private
    MGQE variants — the full table row-sharded over model."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import Embedding, EmbeddingConfig

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for variant, extra in [
                ("private_k", dict(tier_num_centroids=(8, 4))),
                ("private_d", dict(tier_num_subspaces=(4, 2)))]:
            cfg = EmbeddingConfig(vocab_size=128, dim=16, kind="mgqe",
                                  mgqe_variant=variant, num_subspaces=4,
                                  num_centroids=8, tier_boundaries=(16,),
                                  **extra)
            emb = Embedding(cfg)
            p = emb.init(jax.random.PRNGKey(0))
            ids = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 128)
            ref, ref_aux = emb.apply(p, ids)

            semb = Embedding(dataclasses.replace(cfg, sharded_rows=True))
            shard = {"emb": NamedSharding(mesh, P("model", None)),
                     "centroids": [NamedSharding(mesh, P())] * 2}
            p_sharded = jax.device_put(p, shard)
            with mesh:
                out, aux = jax.jit(semb.apply)(p_sharded, ids)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
            np.testing.assert_allclose(float(aux), float(ref_aux),
                                       rtol=1e-5)
        print("OK")
    """)


def test_multipod_mesh_shape():
    _run("""
        import jax
        import numpy as np
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 2, multi_pod=True)
        assert mesh.shape == {"pod": 2, "data": 2, "model": 2}
        print("OK")
    """)


def test_moe_sharded_dispatch_matches_reference():
    """moe_ffn_sharded (both strategies) == moe_ffn at high capacity."""
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp
        from repro.nn import moe as moe_lib
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        for e in (8, 3):   # 8 -> expert strategy; 3 -> ffn strategy
            p = moe_lib.moe_init(key, d_model=32, d_ff=64, num_experts=e)
            ref, _ = moe_lib.moe_ffn(p, x, top_k=2, capacity_factor=64.0)
            with mesh:
                out, _ = jax.jit(lambda p, x: moe_lib.moe_ffn_sharded(
                    p, x, top_k=2, capacity_factor=64.0))(p, x)
            err = float(jnp.max(jnp.abs(out - ref)))
            assert err < 1e-5, (e, err)
        print("OK")
    """)


def test_sharded_row_gather_matches_take():
    _run("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.gather import row_gather
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        table = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
        ids = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 64)
        ref = jnp.take(table, ids, axis=0)
        with mesh:
            out = jax.jit(lambda t, i: row_gather(t, i, sharded=True))(
                table, ids)
            g_s = jax.jit(jax.grad(lambda t: jnp.sum(
                row_gather(t, ids, sharded=True) ** 2)))(table)
        g_r = jax.grad(lambda t: jnp.sum(
            jnp.take(t, ids, axis=0) ** 2))(table)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
        np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_r))
        print("OK")
    """)


def test_elastic_reshard_roundtrip():
    """Checkpoint saved under one mesh restores under a different DP
    width (elastic scaling)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.train import checkpoint as ck
        from repro.train import optimizer as opt_lib
        from repro.train.optimizer import TrainState

        params = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        ocfg = opt_lib.OptimizerConfig()
        state = TrainState.create(ocfg, params)

        mesh1 = make_debug_mesh(4, 2)
        sh1 = NamedSharding(mesh1, P("data", None))
        state1 = jax.tree.map(
            lambda x: jax.device_put(x, sh1) if x.ndim == 2 else x, state)
        with tempfile.TemporaryDirectory() as d:
            ck.save(d, 3, state1, keep=1)
            # restore onto a *different* mesh layout
            mesh2 = make_debug_mesh(2, 4)
            sh2 = NamedSharding(mesh2, P("data", None))
            template = jax.tree.map(lambda x: x, state)
            restored, step = ck.restore_latest(d, template)
            assert step == 3
            r2 = jax.tree.map(
                lambda x: jax.device_put(x, sh2) if x.ndim == 2 else x,
                restored)
            np.testing.assert_array_equal(
                np.asarray(r2.params["w"]), np.asarray(params["w"]))
        print("OK")
    """)
