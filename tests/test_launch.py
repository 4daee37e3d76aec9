"""Launcher-level tests: microbatch equivalence, registry coverage."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, SHAPE_SKIPS, all_cells, shapes_for


def test_registry_covers_assignment():
    assert len(ARCHS) == 10
    total = sum(len(shapes_for(a)) for a in ARCHS)
    assert total == 40                         # 40 assigned cells
    runnable = list(all_cells())
    assert len(runnable) == 38                 # 2 documented skips
    assert set(SHAPE_SKIPS) == {("stablelm-3b", "long_500k"),
                                ("qwen3-moe-30b-a3b", "long_500k")}


@pytest.mark.slow
def test_grad_accumulation_matches_full_batch():
    """The microbatchN train step must produce the same update as the
    full-batch step (linearity of gradients)."""
    from repro.configs.registry import get_arch
    from repro.models import lm
    from repro.train import optimizer as opt_lib
    from repro.train.optimizer import TrainState

    _, cfg = get_arch("stablelm-3b", smoke=True)
    ocfg = opt_lib.OptimizerConfig(kind="adamw", lr=1e-3, grad_clip=None)
    loss_fn = functools.partial(lm.loss_fn, cfg=cfg)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)

    k = jax.random.PRNGKey(1)
    toks = jax.random.randint(k, (4, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    # full-batch reference
    ref_state = TrainState.create(ocfg, params)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        loss_fn, has_aux=True)(ref_state.params, batch)

    # microbatch=2 accumulation (mirrors cells.lm_train_cell)
    def accum(params, batch, m):
        split = jax.tree.map(
            lambda v: v.reshape((m, v.shape[0] // m) + v.shape[1:]), batch)

        def one(carry, mb):
            gsum, lsum = carry
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (gsum, lsum), _ = jax.lax.scan(one, (zeros, jnp.float32(0)), split)
        return (jax.tree.map(lambda g: g / m, gsum), lsum / m)

    grads2, loss2 = accum(ref_state.params, batch, 2)
    np.testing.assert_allclose(float(ref_loss), float(loss2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_grads), jax.tree.leaves(grads2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-5)


def test_compile_cache_leaves_env_dir_in_charge(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR set: the helper reports it and sets
    no directory of its own."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/cache")
    assert compile_cache.setup_compile_cache() == "/somewhere/cache"
    assert calls == []


def test_compile_cache_fixed_repo_path(monkeypatch):
    """Unset: the cache goes to the fixed <repo>/.jax_cache — the same
    path in every process, so entries are found again."""
    import os
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert compile_cache.setup_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2


def _record(name):
    from repro.launch import compile_cache
    return compile_cache.compile_stats().get(name, {})


def test_compile_stats_per_function_and_persistent_cache(tmp_path,
                                                         monkeypatch):
    """One jit compiled: its record names it; in a fresh persistent
    cache the first compile misses and, after ``jax.clear_caches()``,
    the second hits."""
    from jax._src import compilation_cache as cc
    from repro.launch import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    compile_cache.setup_compile_cache()
    compile_cache.setup_compile_cache()     # listeners installed once
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        def compile_stats_probe(x):
            return jnp.tanh(x) * 3.0 + 1.0
        f = jax.jit(compile_stats_probe)
        x = jnp.arange(7.0)
        f(x).block_until_ready()
        first = _record("jit(compile_stats_probe)")
        assert first["compiles"] == 1 and first["compile_s"] > 0
        assert first["traces"] == 1 and first["trace_s"] > 0
        assert (first["cache_misses"], first["cache_hits"]) == (1, 0)
        jax.clear_caches()
        jax.jit(compile_stats_probe)(x).block_until_ready()
        second = _record("jit(compile_stats_probe)")
        assert second["compiles"] == 2
        assert (second["cache_misses"], second["cache_hits"]) == (1, 1)
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
        cc.reset_cache()


def test_compile_stats_keep_threads_apart(monkeypatch):
    """A hit on one thread and a miss on another, interleaved before
    either compile ends: each goes to its own thread's compile."""
    import threading
    from repro.launch import compile_cache as C
    monkeypatch.setenv(C.ENV_VAR, "/unused")
    C.setup_compile_cache()
    hit, miss = list(C.CACHE_EVENTS)
    a_waits, b_done = threading.Event(), threading.Event()

    def a():
        jax.monitoring.record_event(hit)
        a_waits.set()
        b_done.wait(10)
        jax.monitoring.record_event_duration_secs(
            C.BACKEND_COMPILE_EVENT, 0.5, fun_name="jit(thread_a_fn)")

    def b():
        a_waits.wait(10)
        jax.monitoring.record_event(miss)
        jax.monitoring.record_event_duration_secs(
            C.BACKEND_COMPILE_EVENT, 0.25, fun_name="jit(thread_b_fn)")
        b_done.set()
    threads = [threading.Thread(target=t) for t in (a, b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    ra, rb = _record("jit(thread_a_fn)"), _record("jit(thread_b_fn)")
    assert (ra["compiles"], ra["compile_s"]) == (1, 0.5)
    assert (ra["cache_hits"], ra["cache_misses"]) == (1, 0)
    assert (rb["compiles"], rb["compile_s"]) == (1, 0.25)
    assert (rb["cache_hits"], rb["cache_misses"]) == (0, 1)
