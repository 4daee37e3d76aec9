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
