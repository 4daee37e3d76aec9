"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch autoint --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

On CPU containers use --smoke (reduced config); the full configs are
for real TPU slices (the dry-run proves they shard).  The loop includes
checkpoint/auto-resume, straggler detection, and optional failure
injection (--fail-at) to exercise the fault-tolerance path end to end.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.launch.compile_cache import setup_compile_cache
from repro.train import optimizer as opt_lib
from repro.train.loop import LoopConfig, fit
from repro.train.optimizer import TrainState
from repro.train.resilience import FailureInjector


def _lm_setup(cfg, batch: int, seq: int):
    from repro.models import lm
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    ocfg = opt_lib.OptimizerConfig(kind="adamw", lr=3e-4,
                                   schedule="linear_warmup_cosine",
                                   warmup_steps=20, total_steps=1000)
    state = TrainState.create(ocfg, params)
    step = opt_lib.make_step_fn(ocfg, functools.partial(lm.loss_fn, cfg=cfg))

    def data():
        rng = np.random.default_rng(0)
        while True:
            toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
            yield {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                   "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    return state, step, data()


def recsys_setup(cfg, batch: int):
    from repro.data.synthetic import CTRStream
    from repro.launch.cells import _recsys_model
    model = _recsys_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = opt_lib.OptimizerConfig(kind="adagrad", lr=1e-2)
    state = TrainState.create(ocfg, params)
    step = opt_lib.make_step_fn(ocfg, model.loss)

    if cfg.model == "two_tower":
        def data():
            rng = np.random.default_rng(0)
            logq = np.log(1.0 / cfg.n_items)
            while True:
                yield {"user_ids": jnp.asarray(
                           rng.integers(0, cfg.n_users, batch), jnp.int32),
                       "item_ids": jnp.asarray(
                           rng.integers(0, cfg.n_items, batch), jnp.int32),
                       "item_logq": jnp.full((batch,), logq, jnp.float32)}
        return state, step, data()
    if cfg.model == "bst":
        def data():
            rng = np.random.default_rng(0)
            while True:
                yield {"hist_ids": jnp.asarray(
                           rng.integers(0, cfg.n_items,
                                        (batch, cfg.seq_len)), jnp.int32),
                       "target_id": jnp.asarray(
                           rng.integers(0, cfg.n_items, batch), jnp.int32),
                       "label": jnp.asarray(
                           rng.random(batch) < 0.3, jnp.float32)}
        return state, step, data()
    stream = CTRStream(cfg.field_vocab_sizes, batch)
    def data():
        for b in stream:
            yield {"sparse_ids": jnp.asarray(b["sparse_ids"], jnp.int32),
                   "label": jnp.asarray(b["label"], jnp.float32)}
    return state, step, data()


def _gnn_setup(cfg, batch: int):
    from repro.data.graph import molecule_batch
    from repro.models.gnn.mace import MACE
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = opt_lib.OptimizerConfig(kind="adam", lr=1e-3)
    state = TrainState.create(ocfg, params)

    def loss_fn(p, graph):
        g = dict(graph)
        n_graphs = int(g.pop("n_graphs"))
        return model.energy_loss(p, dict(g, n_graphs=n_graphs))

    def step(state, graph):
        g = {k: v for k, v in graph.items()}
        (loss, metrics), grads = jax.value_and_grad(
            model.energy_loss, has_aux=True)(state.params, g)
        new_p, new_o = opt_lib.apply_updates(ocfg, state.params, grads,
                                             state.opt_state)
        return TrainState(new_p, new_o), metrics

    def data():
        seed = 0
        while True:
            g = molecule_batch(n_graphs=min(batch, 32), n_atoms=12,
                               n_edges=24, n_species=cfg.num_species,
                               seed=seed)
            seed += 1
            yield {k: (jnp.asarray(v) if not np.isscalar(v) else v)
                   for k, v in g.items()}
    return state, step, data()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a crash at this step (tests restart)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    setup_compile_cache()

    family, cfg = get_arch(args.arch, smoke=args.smoke)
    if family == "lm":
        state, step, data = _lm_setup(cfg, args.batch, args.seq)
    elif family == "recsys":
        state, step, data = recsys_setup(cfg, args.batch)
    else:
        state, step, data = _gnn_setup(cfg, args.batch)

    injector = (FailureInjector(fail_at_steps=[args.fail_at])
                if args.fail_at else None)
    lcfg = LoopConfig(total_steps=args.steps, log_every=args.log_every,
                      ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                      metrics_hook=lambda s, m: print(
                          f"step {s}: " + " ".join(
                              f"{k}={v:.4f}" for k, v in m.items()
                              if k not in ("step",))))
    t0 = time.time()
    state, hist = fit(state, step, data, lcfg, injector=injector)
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s; "
          f"final loss {hist[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
