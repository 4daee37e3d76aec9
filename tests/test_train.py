"""Training substrate: optimizers, checkpoint/restore, fault tolerance,
straggler detection, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ck
from repro.train import optimizer as opt_lib
from repro.train.compression import (dequantize, init_error_state,
                                     quantize_int8)
from repro.train.loop import LoopConfig, fit
from repro.train.optimizer import TrainState
from repro.train.resilience import (FailureInjector, SimulatedFailure,
                                    StragglerDetector)


def _quad_problem():
    params = {"w": jnp.asarray([2.0, -3.0]), "b": jnp.asarray(1.0)}

    def loss_fn(p, batch):
        l = jnp.sum(p["w"] ** 2) + p["b"] ** 2
        return l, {"loss": l}
    return params, loss_fn


def test_adam_matches_reference_formula():
    params, loss_fn = _quad_problem()
    cfg = opt_lib.OptimizerConfig(kind="adam", lr=0.1, grad_clip=None)
    state = TrainState.create(cfg, params)
    step = opt_lib.make_step_fn(cfg, loss_fn)
    new_state, _ = step(state, {})
    # reference: g = 2w; m=(1-b1)g; v=(1-b2)g^2; update = lr*mhat/(sqrt(vhat)+eps)
    g = 2 * np.asarray([2.0, -3.0])
    mhat = g
    vhat = g ** 2
    expected = np.asarray([2.0, -3.0]) - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_state.params["w"]), expected,
                               rtol=1e-5)


def test_grad_clip_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}           # norm 5
    clipped, norm = opt_lib.clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 5.0) < 1e-5
    np.testing.assert_allclose(np.asarray(clipped["a"]), [0.6, 0.8],
                               rtol=1e-5)


@pytest.mark.parametrize("kind", ["adam", "adamw", "adagrad", "sgd"])
def test_optimizers_descend(kind):
    params, loss_fn = _quad_problem()
    cfg = opt_lib.OptimizerConfig(kind=kind, lr=0.05)
    state = TrainState.create(cfg, params)
    step = jax.jit(opt_lib.make_step_fn(cfg, loss_fn))
    l0 = float(loss_fn(state.params, {})[0])
    for _ in range(120):
        state, _ = step(state, {})
    assert float(loss_fn(state.params, {})[0]) < l0 * 0.5


def test_lr_schedule_warmup_cosine():
    cfg = opt_lib.OptimizerConfig(lr=1.0, schedule="linear_warmup_cosine",
                                  warmup_steps=10, total_steps=100,
                                  min_lr_frac=0.1)
    lr0 = float(opt_lib.schedule_lr(cfg, jnp.asarray(0)))
    lr9 = float(opt_lib.schedule_lr(cfg, jnp.asarray(9)))
    lr_end = float(opt_lib.schedule_lr(cfg, jnp.asarray(99)))
    assert lr0 < lr9 <= 1.0
    assert abs(lr_end - 0.1) < 0.02


# ------------------------------------------------------------ checkpoint

def test_checkpoint_roundtrip(tmp_path):
    params, loss_fn = _quad_problem()
    cfg = opt_lib.OptimizerConfig(kind="adam", lr=0.1)
    state = TrainState.create(cfg, params)
    ck.save(str(tmp_path), 7, state, keep=2)
    restored, step = ck.restore_latest(str(tmp_path), state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_policy(tmp_path):
    params, _ = _quad_problem()
    cfg = opt_lib.OptimizerConfig()
    state = TrainState.create(cfg, params)
    for s in (1, 2, 3, 4):
        ck.save(str(tmp_path), s, state, keep=2)
    _, step = ck.restore_latest(str(tmp_path), state)
    assert step == 4
    kept = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(kept) == 2


def test_checkpoint_corruption_detected(tmp_path):
    params, _ = _quad_problem()
    cfg = opt_lib.OptimizerConfig()
    state = TrainState.create(cfg, params)
    ck.save(str(tmp_path), 1, state, keep=2)
    ck.save(str(tmp_path), 2, state, keep=2)
    # corrupt the newest checkpoint payload
    d2 = os.path.join(tmp_path, "step_00000002")
    for f in os.listdir(d2):
        if f.endswith(".npz"):
            with open(os.path.join(d2, f), "wb") as fh:
                fh.write(b"garbage")
    restored, step = ck.restore_latest(str(tmp_path), state)
    assert step == 1                     # falls back to the older valid one


# -------------------------------------------------------- fault tolerance

def test_crash_restart_resumes_and_converges(tmp_path):
    """Inject a crash mid-run; a relaunch must resume from the last
    checkpoint and reach the same final state as an uninterrupted run."""
    params, loss_fn = _quad_problem()
    ocfg = opt_lib.OptimizerConfig(kind="sgd", lr=0.05, grad_clip=None)
    step_fn = opt_lib.make_step_fn(ocfg, loss_fn)

    def data():
        while True:
            yield {}

    lcfg = LoopConfig(total_steps=20, log_every=100, ckpt_every=5,
                      ckpt_dir=str(tmp_path))
    fresh = lambda: TrainState.create(       # donation-safe: new arrays
        ocfg, jax.tree.map(jnp.array, params))
    # run 1: crash at step 12 (after the step-10 checkpoint)
    inj = FailureInjector(fail_at_steps=[12])
    with pytest.raises(SimulatedFailure):
        fit(fresh(), step_fn, data(), lcfg, injector=inj)
    # run 2: auto-resume to completion
    final, hist = fit(fresh(), step_fn, data(), lcfg)
    assert int(final.step) == 20
    # uninterrupted reference
    ref = fresh()
    jit_step = jax.jit(step_fn)
    for _ in range(20):
        ref, _ = jit_step(ref, {})
    np.testing.assert_allclose(np.asarray(final.params["w"]),
                               np.asarray(ref.params["w"]), rtol=1e-5)


def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(num_hosts=4, threshold=1.8, patience=5)
    rng = np.random.default_rng(0)
    reports = []
    for _ in range(50):
        for h in range(4):
            dt = 1.0 + 0.01 * rng.standard_normal()
            if h == 2:
                dt *= 3.0                        # host 2 is slow
            det.record(h, dt)
        reports = det.check()
    assert [r.host for r in reports] == [2]
    assert reports[0].ratio > 1.8


def test_straggler_detector_recovers():
    det = StragglerDetector(num_hosts=4, threshold=1.5, patience=2)
    for _ in range(20):
        for h in range(4):
            det.record(h, 5.0 if h == 3 else 1.0)
        det.check()
    assert [r.host for r in det.check()] == [3]
    for _ in range(60):                          # host 3 recovers
        for h in range(4):
            det.record(h, 1.0)
        det.check()
    assert det.check() == []


# ----------------------------------------------------- grad compression

def test_int8_compression_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)) * 0.01, jnp.float32)
    q, scale = quantize_int8(g)
    d = dequantize(q, scale)
    assert q.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(d - g))) <= float(scale) + 1e-8


def test_error_feedback_residual_unbiased():
    """Error feedback: the time-average of dequantized sends converges
    to the true gradient even when one step's quantization is biased."""
    g = jnp.full((64,), 0.003, jnp.float32)
    err = init_error_state(g)
    acc = jnp.zeros_like(g)
    for _ in range(50):
        g32 = g + err
        q, scale = quantize_int8(g32)
        deq = dequantize(q, scale)
        err = g32 - deq
        acc = acc + deq
    np.testing.assert_allclose(np.asarray(acc) / 50, np.asarray(g),
                               rtol=0.02)


def test_compressed_psum_mean_single_device():
    """shard_map'd compressed all-reduce on a 1-device mesh: the mean
    must equal the (dequantized) local gradient."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P
    from repro.train.compression import compressed_psum_mean

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16,)),
                          jnp.float32)}
    err = init_error_state(g)

    def f(g, e):
        return compressed_psum_mean(g, e, "dp")

    out, new_err = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(g, err)
    q, scale = quantize_int8(g["w"])
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(dequantize(q, scale)), rtol=1e-6)
