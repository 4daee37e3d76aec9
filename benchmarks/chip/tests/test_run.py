"""The harness driven end to end on the CPU at a small size: every cell
comes out correct, and the same run with the timed path broken comes
out not correct."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

import conftest as C
import harness as H

SERVING = ["deepfm.ctr_online", "autoint.ctr_online", "autoint.ctr_bulk"]


@pytest.mark.parametrize("workload", SERVING + ["deepfm.train"])
def test_cell_runs_correct(spec, workload):
    out = C.run_small(spec, workload)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    assert {"platform", "kind", "count"} <= set(out["device"])


def _alter_one_answer(monkeypatch):
    from repro.launch import serve
    real = serve.serve_ctr

    def broken(*a, **kw):
        res = real(*a, **kw)
        step = res.step
        return res._replace(step=lambda p, art, b: step(p, art, b)
                            .at[0].add(1.0))
    monkeypatch.setattr(serve, "serve_ctr", broken)


@pytest.mark.parametrize("workload", SERVING)
def test_altered_answer_is_not_correct(spec, workload, monkeypatch):
    _alter_one_answer(monkeypatch)
    out = C.run_small(spec, workload)
    assert out["correct"] is False
    assert out["checks"]["score_err"]["value"] > \
        out["checks"]["score_err"]["limit"]


def _break_step(monkeypatch, fault):
    from repro.train import optimizer as O
    real = O.make_step_fn

    def make(cfg, loss_fn):
        step = real(cfg, loss_fn)

        def broken(state, batch):
            if fault == "unchanged":
                _, m = step(state, batch)
                return state, m
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            return step(state, half)
        return broken
    monkeypatch.setattr(O, "make_step_fn", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(spec, fault, monkeypatch):
    _break_step(monkeypatch, fault)
    out = C.run_small(spec, "deepfm.train")
    assert out["correct"] is False, out["checks"]


def test_refused_off_tpu(spec):
    import run
    with pytest.raises(run.Refused, match="not 'tpu'"):
        run.run(spec, "deepfm.ctr_online", 1, 1.0, False)


@pytest.mark.parametrize("var", ["REPRO_KERNEL_BACKEND",
                                 "REPRO_KERNEL_TUNE_CACHE"])
def test_refused_with_backend_env(var, monkeypatch):
    import run
    monkeypatch.setenv(var, "xla")
    assert run.main(["--workload", "deepfm.ctr_online", "--seed", "1",
                     "--seconds", "1"]) == 2


@pytest.mark.parametrize("env", ["REPRO_KERNEL_BACKEND",
                                 "REPRO_KERNEL_TUNE_CACHE"])
def test_traced_run_refused_with_the_dispatch_steered(env, monkeypatch,
                                                      capsys):
    import run
    monkeypatch.setenv(env, "xla")
    assert run.main(["--workload", "deepfm.train", "--seed", "7",
                     "--seconds", "1", "--trace", "1"]) == 2
    assert "refused" in capsys.readouterr().err


def test_traced_run_refused_off_the_chip(spec, monkeypatch, capsys):
    """Off a TPU no split is measured, and nothing is set up."""
    import run
    for var in run.REFUSED_ENV:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(H.load_module("drivers", "train_loop"), "setup",
                        _never_set_up)
    assert run.main(["--workload", "deepfm.train", "--seed", "7",
                     "--seconds", "1", "--trace", "1"]) == 2
    assert "not 'tpu'" in capsys.readouterr().err
    wide = dict(spec, workloads=[dict(w, chips=99)
                                 for w in spec["workloads"]])
    with pytest.raises(run.Refused, match="needs 99"):
        run.run(wide, "deepfm.train", 7, 1.0, True, require_tpu=False)


def _never_set_up(ctx):
    raise AssertionError("set up after a refusal")


def test_configuration_without_its_work_is_refused(spec, monkeypatch):
    """Refused before set-up, naming the file, not after the window."""
    import run
    ref = H.load_module("configs", "deepfm")
    monkeypatch.delattr(ref, "forward_flops_per_example")
    monkeypatch.setattr(H.load_module("drivers", "train_loop"), "setup",
                        _never_set_up)
    for trace in (False, True):
        with pytest.raises(run.Refused, match="configs/deepfm.py"):
            C.run_small(spec, "deepfm.train", trace=trace)


def test_traced_train_run_reads_the_steps_scopes(spec, monkeypatch):
    """The train driver's step text, compiled again after the window,
    names every layer; each per-layer metric of the cell is reported."""
    C.with_device_ops(monkeypatch)
    out = C.run_small(spec, "deepfm.train", trace=True)
    assert out["correct"] is True, out["checks"]
    want = {m["name"] for m in spec["per_layer"]
            if "deepfm.train" in m.get("workloads", ["deepfm.train"])}
    assert set(out["metrics"]) == want
    device = ("fields_fwd_ms.train", "fields_bwd_ms.train",
              "first_order_ms.train", "tower_ms.train",
              "optimizer_ms.train", "unscoped_ms.train")
    ms = {k: out["metrics"][k]["value"] for k in device}
    # one 10 ns op per scope; the first order, tower and optimizer are
    # read in both directions
    assert ms["tower_ms.train"] == pytest.approx(2 * ms["fields_fwd_ms.train"])
    assert ms["fields_fwd_ms.train"] > 0
    assert out["metrics"]["compiles.train"]["value"] >= 1
    assert out["device"]["busy_s"] > 0


def test_costliest_compiles():
    import run
    rec = lambda n, s, h=0, m=0: {"compiles": n, "compile_s": s,
                                  "traces": n, "trace_s": 0.1,
                                  "cache_hits": h, "cache_misses": m}
    stats = {"jit(step)": rec(1, 90.0, m=1),
             "jit(broadcast_in_dim)": rec(40, 4.0, h=40),
             "jit(_take)": rec(0, 0.0)}
    assert run.total(stats, "compiles") == 41
    assert run.total(stats, "compile_s") == 94.0
    assert run.costliest(stats) == [
        ["jit(step)", 1, 90.0, 1, 0.1, 0, 1],
        ["jit(broadcast_in_dim)", 40, 4.0, 40, 0.1, 40, 0]]
    before = {"jit(step)": rec(1, 90.0, m=1), "jit(_take)": rec(0, 0.0)}
    assert run.since(stats, before) == {
        "jit(broadcast_in_dim)": rec(40, 4.0, h=40)}


def test_no_result_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and benchmarks/chip."""
    shutil.copy(os.path.join(C.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(C.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "deepfm.ctr_online", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
