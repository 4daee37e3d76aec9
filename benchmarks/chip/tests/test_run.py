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
