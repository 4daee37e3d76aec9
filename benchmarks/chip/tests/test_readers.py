"""The eight per-layer readers of ``deepfm.train`` that read named
scopes and set-up's compiles, from synthetic device events and a small
step text, and ``mfu.train`` by the formula it has always had."""
import json
import os
import types

import pytest

import conftest as C
import harness as H

T = H.load_module("trace")
DEV = "/device:TPU:0"
MS = 1e6        # ns

# a step's text: one instruction for each scope, a fusion whose scope
# is in its body, and a copy the compiler inserted with none
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %gather.9 = f32[8]{0} gather(%p0), metadata={op_name="jit(step)/jvp(fields)/gather"}
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %scatter.2 = f32[8]{0} scatter(%x), metadata={op_name="jit(step)/transpose(jvp(fields))/scatter-add"}
  %gather.3 = f32[8]{0} gather(%x), metadata={op_name="jit(step)/jvp(first_order)/gather"}
  %scatter.4 = f32[8]{0} scatter(%x), metadata={op_name="jit(step)/transpose(jvp(first_order))/scatter-add"}
  %dot.5 = f32[8]{0} dot(%x), metadata={op_name="jit(step)/jvp(tower)/dot_general"}
  %dot.6 = f32[8]{0} dot(%x), metadata={op_name="jit(step)/transpose(jvp(tower))/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%x), metadata={op_name="jit(step)/optimizer/adagrad/div"}
  ROOT %copy.8 = f32[8]{0} copy(%fusion.7)
}
"""
# instruction -> its device ms in each of the window's two steps
STEP_MS = {"fusion.1": 3.0, "scatter.2": 5.0, "gather.3": 1.0,
           "scatter.4": 2.0, "dot.5": 0.5, "dot.6": 0.25, "fusion.7": 4.0,
           "copy.8": 0.25}
WANT = {"fields_fwd_ms.train": 3.0, "fields_bwd_ms.train": 5.0,
        "first_order_ms.train": 3.0, "tower_ms.train": 0.75,
        "optimizer_ms.train": 4.0, "unscoped_ms.train": 0.25}
SETUP = {"jit(step)": {"compiles": 1, "compile_s": 80.0, "traces": 1,
                       "trace_s": 1.0, "cache_hits": 0, "cache_misses": 1},
         "jit(broadcast_in_dim)": {"compiles": 18, "compile_s": 1.5,
                                   "traces": 18, "trace_s": 0.2,
                                   "cache_hits": 0, "cache_misses": 18}}


def events():
    """Two steps (the window's ``steps`` is 1) of the ops above, end to
    end from t=0, inside a 100 ms window."""
    evs = [T.Event("/host:CPU", "python", "bench.window", 0.0, 100 * MS)]
    t = 0.0
    for _ in range(2):
        for name, ms in STEP_MS.items():
            evs.append(T.Event(DEV, "XLA Ops", f"%{name} = f32[8] op(x)", t,
                               ms * MS))
            t += ms * MS
    return evs


def readers():
    with open(os.path.join(C.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    return {m["name"]: H.load_module("metrics", m["name"])
            for m in spec["per_layer"]
            if "deepfm.train" in m.get("workloads", ["deepfm.train"])}


def record(op_names, rate=475_000.0):
    import run
    ctx = types.SimpleNamespace(
        model=H.load_json("configs", "deepfm.json")["model"], traffic={},
        ref=H.load_module("configs", "deepfm"))
    window = {"stats": {"steps": 1, "window_s": 0.1},
              "metrics": {"train_examples_per_s": rate}}
    return run.Record(ctx, window, T.reduce(events(), (), op_names),
                      C.CPU_PEAKS, SETUP)


def test_scope_readers_give_ms_per_step_and_sum_to_busy():
    rec = record(T.hlo_op_names(HLO))
    assert rec.trace.found_share == 1.0
    got = {k: r.read(rec) for k, r in readers().items() if k in WANT}
    assert got == pytest.approx(WANT)
    busy_ms_per_step = rec.trace.busy_s * 1e3 / (rec.stats["steps"] + 1)
    assert sum(got.values()) == pytest.approx(busy_ms_per_step)


def test_compile_readers_sum_over_functions():
    rec = record(T.hlo_op_names(HLO))
    rs = readers()
    assert rs["compile_s.train"].read(rec) == pytest.approx(81.5)
    assert rs["compiles.train"].read(rec) == 19
    rec.setup_compiles = {}
    assert rs["compiles.train"].read(rec) is None


def test_without_programs_the_scope_readers_read_nothing():
    rec = record({})
    assert rec.trace.scope_ns == {}
    for name, r in readers().items():
        if name in WANT:
            assert r.read(rec) is None, name
    # the others read as before
    assert readers()["idle_share.train"].read(rec) == pytest.approx(
        100 * (1 - rec.trace.busy_s / rec.trace.window_s))
    assert readers()["compiles.train"].read(rec) == 19


def test_mfu_train_is_three_forward_passes_over_the_peak():
    rec = record({}, rate=475_000.0)
    assert rec.flops == 954_000      # from configs/deepfm.py
    assert readers()["mfu.train"].read(rec) == pytest.approx(
        100 * 3 * 954_000 * 475_000.0 / C.CPU_PEAKS["bf16_flops_per_s"])
