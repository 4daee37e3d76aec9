import pytest

import harness as H

S = H.load_module("scopes")
T = H.load_module("trace")
DEV = "/device:TPU:0"


def ev(name, s, e, plane=DEV, line="XLA Ops"):
    return T.Event(plane, line, name, float(s), float(e - s))


@pytest.mark.parametrize("path,want,depth", [
    ("jit(step)/optimizer/adagrad/sqrt", "optimizer/adagrad", 2),
    ("jit(step)/jit(main)/optimizer/clip/jit(norm)/sqrt", "optimizer/clip",
     2),
    ("jit(step)/optimizer/add", "optimizer", 2),
    ("jit(step)/transpose(jvp(tower))/mlp/dot_general", "tower/mlp.bwd", 2),
    ("jit(step)/add", "", 2),
] + [(p, w, 1) for p, w in [
    ("jit(step)/jit(main)/jvp(fields)/jit(_take)/gather", "fields"),
    ("jit(step)/transpose(jvp(fields))/jit(_take)/scatter-add",
     "fields.bwd"),
    ("jit(step)/transpose(jvp(jit(loss)))/tower/dot_general", "tower.bwd"),
    ("jit(step)/jvp(jit(loss))/tower/dot_general", "tower"),
    ("jit(step)/vmap(checkpoint(transpose(jvp(tower))))/mul", "tower.bwd"),
    ("jit(step)/optimizer/adagrad/sqrt", "optimizer"),
    ("jit(step)/transpose(jvp(fields))/mul;jit(step)/tower/add",
     "fields.bwd"),
    ("jit(step)/jvp()/mul", ""),
    ("jit(step)/add", ""),
    ("reduce_sum", ""),
    ("", ""),
]])
def test_scope_of(path, want, depth):
    assert S.scope_of(path, depth) == want


# the shape of a v5e step's text: a scatter fusion built with no
# op_name, its body calling a nested fusion whose ops carry one; a
# multi-output fusion whose first op_name names no scope
HLO = """HloModule jit_step, is_scheduled=true

%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%a, %b)
}

%fused_computation.190.clone (p0: f32[1280,2], p1: s32[40960], p2: f32[40960,2]) -> f32[1280,2] {
  %p0 = f32[1280,2]{1,0} parameter(0)
  %p2 = f32[40960,2]{1,0} parameter(2)
  %reshape.4316 = f32[8192,5,2]{2,1,0} reshape(%p2), metadata={op_name="jit(step)/transpose(jvp(fields))/neg" stack_frame_id=66}
  ROOT %scatter.253 = f32[1280,2]{1,0} scatter(%p0, %p1, %reshape.4316), to_apply=%region_1.2
}

%fused_computation.876 (param_0: s32[40960], param_1: f32[40960,2]) -> f32[1280,2] {
  %param_0 = s32[40960]{0} parameter(0)
  ROOT %fusion.535 = f32[1280,2]{1,0} fusion(%param_0), kind=kCustom, calls=%fused_computation.190.clone
}

%fused_computation.904 (q0: s32[8192,5]) -> (s32[8192,5], s32[8192,5]) {
  %constant.1 = s32[] constant(0), metadata={op_name="jit(step)/jvp(fields)"}
  %pad.2 = s32[8192,5]{1,0} pad(%q0, %constant.1), metadata={op_name="jit(step)/jvp(fields)/jit(take_along_axis)/gather" stack_frame_id=62}
  ROOT %tuple.3 = (s32[8192,5], s32[8192,5]) tuple(%pad.2, %pad.2)
}

ENTRY %main.334 (x: s32[40960], y: f32[40960,2]) -> (f32[1280,2]) {
  %x = s32[40960]{0} parameter(0)
  %fusion.190 = f32[1280,2]{1,0:T(8,128)} fusion(%x, %y), kind=kCustom, calls=%fused_computation.876, backend_config={"flag_configs":[]}
  %multiply_reduce_fusion.90 = (s32[8192,5], s32[8192,5]) fusion(%x), kind=kLoop, calls=%fused_computation.904
  %tanh.7 = f32[8] tanh(%z), metadata={op_name="jit(step)/jvp(tower)/tanh"}
  %copy-start.1 = (f32[8], f32[8], u32[]) copy-start(%tanh.7)
  ROOT %tuple.9 = (f32[1280,2]) tuple(%fusion.190)
}
"""


def test_hlo_op_names_takes_a_fusions_scope_from_its_body():
    names = S.hlo_op_names(HLO)
    assert S.scope_of(names["fusion.190"]) == "fields.bwd"
    assert names["fusion.190"] == "jit(step)/transpose(jvp(fields))/neg"
    assert S.scope_of(names["multiply_reduce_fusion.90"]) == "fields"
    assert names["tanh.7"] == "jit(step)/jvp(tower)/tanh"
    # ops the compiler inserts with nothing to call keep no scope
    assert names["copy-start.1"] == "" and names["tuple.9"] == ""
    assert names["constant.1"] == "jit(step)/jvp(fields)"


def test_split_ms_of_the_optimizer():
    ns = {"optimizer/clip": 2e6, "optimizer/adagrad": 6e6,
          "optimizer": 2e6, "fields": 9e6, "optimizer_x": 1e6}
    assert S.split_ms(ns, "optimizer", steps=1) == pytest.approx(
        {"optimizer": 1.0, "optimizer/adagrad": 3.0, "optimizer/clip": 1.0})


def test_scope_ns_two_deep_sums_to_one_deep():
    names = dict(OP_NAMES, **{"fusion.5": "jit(step)/optimizer/clip/sqrt"})
    one, two = S.scope_ns(events(), names), S.scope_ns(events(), names, 2)
    assert two["optimizer/clip"] == one["optimizer"]
    assert sum(two.values()) == pytest.approx(sum(one.values()))


@pytest.mark.parametrize("env", ["REPRO_KERNEL_BACKEND",
                                 "REPRO_KERNEL_TUNE_CACHE"])
def test_refused_with_the_dispatch_steered(env, monkeypatch, capsys):
    monkeypatch.setenv(env, "xla")
    assert S.main(["--workload", "deepfm.train", "--seed", "7",
                   "--seconds", "1"]) == 2
    assert "refused" in capsys.readouterr().err


def test_refused_off_the_chip(monkeypatch, capsys):
    """Off a TPU no split is measured, and nothing is set up."""
    for var in S.R.REFUSED_ENV:
        monkeypatch.delenv(var, raising=False)
    assert S.main(["--workload", "deepfm.train", "--seed", "7",
                   "--seconds", "1"]) == 2
    assert "not 'tpu'" in capsys.readouterr().err
    with pytest.raises(S.R.Refused):
        S._device({"name": "x", "chips": 1})
    assert S._device({"name": "x", "chips": 1}, require_tpu=False)[
        "count"] >= 1
    with pytest.raises(S.R.Refused, match="needs 99"):
        S._device({"name": "x", "chips": 99}, require_tpu=False)


def events():
    return [
        ev("bench.window", 0, 1000, plane="/host:CPU", line="python"),
        ev("%fusion.1 = f32[8] fusion(x)", 0, 100),     # half before
        ev("%fusion.2 = f32[8] fusion(y)", 100, 300),
        ev("%scatter.3 = f32[8] scatter(z)", 300, 600),
        ev("%copy.4 = f32[8] copy(z)", 600, 700),
        ev("%fusion.5 = f32[8] fusion(w)", 900, 1200),   # clipped
        ev("module", 0, 1000, line="XLA Modules"),      # not an op line
    ]


OP_NAMES = {"fusion.1": "jit(step)/jvp(fields)/gather",
            "fusion.2": "jit(step)/jvp(tower)/dot_general",
            "scatter.3": "jit(step)/transpose(jvp(fields))/scatter-add",
            "fusion.5": "jit(step)/optimizer/adagrad/sqrt"}


def test_scope_ns_inside_the_window():
    evs = [e for e in events() if e.name != "%fusion.1 = f32[8] fusion(x)"]
    evs.append(ev("%fusion.1 = f32[8] fusion(x)", -100, 100))
    ns = S.scope_ns(evs, OP_NAMES)
    assert ns == pytest.approx({"fields": 100, "tower": 200,
                                "fields.bwd": 300, "": 100,
                                "optimizer": 100})
    # the scopes add up to the busy time when ops do not overlap
    assert sum(ns.values()) == pytest.approx(T.reduce(evs).busy_s * 1e9)


def test_top_ops_per_scope():
    evs = events() + [ev("%fusion.7 = f32[8] fusion(y)", 700, 900)]
    names = dict(OP_NAMES, **{"fusion.7": "jit(step)/transpose(jvp(fields))/add"})
    top = S.top_ops(evs, names, n=1)
    assert top["fields.bwd"] == [["%scatter.3 = f32[8] scatter(z)", 300.0]]
    assert top[""] == [["%copy.4 = f32[8] copy(z)", 100.0]]
    assert [t[1] for t in S.top_ops(evs, names)["fields.bwd"]] == [300, 200]


def test_scope_ns_averages_over_devices():
    evs = events() + [ev("%fusion.2 = f32[8] fusion(y)", 0, 1000,
                         plane="/device:TPU:1")]
    ns = S.scope_ns(evs, OP_NAMES)
    assert ns["tower"] == pytest.approx((200 + 1000) / 2)


def test_per_step_ms_divides_by_steps_plus_one():
    ns = {"fields": 4e6, "fields.bwd": 8e6, "first_order": 1e6,
          "first_order.bwd": 3e6, "tower": 2e6, "tower.bwd": 2e6,
          "optimizer": 6e6, "": 4e6}
    ms = S.per_step_ms(ns, steps=1)
    assert ms == pytest.approx({
        "fields_fwd_ms": 2.0, "fields_bwd_ms": 4.0, "first_order_ms": 2.0,
        "tower_ms": 2.0, "optimizer_ms": 3.0, "unscoped_ms": 2.0})
    assert sum(ms.values()) == pytest.approx(sum(ns.values()) / 2 / 1e6)
    assert S.per_step_ms({}, steps=9)["tower_ms"] == 0.0


def test_compile_totals_and_costliest():
    rec = lambda n, s, h=0, m=0: {"compiles": n, "compile_s": s,
                                  "traces": n, "trace_s": 0.1,
                                  "cache_hits": h, "cache_misses": m}
    stats = {"jit(step)": rec(1, 90.0, m=1),
             "jit(broadcast_in_dim)": rec(40, 4.0, h=40),
             "jit(_take)": rec(0, 0.0)}
    assert S.compile_totals(stats) == (94.0, 41)
    assert list(S.costliest(stats)) == [
        ["jit(step)", 1, 90.0, 1, 0.1, 0, 1],
        ["jit(broadcast_in_dim)", 40, 4.0, 40, 0.1, 40, 0]]


def test_hlo_op_names_of_a_compiled_step():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("tower"):
            return jnp.tanh(x) * 2.0

    hlo = jax.jit(jax.grad(lambda x: f(x).sum())).lower(
        jnp.ones(8)).compile().as_text()
    names = S.hlo_op_names(hlo)
    scopes = {S.scope_of(p) for p in names.values()}
    assert scopes <= {"tower", "tower.bwd", ""} and "tower.bwd" in scopes
