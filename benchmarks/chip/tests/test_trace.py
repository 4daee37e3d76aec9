import pytest

import harness as H

T = H.load_module("trace")
DEV = "/device:TPU:0"


def ev(name, s, e, plane=DEV, line="XLA Ops"):
    return T.Event(plane, line, name, float(s), float(e - s))


def events():
    return [
        ev("bench.window", 0, 1000, plane="/host:CPU", line="python"),
        ev("bench.call", 90, 310, plane="/host:CPU", line="python"),
        ev("bench.fetch", 310, 700, plane="/host:CPU", line="python"),
        ev("mgqe_decode.1", 100, 200),
        ev("fusion.2", 150, 300),
        ev("mgqe_decode.3", 500, 600),
        ev("copy.4", 950, 1100),            # clipped at the window's end
        ev("outside", 1200, 1300),          # after the window
        ev("module", 0, 1000, line="XLA Modules"),   # not an op line
    ]


def test_busy_idle_kernels_and_breakdown():
    r = T.reduce(events(), ["mgqe_decode"])
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(350e-9)     # [100,300]+[500,600]+[950,1000]
    assert r.idle_share == pytest.approx(0.65)
    assert r.kernel_ns["mgqe_decode"] == pytest.approx(200)
    assert r.kernel_calls["mgqe_decode"] == 2
    assert r.busy_intervals == [(100, 300), (500, 600), (950, 1000)]
    ops = dict((k, v) for k, v in r.breakdown["device_ops"])
    assert ops["fusion.2"] == pytest.approx(150e-9)
    assert ops["copy.4"] == pytest.approx(50e-9)
    assert "outside" not in ops
    gaps = r.breakdown["idle_gaps"]
    assert [g[0] for g in gaps] == ["no bench span", "bench.fetch",
                                    "no bench span"]
    assert [g[1] for g in gaps] == pytest.approx([350e-9, 200e-9, 100e-9])
    assert r.spans["bench.call"] == [(90, 310)]


def test_busy_is_averaged_over_devices():
    evs = events() + [ev("fusion.9", 0, 1000, plane="/device:TPU:1")]
    r = T.reduce(evs, ["mgqe_decode"])
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx((350e-9 + 1000e-9) / 2)
    assert r.kernel_calls["mgqe_decode"] == 1


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce([e for e in events() if e.name != "bench.window"])
    with pytest.raises(ValueError, match="no device op"):
        T.reduce([e for e in events() if e.plane != DEV])


def test_load_reads_bench_spans_from_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with H.span("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    evs = T.load(T.find_xplane(str(tmp_path)))
    lo, hi = T.window_of(evs)
    assert hi > lo


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
    assert T.scope_of(path, depth) == want


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
    names = T.hlo_op_names(HLO)
    assert T.scope_of(names["fusion.190"]) == "fields.bwd"
    assert names["fusion.190"] == "jit(step)/transpose(jvp(fields))/neg"
    assert T.scope_of(names["multiply_reduce_fusion.90"]) == "fields"
    assert names["tanh.7"] == "jit(step)/jvp(tower)/tanh"
    # ops the compiler inserts with nothing to call keep no scope
    assert names["copy-start.1"] == "" and names["tuple.9"] == ""
    assert names["constant.1"] == "jit(step)/jvp(fields)"


def test_split_ms_of_the_optimizer():
    ns = {"optimizer/clip": 2e6, "optimizer/adagrad": 6e6,
          "optimizer": 2e6, "fields": 9e6, "optimizer_x": 1e6}
    assert T.split_ms(ns, "optimizer", steps=1) == pytest.approx(
        {"optimizer": 1.0, "optimizer/adagrad": 3.0, "optimizer/clip": 1.0})


def test_scope_ns_two_deep_sums_to_one_deep():
    names = dict(OP_NAMES, **{"fusion.5": "jit(step)/optimizer/clip/sqrt"})
    one = T.scope_ns(step_events(), names)
    two = T.scope_ns(step_events(), names, 2)
    assert two["optimizer/clip"] == one["optimizer"]
    assert sum(two.values()) == pytest.approx(sum(one.values()))


def step_events():
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
    evs = [e for e in step_events()
           if e.name != "%fusion.1 = f32[8] fusion(x)"]
    evs.append(ev("%fusion.1 = f32[8] fusion(x)", -100, 100))
    ns = T.scope_ns(evs, OP_NAMES)
    assert ns == pytest.approx({"fields": 100, "tower": 200,
                                "fields.bwd": 300, "": 100,
                                "optimizer": 100})
    # the scopes add up to the busy time when ops do not overlap
    assert sum(ns.values()) == pytest.approx(T.reduce(evs).busy_s * 1e9)


def test_top_ops_per_scope():
    evs = step_events() + [ev("%fusion.7 = f32[8] fusion(y)", 700, 900)]
    names = dict(OP_NAMES,
                 **{"fusion.7": "jit(step)/transpose(jvp(fields))/add"})
    top = T.top_ops(evs, names, n=1)
    assert top["fields.bwd"] == [["%scatter.3 = f32[8] scatter(z)", 300.0]]
    assert top[""] == [["%copy.4 = f32[8] copy(z)", 100.0]]
    assert [t[1] for t in T.top_ops(evs, names)["fields.bwd"]] == [300, 200]


def test_scope_ns_averages_over_devices():
    evs = step_events() + [ev("%fusion.2 = f32[8] fusion(y)", 0, 1000,
                         plane="/device:TPU:1")]
    ns = T.scope_ns(evs, OP_NAMES)
    assert ns["tower"] == pytest.approx((200 + 1000) / 2)


def test_hlo_op_names_of_a_compiled_step():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("tower"):
            return jnp.tanh(x) * 2.0

    hlo = jax.jit(jax.grad(lambda x: f(x).sum())).lower(
        jnp.ones(8)).compile().as_text()
    names = T.hlo_op_names(hlo)
    scopes = {T.scope_of(p) for p in names.values()}
    assert scopes <= {"tower", "tower.bwd", ""} and "tower.bwd" in scopes


def test_reduce_splits_by_scope_with_the_programs_texts():
    names = dict(OP_NAMES, **{"fusion.5": "jit(step)/optimizer/clip/sqrt"})
    r = T.reduce(step_events(), op_names=names)
    assert r.scope_ns[1] == T.scope_ns(step_events(), names)
    assert r.scope_ns[2] == T.scope_ns(step_events(), names, 2)
    assert set(r.scope_ns) == set(T.SCOPE_DEPTHS)
    # copy.4 (100 of the window's 800 device ns) is in no text
    assert r.found_share == pytest.approx(700 / 800)
    plain = T.reduce(step_events())
    assert plain.scope_ns == {} and plain.found_share is None
    assert plain.busy_s == r.busy_s


def test_found_share_counts_device_time_not_ops():
    assert T.found_share(step_events(), {}) == 0.0
    every = {T.op_name(e.name): "" for e in step_events()}
    assert T.found_share(step_events(), every) == 1.0
