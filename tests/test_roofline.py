"""Roofline machinery: loop-weighted HLO analysis + term computation,
parser edge cases (malformed/partial HLO text must degrade, never
raise), and the per-kernel achieved-vs-peak helper the bench gate
uses (DESIGN.md §11)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import (PEAK_FLOPS_BF16, analyze, kernel_roofline,
                            terms_from_hlo)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_weighted_by_trip_count():
    W = jnp.ones((8, 128, 128))
    x0 = jnp.ones((4, 128))

    def scanned(x, W):
        def body(c, w):
            return c @ w, None
        return jax.lax.scan(body, x, W)[0]

    def unrolled(x, W):
        for i in range(8):
            x = x @ W[i]
        return x

    cs = analyze(_compiled_text(scanned, x0, W))
    cu = analyze(_compiled_text(unrolled, x0, W))
    expected = 8 * 2 * 4 * 128 * 128
    assert abs(cs.flops - expected) / expected < 0.01
    assert abs(cu.flops - expected) / expected < 0.01
    assert not cs.warnings


def test_nested_scan_weighting():
    W = jnp.ones((3, 4, 64, 64))
    x0 = jnp.ones((2, 64))

    def nested(x, W):
        def outer(c, w_group):
            def inner(cc, w):
                return cc @ w, None
            return jax.lax.scan(inner, c, w_group)[0], None
        return jax.lax.scan(outer, x, W)[0]

    c = analyze(_compiled_text(nested, x0, W))
    expected = 12 * 2 * 2 * 64 * 64
    assert abs(c.flops - expected) / expected < 0.02


def test_dot_general_batched_flops():
    a = jnp.ones((8, 32, 16))
    b = jnp.ones((8, 16, 24))
    c = analyze(_compiled_text(lambda a, b: jnp.einsum("bik,bkj->bij", a, b),
                               a, b))
    expected = 2 * 8 * 32 * 24 * 16
    assert abs(c.flops - expected) / expected < 0.01


def test_collective_bytes_from_handwritten_hlo():
    hlo = """
HloModule test

ENTRY %main (p0: f32[128,4]) -> f32[128,4] {
  %p0 = f32[128,4]{1,0} parameter(0)
  %ar = f32[128,4]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%sum
  ROOT %cp = f32[128,4]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
    c = analyze(hlo)
    assert c.collective_by_kind.get("all-reduce") == 128 * 4 * 4
    assert c.collective_by_kind.get("collective-permute") == 128 * 4 * 4
    assert c.collective_counts == {"all-reduce": 1, "collective-permute": 1}


def test_collectives_inside_while_weighted():
    """A psum inside a scanned body must count once per iteration."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def inner(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "x"), None
        return jax.lax.scan(body, jnp.zeros((64,)), xs)[0]

    # check_vma=False: the scan carry's replication type flips under
    # psum, which strict replication checking rejects on a 1-device mesh
    f = jax.shard_map(inner, mesh=mesh, in_specs=P(None, None),
                      out_specs=P(), check_vma=False)
    txt = jax.jit(f).lower(jnp.ones((5, 64))).compile().as_text()
    c = analyze(txt)
    # 5 iterations x 64 f32 = 1280 bytes (if XLA keeps the psum; on a
    # 1-device mesh it may elide it — accept 0 or the weighted value)
    ar = c.collective_by_kind.get("all-reduce", 0)
    assert ar in (0, 5 * 64 * 4)


def test_terms_and_dominance():
    class FakeCost:
        flops = 197e12          # exactly 1s of compute on one chip
        bytes = 819e9 / 2       # 0.5s of HBM
        collective_bytes = 50e9 * 2   # 2s of ICI
        collective_by_kind = {}
        collective_counts = {}
        warnings = []

    t = terms_from_hlo(FakeCost(), chips=1, model_flops=197e12 / 2)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 0.5) < 1e-9
    assert abs(t.collective_s - 2.0) < 1e-9
    assert t.dominant == "collective"
    assert abs(t.useful_fraction - 0.5) < 1e-9
    # roofline fraction: ideal 0.5s of useful compute / 2s bound = 0.25
    assert abs(t.roofline_fraction - 0.25) < 1e-9


def test_tpu_fusion_mode_drops_convert_fusions():
    """analyze(tpu_fusion=True) must charge convert-only fusions zero
    (CPU backend emulates bf16 in f32; TPU is native)."""
    x = jnp.ones((256, 256), jnp.bfloat16)

    def f(x):
        return (x.astype(jnp.float32) @ x.astype(jnp.float32).T
                ).astype(jnp.bfloat16)

    txt = _compiled_text(f, x)
    raw = analyze(txt)
    cal = analyze(txt, tpu_fusion=True)
    assert cal.bytes <= raw.bytes
    assert cal.flops == raw.flops           # flops unaffected


# ------------------------------------------------ parser edge cases

@pytest.mark.parametrize("text", [
    "",                                        # empty module text
    "HloModule empty\n",                       # header, no computations
    "not hlo at all\n{}\nrandom noise",        # garbage
])
def test_analyze_empty_or_garbage_text_degrades(text):
    """No computations -> zero cost + a warning, never an exception."""
    c = analyze(text)
    assert c.flops == 0 and c.bytes == 0 and c.collective_bytes == 0
    assert c.warnings == ["no entry computation found"]


def test_analyze_unparseable_shape_strings_skipped():
    """Ops whose type strings don't parse (opaque/token/custom dtypes)
    contribute zero bytes instead of crashing the sweep."""
    hlo = """
HloModule weird

ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %tok = token[] after-all()
  %oc = opaque[] custom-call(%p0), custom_call_target="noop"
  %strange = mystery[8,?]{1,0} add(%p0, %p0)
  ROOT %out = f32[8,4]{1,0} add(%p0, %p0)
}
"""
    c = analyze(hlo)                 # must not raise
    # the well-formed root add still counts: 2 operands + 1 output
    assert c.bytes >= 3 * 8 * 4 * 4
    assert c.flops >= 8 * 4


def test_analyze_fusion_with_multiply_shapes():
    """A fusion op charges operands + outputs once (innards excluded),
    including tuple-shaped fusion outputs."""
    hlo = """
HloModule fused

%fused_computation (a: f32[16,8], b: f32[16,8]) -> f32[16,8] {
  %a = f32[16,8]{1,0} parameter(0)
  %b = f32[16,8]{1,0} parameter(1)
  %m = f32[16,8]{1,0} multiply(%a, %b)
  ROOT %s = f32[16,8]{1,0} add(%m, %b)
}

ENTRY %main (p0: f32[16,8], p1: f32[16,8]) -> (f32[16,8], f32[16,8]) {
  %p0 = f32[16,8]{1,0} parameter(0)
  %p1 = f32[16,8]{1,0} parameter(1)
  %f = f32[16,8]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused_computation
  ROOT %t = (f32[16,8]{1,0}, f32[16,8]{1,0}) tuple(%f, %p1)
}
"""
    c = analyze(hlo)
    n = 16 * 8 * 4
    # fusion: 2 operand reads + 1 output write; tuple is free; the
    # multiply/add INSIDE the fusion body add flops but no bytes
    assert c.bytes == 3 * n
    assert c.flops == 2 * 16 * 8


def test_analyze_while_without_trip_count_warns_once():
    hlo = """
HloModule loopy

%body (x: f32[4]) -> f32[4] {
  ROOT %x = f32[4]{0} parameter(0)
}

%cond (x: f32[4]) -> pred[] {
  %x = f32[4]{0} parameter(0)
  ROOT %p = pred[] constant(false)
}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %w = f32[4]{0} while(%p0), condition=%cond, body=%body
}
"""
    c = analyze(hlo)
    assert any("unknown trip count" in w for w in c.warnings)


# ------------------------------------- kernel_roofline (bench gate)

def test_kernel_roofline_fraction_in_unit_interval():
    x = jnp.ones((256, 64))
    txt = jax.jit(lambda x: x @ x.T).lower(x).compile().as_text()
    out = kernel_roofline(txt, measured_s=1e-3)
    f = out["roofline_fraction"]
    assert f is not None and 0.0 < f <= 1.0
    assert out["bound_ms"] > 0
    assert out["bound_kind"] in ("compute", "memory", "collective")


def test_kernel_roofline_clamps_at_one():
    """A measured time below the hardware bound clamps to exactly 1.0
    (the gate treats >1 as a measurement artifact, not an achievement)."""
    x = jnp.ones((512, 512))
    txt = jax.jit(lambda x: x @ x).lower(x).compile().as_text()
    assert kernel_roofline(txt, measured_s=1e-12)["roofline_fraction"] == 1.0


def test_kernel_roofline_degenerate_inputs():
    assert kernel_roofline("", measured_s=1e-3)["roofline_fraction"] is None
    x = jnp.ones((16, 16))
    txt = jax.jit(lambda x: x + x).lower(x).compile().as_text()
    assert kernel_roofline(txt, measured_s=0.0)["roofline_fraction"] is None


def test_bench_entries_carry_roofline_fraction():
    """Quick-mode bench functions must attach roofline_fraction ∈ (0, 1]
    to every kernel entry — the invariant the bench exit code gates."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    try:
        import kernel_bench
    finally:
        sys.path.pop(0)
    results = {}
    # tiny shapes: seconds, not the bench's minutes
    kernel_bench.bench_rq_decode(results, n=2048, d=16, M=2, K=8,
                                 batch=256)
    kernel_bench.bench_adc(results, d=16, D=4, K=8, n_cand=2048)
    kernel_bench.bench_dpq_assign(results, d=16, D=4, K=8, b=512)
    for name in ("rq_decode", "adc", "dpq_assign"):
        f = results[name]["roofline_fraction"]
        assert f is not None and 0.0 < f <= 1.0, (name, f)
    assert results["rq_decode"]["parity_ok"]
    assert "speedup_ok" in results["rq_decode"]
    assert results["rq_decode"]["tuned_block_b"] in (64, 128, 256, 512)


def test_remat_recompute_visible_in_flops():
    """jax.checkpoint recompute inside a scan shows up as extra counted
    FLOPs (what useful_frac is designed to catch).  The scan stops XLA
    from CSE-ing the recompute away."""
    W = jnp.ones((4, 64, 64))
    x = jnp.ones((32, 64))

    def make(remat):
        def body(c, w):
            f = lambda c: jnp.tanh(c @ w) @ w
            if remat:
                f = jax.checkpoint(f)
            return f(c), None

        def loss(x, W):
            y, _ = jax.lax.scan(body, x, W)
            return jnp.sum(y)
        return loss

    g_plain = analyze(_compiled_text(jax.grad(make(False)), x, W))
    g_remat = analyze(_compiled_text(jax.grad(make(True)), x, W))
    assert g_remat.flops >= g_plain.flops * 1.1
