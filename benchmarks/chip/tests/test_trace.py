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
