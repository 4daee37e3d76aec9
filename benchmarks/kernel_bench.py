"""Kernel micro-benchmarks.

The container is CPU-only, so wall-times here are *reference-path* CPU
numbers (the Pallas kernels run in interpret mode and are not
representative of TPU).  What IS meaningful on CPU:

  * bytes-moved accounting per path (the roofline input) — e.g. ADC
    reads N*D code bytes vs N*d*4 embedding bytes, a 32x stream cut;
  * XLA-path timings of the jnp reference implementations, which the
    serving benches compare (quantized vs full lookup);
  * fused-vs-unfused serving decode through the backend dispatch layer
    (on TPU "fused" is the Pallas mgqe_decode kernel; off-TPU the
    dispatcher resolves to the XLA reference, and the resolved backend
    is recorded alongside the numbers).

Every kernel entry additionally records ``roofline_fraction`` — the
three-term v5e roofline bound of its compiled HLO over the measured
time (``repro.roofline.kernel_roofline``, DESIGN.md §11) — and the
decode benches record the block geometry the autotune cache picked
(``tuned_block_b``/``tuned_block_d``).  Exit-code gates: every parity
flag, the hot-cache / rq-decode / mpe-decode speedup bars, the mpe
tail-tier byte bar, the async SLO, the retrieval-scale
recall/peak-memory pair (``recall_ok`` / ``build_peak_ok``),
``roofline_fraction`` ∈ (0, 1] on each kernel entry, and — off the
interpret backend — ``roofline_fraction`` >= 0.001 (an entry further
under the bound than that is flagged ``roofline_suspect``: the
measurement likely caught compile or an unblocked path).

Results are written to a BENCH_*.json (default BENCH_kernels.json) so
PR-over-PR runs can be diffed.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Embedding, EmbeddingConfig
from repro.core.partition import frequency_boundaries
from repro.kernels import dispatch
from repro.kernels.mgqe_decode.ref import mgqe_decode_ref
from repro.kernels.pq_score.ref import build_lut_ref, pq_score_ref


def _time(fn, *args, iters=20, repeats=3):
    """Best-of-``repeats`` mean over ``iters`` calls (best-of damps
    scheduler noise on shared CPU runners; compile paid outside)."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _roofline(jfn, *args, measured_s):
    """``roofline_*`` fields for one jitted callable: the three-term
    v5e bound of its compiled HLO vs the measured time (DESIGN.md §11).
    Lower/compile only — adds no executions to the bench."""
    from repro.roofline import kernel_roofline
    rf = kernel_roofline(jfn.lower(*args).compile().as_text(), measured_s)
    return {"roofline_fraction": rf["roofline_fraction"],
            "roofline_bound_ms": rf["bound_ms"],
            "roofline_bound_kind": rf["bound_kind"]}


def bench_serving_decode(results: dict, n: int, d: int, D: int, K: int,
                         batch: int):
    """Fused (dispatched serving_lookup) vs unfused (take + jnp decode)
    vs the full-table FE baseline."""
    from repro.core import dpq
    k = jax.random.PRNGKey(0)
    bounds = frequency_boundaries(n, (0.1,))
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="mgqe",
                          num_subspaces=D, num_centroids=K,
                          tier_boundaries=bounds,
                          tier_num_centroids=(K, max(2, K // 4)))
    codes = jax.random.randint(k, (n, D), 0, K).astype(jnp.uint8)
    cent = jax.random.normal(k, (D, K, d // D))
    full_table = jax.random.normal(k, (n, d))
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, n)

    t_full = _time(jax.jit(lambda t, i: jnp.take(t, i, axis=0)),
                   full_table, ids)
    # unfused: row-wise codes gather, then take_along_axis decode in HBM
    t_unfused = _time(jax.jit(lambda c, ce, i: mgqe_decode_ref(
        jnp.take(c, i, axis=0).astype(jnp.int32), ce)), codes, cent, ids)
    # fused: the serving hot path as Embedding.serve runs it — through
    # the kernel dispatch layer (Pallas one-hot-matmul kernel on TPU),
    # block_b from the autotune cache (tuned here on the benched shape)
    backend = dispatch.resolve_backend(cfg.kernel_backend)
    sel = jnp.take(codes, ids, axis=0).astype(jnp.int32)
    tuned = next(iter(dispatch.tune("mgqe_decode", [(sel, cent)],
                                    backend=backend).values()))
    fused_fn = jax.jit(lambda c, ce, i: dpq.serving_lookup(
        c, ce, i, backend=backend))
    t_fused = _time(fused_fn, codes, cent, ids)

    print(f"lookup B={batch} of n={n/1e6:.1f}M d={d}: "
          f"full {t_full*1e3:.2f} ms ({n*d*4/1e6:.0f} MB table) | "
          f"unfused decode {t_unfused*1e3:.2f} ms | "
          f"fused[{backend}] {t_fused*1e3:.2f} ms "
          f"({n*D/1e6:.0f} MB codes + {K*d*4/1e3:.0f} KB centroids)")
    print(f"  table bytes cut: {n*d*4/(n*D + K*d*4):.1f}x "
          f"(serving size {100*cfg.serving_size_bits()/(n*d*32):.1f}% "
          f"of full)")
    results["serving_decode"] = {
        "vocab": n, "dim": d, "num_subspaces": D, "num_centroids": K,
        "batch": batch,
        "fused_backend": backend,
        "full_take_ms": t_full * 1e3,
        "unfused_decode_ms": t_unfused * 1e3,
        "fused_decode_ms": t_fused * 1e3,
        "fused_vs_unfused_speedup": t_unfused / t_fused,
        "tuned_block_b": tuned.get("block_b"),
        "table_mbytes_full": n * d * 4 / 1e6,
        "table_mbytes_codes": (n * D + K * d * 4) / 1e6,
        "hbm_bytes_cut_x": n * d * 4 / (n * D + K * d * 4),
        "serving_size_pct_of_full":
            100 * cfg.serving_size_bits() / (n * d * 32),
        **_roofline(fused_fn, codes, cent, ids, measured_s=t_fused),
    }


def bench_engine(results: dict, n: int, d: int, D: int, K: int,
                 n_requests: int, req_batch: int):
    """Micro-batched engine throughput on the exported artifact."""
    from repro.launch.engine import ServingEngine, drive_random_stream
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="dpq",
                          num_subspaces=D, num_centroids=K)
    emb = Embedding(cfg)
    params = emb.init(jax.random.PRNGKey(0))
    artifact = emb.export(params)
    engine = ServingEngine(emb, artifact, max_queue=4096)
    st = drive_random_stream(engine, n, n_requests, req_batch)
    print(f"engine: {st.requests} reqs / {st.lookups} lookups "
          f"-> {st.lookups_per_s:,.0f} lookups/s "
          f"(block_b={engine.block_b}, {st.flushes} flushes)")
    results["serving_engine"] = {
        "vocab": n, "dim": d, "block_b": engine.block_b,
        **st.as_dict(),
    }


def bench_sharded_decode(results: dict, n: int, d: int, D: int, K: int,
                         batch: int):
    """Sharded (Mesh(data=2, model=2) shard_map quantized gather) vs
    single-device serving decode on the same artifact + batch.

    Needs >= 4 devices; as a script this file forces 4 host devices
    before jax initializes, so the bench runs on a CPU dev box too (the
    shards then timeshare one CPU — the number that matters there is
    parity and the wire-byte accounting, not wall-clock).
    """
    import dataclasses
    from repro.sharding.rules import shard_quantized_artifact
    if jax.device_count() < 4:
        print(f"sharded decode: skipped ({jax.device_count()} device(s); "
              f"run benchmarks/kernel_bench.py as a script for forced "
              f"host devices)")
        results["sharded_decode"] = {
            "skipped": f"needs >= 4 devices, have {jax.device_count()}"}
        return
    k = jax.random.PRNGKey(0)
    bounds = frequency_boundaries(n, (0.1,))
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="mgqe",
                          num_subspaces=D, num_centroids=K,
                          tier_boundaries=bounds,
                          tier_num_centroids=(K, max(2, K // 4)),
                          sharded_codes=True)
    artifact = {
        "codes": jax.random.randint(k, (n, D), 0, K).astype(jnp.uint8),
        "centroids": jax.random.normal(k, (D, K, d // D)),
    }
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, n)

    single_cfg = dataclasses.replace(cfg, sharded_codes=False)
    single_fn = jax.jit(Embedding(single_cfg).serve)
    t_single = _time(single_fn, artifact, ids)
    ref = single_fn(artifact, ids)

    # tune the decode block geometry on the shard-local shape FIRST:
    # the shard body's batch is the all-gathered GLOBAL batch, so the
    # tuner sees exactly what each shard will decode.  quantized_gather
    # defaults block_b to this cache; the pinned variant below times
    # the old behaviour (cfg.decode_block_b forced into the shard body)
    backend = dispatch.resolve_backend(cfg.kernel_backend)
    sel = jnp.take(artifact["codes"], ids, axis=0).astype(jnp.int32)
    tuned = next(iter(dispatch.tune(
        "mgqe_decode", [(sel, artifact["centroids"])],
        backend=backend).values()))

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    emb_sharded = Embedding(cfg)
    art_sharded = shard_quantized_artifact(artifact, cfg, mesh)
    with mesh:
        sharded_fn = jax.jit(emb_sharded.serve)
        t_sharded = _time(sharded_fn, art_sharded, ids)
        out = sharded_fn(art_sharded, ids)
        roofline = _roofline(sharded_fn, art_sharded, ids,
                             measured_s=t_sharded)
        from repro.sharding.quantized import quantized_gather
        pinned_fn = jax.jit(lambda a, i: quantized_gather(
            a, i, cfg, mesh=mesh, decode_block_b=cfg.decode_block_b))
        t_pinned = _time(pinned_fn, art_sharded, ids)
    err = float(jnp.max(jnp.abs(out - ref)))
    parity_ok = err < 1e-5
    if not parity_ok:
        # recorded + reported, never a bare assert: the json must still
        # be written (CI uploads it), and the check must survive -O
        print(f"WARNING: sharded decode parity FAILED (max err {err:.2e})")

    model_n = dict(mesh.shape)["model"]
    wire_mb = batch * d * 4 / 1e6          # psum of (B, d) partials
    print(f"sharded decode B={batch} mesh{dict(mesh.shape)}: "
          f"single-dev {t_single*1e3:.2f} ms | sharded {t_sharded*1e3:.2f} "
          f"ms (parity err {err:.1e}); codes {n*D/1e6:.1f} MB -> "
          f"{n*D/model_n/1e6:.1f} MB/shard, wire {wire_mb:.2f} MB/step "
          f"(vocab-independent)")
    print(f"  shard-body block_b: pinned {cfg.decode_block_b} "
          f"{t_pinned*1e3:.2f} ms | tuned {tuned.get('block_b')} "
          f"{t_sharded*1e3:.2f} ms ({t_pinned/t_sharded:.2f}x)")
    results["sharded_decode"] = {
        "vocab": n, "dim": d, "num_subspaces": D, "num_centroids": K,
        "batch": batch, "mesh": dict(mesh.shape),
        "single_device_ms": t_single * 1e3,
        "sharded_ms": t_sharded * 1e3,
        "sharded_pinned_ms": t_pinned * 1e3,
        "pinned_block_b": cfg.decode_block_b,
        "tuned_block_b": tuned.get("block_b"),
        "tuned_vs_pinned_speedup": t_pinned / t_sharded,
        "parity_max_err": err,
        "parity_ok": parity_ok,
        "codes_mbytes_total": n * D / 1e6,
        "codes_mbytes_per_shard": n * D / model_n / 1e6,
        "wire_mbytes_per_step": wire_mb,
        **roofline,
    }


def bench_rq_decode(results: dict, n: int, d: int, M: int, K: int,
                    batch: int):
    """Residual-quantization serving decode: the single-pass fused op
    vs per-stage kernel launches.

    Fused = ONE dispatched ``rq_decode_stages`` call (DESIGN.md §11) —
    what ``rq.decode`` serves through on every backend: on
    pallas/interpret the M-stage sum accumulates in the kernel's
    revisited VMEM output block; on xla the per-stage gather chain
    fuses into a single pass under one jit.  Unfused = the shape the
    serve path used to have — one decode launch per residual stage
    (each its own jit dispatch) with the stage outputs summed outside
    the kernel.  Block geometry for the fused path comes from the
    autotune cache (``dispatch.tune`` runs on the benched shape first;
    the winners are recorded).  ``parity_ok`` AND ``speedup_ok``
    (fused >= 1x unfused) flip the exit code (after the json is
    written).
    """
    from repro.kernels.mgqe_decode import decode_stages
    k = jax.random.PRNGKey(0)
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="rq", num_levels=M,
                          num_centroids=K)
    artifact = {
        "codes": jax.random.randint(k, (n, M), 0, K).astype(jnp.uint8),
        "codebooks": jax.random.normal(k, (M, K, d)),
    }
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, n)

    backend = dispatch.resolve_backend(cfg.kernel_backend)

    # autotune the fused op's block geometry on the benched shape —
    # dispatch injects the winners into the unpinned call below
    sel0 = jnp.take(artifact["codes"], ids, axis=0)       # (B, M) uint8
    tuned = next(iter(dispatch.tune(
        "rq_decode_stages", [(sel0, artifact["codebooks"])],
        backend=backend).values()))

    fused_fn = jax.jit(lambda a, i: decode_stages(
        jnp.take(a["codes"], i, axis=0), a["codebooks"], backend=backend))
    t_fused = _time(fused_fn, artifact, ids)

    # unfused: M separate stage launches; the running sum happens
    # between dispatches, outside any kernel
    cbs = [artifact["codebooks"][m] for m in range(M)]
    take_codes = jax.jit(lambda c, i: jnp.take(c, i, axis=0))
    stage_fn = jax.jit(lambda cb, c: jnp.take(cb, c.astype(jnp.int32),
                                              axis=0))

    def unfused(a, i):
        sel = take_codes(a["codes"], i)
        out = stage_fn(cbs[0], sel[:, 0])
        for m in range(1, M):
            out = out + stage_fn(cbs[m], sel[:, m])
        return out
    t_unfused = _time(unfused, artifact, ids)

    err = float(jnp.max(jnp.abs(fused_fn(artifact, ids)
                                - unfused(artifact, ids))))
    parity_ok = err < 1e-5
    speedup = t_unfused / t_fused
    speedup_ok = speedup >= 1.0
    if not parity_ok:
        print(f"WARNING: rq decode parity FAILED (max err {err:.2e})")
    if not speedup_ok:
        print(f"WARNING: rq fused decode below 1x the per-stage "
              f"launches ({speedup:.2f}x)")
    print(f"rq decode B={batch} n={n/1e6:.1f}M d={d} M={M}: "
          f"per-stage launches {t_unfused*1e3:.2f} ms | "
          f"fused[{backend}] {t_fused*1e3:.2f} ms ({speedup:.1f}x, "
          f"parity err {err:.1e}, tuned {tuned}); "
          f"codes {n*M/1e6:.1f} MB + {M*K*d*4/1e3:.0f} KB codebooks vs "
          f"{n*d*4/1e6:.0f} MB full")
    results["rq_decode"] = {
        "vocab": n, "dim": d, "num_levels": M, "num_centroids": K,
        "batch": batch,
        "fused_backend": backend,
        "unfused_decode_ms": t_unfused * 1e3,
        "fused_decode_ms": t_fused * 1e3,
        "fused_vs_unfused_speedup": speedup,
        "speedup_ok": speedup_ok,
        "tuned_block_b": tuned.get("block_b"),
        "tuned_block_d": tuned.get("block_d"),
        "parity_max_err": err,
        "parity_ok": parity_ok,
        "table_mbytes_codes": (n * M + M * K * d * 4) / 1e6,
        "serving_size_pct_of_full":
            100 * cfg.serving_size_bits() / (n * d * 32),
        **_roofline(fused_fn, artifact, ids, measured_s=t_fused),
    }


def bench_mpe_decode(results: dict, n: int, d: int, D: int, batch: int):
    """Mixed-precision packed codes (DESIGN.md §13): the fused
    unpack-and-decode kernel vs the O(n) unpack-then-decode shape.

    Fused = packed row gather + ONE dispatched ``packed_decode`` call —
    the ``mpe`` serve path: the (B, W) packed words cross the kernel
    boundary and unpack inside the VMEM block, so HBM reads stay at the
    packed width.  Reference = unpack the WHOLE (n, W) table to (n, D)
    uint8 codes first (its own jit — the materialized copy the fused
    kernel exists to avoid), then the plain gather+decode.
    ``gather_unpacked_ms`` is the same gather against a PRE-unpacked
    table — the uint8-layout wall-time the packed layout competes with
    once the copy is amortized away (the honest bytes story: the tail
    tier reads ``packed_width(D, 2)/D`` = 1/4 the code bytes).

    The tail tier (bits=2, the 4x byte cut) is the timed/gated path;
    ``blended_decode_ms`` records the full 3-tier masked-blend decode
    the scheme actually serves.  ``parity_ok``, ``speedup_ok`` (fused
    >= 1x unpack-then-decode) and ``tail_bytes_ok`` (packed tail bytes
    <= 40% of the uint8 layout) flip the exit code.
    """
    from repro.core.schemes import get_scheme
    from repro.kernels.packed_decode import (decode, pack_codes,
                                             packed_width, unpack_codes)
    k = jax.random.PRNGKey(0)
    tier_bits = (8, 4, 2)
    bounds = frequency_boundaries(n, (0.05, 0.25))
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="mpe",
                          num_subspaces=D, tier_boundaries=bounds,
                          tier_bits=tier_bits)
    backend = dispatch.resolve_backend(cfg.kernel_backend)
    s = d // D
    # synthesize per-tier packed tables + codebooks (assignment quality
    # is irrelevant to decode wall-time)
    artifact = {"codes": [], "centroids": []}
    for bits in tier_bits:
        codes = jax.random.randint(k, (n, D), 0, 2 ** bits)
        artifact["codes"].append(pack_codes(codes, bits))
        artifact["centroids"].append(jax.random.normal(k, (D, 2 ** bits, s)))
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, n)

    bits_t = tier_bits[-1]
    packed_t = artifact["codes"][-1]
    cent_t = artifact["centroids"][-1]
    sel = jnp.take(packed_t, ids, axis=0)                # (B, W) uint8
    tuned = next(iter(dispatch.tune(
        "packed_decode", [(sel, cent_t, bits_t)],
        backend=backend).values()))
    fused_fn = jax.jit(lambda p, c, i: decode(
        jnp.take(p, i, axis=0), c, bits_t, backend=backend))
    t_fused = _time(fused_fn, packed_t, cent_t, ids)

    # unpack-then-decode: the table-wide unpack is its own jit so XLA
    # cannot fuse it into the batch gather — the (n, D) copy is real
    unpack_fn = jax.jit(lambda p: unpack_codes(p, bits_t, D))
    gather_fn = jax.jit(lambda c, ce, i: mgqe_decode_ref(
        jnp.take(c, i, axis=0).astype(jnp.int32), ce))

    def unpack_then_decode(p, c, i):
        return gather_fn(unpack_fn(p), c, i)
    t_unpack = _time(unpack_then_decode, packed_t, cent_t, ids)
    codes_full = unpack_fn(packed_t)
    t_gather_unpacked = _time(gather_fn, codes_full, cent_t, ids)

    # the full serve path: 3 tiers, fused decode each, masked blend
    scheme = get_scheme(cfg)
    blended_fn = jax.jit(lambda a, i: scheme.decode(a, i, block_b=None))
    t_blend = _time(blended_fn, artifact, ids)

    err = float(jnp.max(jnp.abs(fused_fn(packed_t, cent_t, ids)
                                - unpack_then_decode(packed_t, cent_t,
                                                     ids))))
    parity_ok = err < 1e-5
    speedup = t_unpack / t_fused
    speedup_ok = speedup >= 1.0
    w_tail = packed_width(D, bits_t)
    tail_frac = w_tail / D
    tail_ok = tail_frac <= 0.40
    if not parity_ok:
        print(f"WARNING: mpe packed decode parity FAILED "
              f"(max err {err:.2e})")
    if not speedup_ok:
        print(f"WARNING: mpe fused packed decode below 1x the "
              f"unpack-then-decode reference ({speedup:.2f}x)")
    if not tail_ok:
        print(f"WARNING: mpe tail-tier code bytes {100*tail_frac:.0f}% "
              f"of the uint8 layout (> 40%)")
    print(f"mpe decode B={batch} n={n/1e6:.1f}M d={d} "
          f"bits={tier_bits}: unpack-then-decode {t_unpack*1e3:.2f} ms | "
          f"fused[{backend}] {t_fused*1e3:.2f} ms ({speedup:.1f}x, "
          f"parity err {err:.1e}, tuned {tuned}) | "
          f"gather-unpacked {t_gather_unpacked*1e3:.2f} ms | "
          f"3-tier blend {t_blend*1e3:.2f} ms")
    print(f"  tail tier codes {n*w_tail/1e6:.2f} MB packed vs "
          f"{n*D/1e6:.2f} MB uint8 ({100*tail_frac:.0f}%); serving size "
          f"{100*cfg.serving_size_bits()/(n*d*32):.1f}% of full")
    results["mpe_decode"] = {
        "vocab": n, "dim": d, "num_subspaces": D, "batch": batch,
        "tier_bits": list(tier_bits),
        "fused_backend": backend,
        "fused_decode_ms": t_fused * 1e3,
        "unpack_then_decode_ms": t_unpack * 1e3,
        "fused_vs_unpack_speedup": speedup,
        "speedup_ok": speedup_ok,
        "gather_unpacked_ms": t_gather_unpacked * 1e3,
        "blended_decode_ms": t_blend * 1e3,
        "tuned_block_b": tuned.get("block_b"),
        "tuned_block_d": tuned.get("block_d"),
        "parity_max_err": err,
        "parity_ok": parity_ok,
        "code_mbytes_per_tier": [n * packed_width(D, b) / 1e6
                                 for b in tier_bits],
        "uint8_code_mbytes": n * D / 1e6,
        "tail_code_bytes_frac": tail_frac,
        "tail_bytes_ok": tail_ok,
        "serving_size_pct_of_full":
            100 * cfg.serving_size_bits() / (n * d * 32),
        **_roofline(fused_fn, packed_t, cent_t, ids, measured_s=t_fused),
    }


def bench_hot_cache(results: dict, n: int, d: int, D: int, K: int,
                    n_requests: int, req_batch: int):
    """Hot-row decode-ahead cache (DESIGN.md §9) on Zipfian engine
    traffic: the cached ServingEngine vs the no-cache engine on the
    SAME power-law request stream, swept over head-heaviness
    ``zipf_a`` ∈ {1.05, 1.2, 1.5}.

    The table is the paper's own mgqe (private_k, a three-tier
    head/torso/tail split — the no-cache engine pays one fused decode
    pass per tier for EVERY lookup); the cache holds the hottest n/8
    ids pre-decoded dense.  The gated sweep runs on the ``interpret``
    backend — the real Pallas kernel body, i.e. the one-hot-matmul
    decode that executes on TPU — because that is where the cache
    removes actual kernel work.  (On the CPU ``xla`` reference path the
    decode degenerates to the very gather the cache performs, so both
    sides cost alike and sub-ms wall times are all scheduler noise —
    that path is parity-checked by the tests, not timed here.)

    Recorded per sweep point: hit rate, lookups/s for both engines, and
    the rows that actually reached the fused decode.  Two gates flip
    the exit code (after the json is written): ``parity_ok`` — cached
    lookups bit-identical to the uncached fused decode — and
    ``speedup_ok`` — >= 2x engine throughput at zipf_a = 1.2, the
    acceptance bar for exploiting the power law.  Each measured number
    is the best of 5 post-warmup passes (best-of damps scheduler noise
    on shared CPU runners).
    """
    from repro.core.partition import frequency_boundaries, tier_of_ids
    from repro.data.synthetic import zipf_request_stream
    from repro.launch.engine import EngineStats, ServingEngine
    bounds = frequency_boundaries(n, (0.05, 0.25))
    tier_ks = (K, max(2, K // 4), max(2, K // 16))
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="mgqe",
                          mgqe_variant="private_k",
                          num_subspaces=D, num_centroids=K,
                          tier_boundaries=bounds,
                          tier_num_centroids=tier_ks)
    emb = Embedding(cfg)
    # codes must respect the PER-TIER codebook width (tail rows index
    # the small tier tables; out-of-range codes hit take_along_axis's
    # NaN fill and poison parity)
    kmax = np.asarray(tier_ks)[
        np.asarray(tier_of_ids(np.arange(n), bounds))][:, None]
    rng_codes = np.random.default_rng(3)
    artifact = {
        "codes": jnp.asarray(
            rng_codes.integers(0, 1 << 30, (n, D)) % kmax, jnp.uint8),
        "centroids": [
            jax.random.normal(jax.random.PRNGKey(i), (D, k_i, d // D))
            for i, k_i in enumerate(tier_ks)],
    }
    hot = max(1024, n // 8)

    def best_of(engine, reqs, passes=5):
        engine.serve_stream(reqs)              # warm: pays jit traces
        best = None
        for _ in range(passes):
            engine.stats_ = EngineStats()
            st = engine.serve_stream(reqs)
            if best is None or st.lookups_per_s > best.lookups_per_s:
                best = st
        return best

    rng = np.random.default_rng(0)
    probe = np.r_[np.arange(64), rng.integers(0, n, 192)]
    # one engine pair reused across the sweep: the request-SIZE
    # sequence is zipf_a-independent (same seed), so flush shapes are
    # shared and only the hot/cold split shapes recompile per a
    base = ServingEngine(emb, artifact, max_queue=8192,
                         backend="interpret")
    eng = ServingEngine(emb, artifact, max_queue=8192,
                        backend="interpret", hot_rows=hot)
    # bit-parity of cached lookups vs the uncached fused decode — the
    # engines (and so the probe's answer) are fixed across the sweep
    parity_ok = bool(np.array_equal(np.asarray(eng.lookup(probe)),
                                    np.asarray(base.lookup(probe))))
    sweep = {}
    for a in (1.05, 1.2, 1.5):
        reqs = zipf_request_stream(n, n_requests, req_batch, zipf_a=a,
                                   seed=17)
        st0, st1 = best_of(base, reqs), best_of(eng, reqs)
        speed = st1.lookups_per_s / max(st0.lookups_per_s, 1e-9)
        sweep[str(a)] = {
            "hit_rate": st1.hit_rate,
            "no_cache_lookups_per_s": st0.lookups_per_s,
            "hot_cache_lookups_per_s": st1.lookups_per_s,
            "speedup": speed,
            "decoded_lookups": st1.decoded_lookups,
            "decoded_lookups_no_cache": st0.decoded_lookups,
        }
        print(f"hot cache zipf_a={a} [interpret]: hit {st1.hit_rate:.3f}"
              f" | no-cache {st0.lookups_per_s:,.0f}/s | cached "
              f"{st1.lookups_per_s:,.0f}/s ({speed:.2f}x) | decode rows "
              f"{st1.decoded_lookups} vs {st0.decoded_lookups}")
    # the CPU xla reference path is parity-only: cached lookups must
    # still be bit-identical to its decode (timing it here would be
    # gather-vs-gather scheduler noise, see docstring)
    base_x = ServingEngine(emb, artifact, max_queue=8192, backend="xla")
    eng_x = ServingEngine(emb, artifact, max_queue=8192, backend="xla",
                          hot_rows=hot)
    parity_ok &= bool(np.array_equal(np.asarray(eng_x.lookup(probe)),
                                     np.asarray(base_x.lookup(probe))))

    speed12 = sweep["1.2"]["speedup"]
    speedup_ok = speed12 >= 2.0
    if not parity_ok:
        print("WARNING: hot cache parity FAILED (cached rows not "
              "bit-identical to the fused decode)")
    if not speedup_ok:
        print(f"WARNING: hot cache speedup at zipf_a=1.2 below 2x "
              f"({speed12:.2f}x)")
    results["hot_cache_lookup"] = {
        "vocab": n, "dim": d, "num_subspaces": D, "num_centroids": K,
        "kind": "mgqe", "mgqe_variant": "private_k",
        "tier_num_centroids": list(tier_ks),
        "hot_rows": hot, "fused_backend": "interpret",
        "hot_block_mbytes": hot * d * 4 / 1e6,
        "sweep": sweep,
        "speedup_at_zipf_1_2": speed12,
        "speedup_ok": speedup_ok,
        "parity_ok": parity_ok,
    }


def bench_async_serving(results: dict, n: int, d: int, D: int, K: int,
                        req_batch: int, duration_s: float,
                        rates: tuple, slo_ms: float = 5.0):
    """Latency-SLO sweep of the async front-end (DESIGN.md §10): an
    open-loop Zipf(a=1.2) request stream is replayed at each offered
    arrival rate, and the submit->result latency histogram is read out
    at p50/p99/p999.  Open-loop means submissions follow the
    generator's clock even when the engine lags — the measured tail
    INCLUDES queueing delay, which a closed-loop driver would hide
    (coordinated omission).

    Swept on both the ``interpret`` backend (the real Pallas kernel
    body — the decode that executes on TPU) and the ``xla`` reference
    path.  Per backend the json records every swept rate plus
    ``max_rate_meeting_slo`` — the highest offered rate whose p99 stays
    within ``slo_ms``; ``slo_ok`` (every backend sustains at least the
    lowest swept rate) flips the exit code after the json is written.
    Warmup pre-pays the jit traces of both padded flush shapes, so the
    measured stream sees no compiles.
    """
    import gc
    from repro.data.synthetic import zipf_open_loop_stream
    from repro.launch.async_engine import AsyncServingEngine, drive_open_loop
    from repro.launch.engine import ServingEngine
    # the earlier benches leave a large tracked heap (jit caches, big
    # host arrays); a gen-2 GC pause mid-stream is tens of ms — exactly
    # the artifact a p99 readout amplifies.  Freeze the survivors so
    # collections during the sweep only scan the sweep's own garbage
    # (standard serving-process hygiene, not a bench-only trick).
    gc.collect()
    gc.freeze()
    cfg = EmbeddingConfig(vocab_size=n, dim=d, kind="dpq",
                          num_subspaces=D, num_centroids=K)
    emb = Embedding(cfg)
    artifact = emb.export(emb.init(jax.random.PRNGKey(0)))
    max_wait_us = 500.0
    backends_out, slo_ok = {}, True
    for backend in ("interpret", "xla"):
        engine = ServingEngine(emb, artifact, backend=backend,
                               max_queue=8192)
        per_rate, best = {}, 0.0
        with AsyncServingEngine(engine, max_wait_us=max_wait_us) as a:
            # warm the two padded flush shapes (bounded batch take keeps
            # every flush at 1 or 2 blocks — see run_flat)
            for rows in (1, engine.pad_multiple + 1):
                a.lookup(np.zeros(rows, np.int64))
            for rate in rates:
                arrivals, reqs = zipf_open_loop_stream(
                    n, rate, duration_s, req_batch, zipf_a=1.2, seed=7)
                a.reset_stats()
                st = drive_open_loop(a, reqs, arrivals)
                met = bool(st.p99_ms <= slo_ms)
                if met:
                    best = max(best, float(rate))
                per_rate[str(rate)] = {
                    "offered_req_per_s": float(rate),
                    "requests": st.requests,
                    "p50_ms": st.p50_ms,
                    "p99_ms": st.p99_ms,
                    "p999_ms": st.p999_ms,
                    "sustained_lookups_per_s": st.sustained_lookups_per_s,
                    "flushes_full": st.flushes_full,
                    "flushes_deadline": st.flushes_deadline,
                    "slo_met": met,
                }
                print(f"async[{backend}] {rate:>6.0f} req/s offered: "
                      f"p50 {st.p50_ms:.2f} | p99 {st.p99_ms:.2f} | "
                      f"p999 {st.p999_ms:.2f} ms "
                      f"({st.sustained_lookups_per_s:,.0f} lookups/s; "
                      f"SLO {'MET' if met else 'MISSED'})")
        backends_out[backend] = {"rates": per_rate,
                                 "max_rate_meeting_slo": best}
        slo_ok &= best > 0.0
        print(f"async[{backend}]: max offered rate meeting p99 <= "
              f"{slo_ms:g} ms: {best:,.0f} req/s")
    gc.unfreeze()
    if not slo_ok:
        print(f"WARNING: async serving missed the {slo_ms:g} ms p99 SLO "
              f"at every swept rate on some backend")
    results["async_serving"] = {
        "vocab": n, "dim": d, "num_subspaces": D, "num_centroids": K,
        "req_batch": req_batch, "zipf_a": 1.2,
        "arrival_process": "poisson", "open_loop": True,
        "max_wait_us": max_wait_us, "duration_s": duration_s,
        "slo_ms": slo_ms,
        "backends": backends_out,
        "slo_ok": slo_ok,
    }


def bench_adc(results: dict, d: int, D: int, K: int, n_cand: int):
    k = jax.random.PRNGKey(0)
    cent = jax.random.normal(k, (D, K, d // D))
    q = jax.random.normal(k, (d,))
    cand_vecs = jax.random.normal(k, (n_cand, d))
    cand_codes = jax.random.randint(k, (n_cand, D), 0, K).astype(jnp.uint8)
    t_dense = _time(jax.jit(lambda v, q: v @ q), cand_vecs, q)
    lut = build_lut_ref(q, cent)
    adc_fn = jax.jit(lambda l, c: pq_score_ref(l, c.astype(jnp.int32)))
    t_adc = _time(adc_fn, lut, cand_codes)
    print(f"retrieval 1x{n_cand//1000}k cands: dense {t_dense*1e3:.1f} ms "
          f"({n_cand*d*4/1e6:.0f} MB) | ADC {t_adc*1e3:.1f} ms "
          f"({n_cand*D/1e6:.0f} MB codes)")
    print(f"  stream cut {d*4/D:.0f}x -> memory-roofline ceiling "
          f"{d*4/D:.0f}x faster on TPU (819 GB/s HBM)")
    results["adc"] = {
        "n_candidates": n_cand, "dim": d,
        "dense_ms": t_dense * 1e3, "adc_ms": t_adc * 1e3,
        "stream_cut_x": d * 4 / D,
        **_roofline(adc_fn, lut, cand_codes, measured_s=t_adc),
    }


def bench_retrieval_topk(results: dict, d: int, D: int, n_cand: int,
                         k: int = 100, batch: int = 16):
    """Batched fused top-k retrieval (DESIGN.md §8): the dispatched
    ``pq_topk`` path (one LUT batch, one pass over the code stream,
    block-wise top-k accumulation) vs the per-query unfused loop
    (B separate full scans + top_k), plus the ivf_pq index probing
    nprobe/nlist of the corpus.  Runs on the PQ-structured synthetic
    corpus so recall@k vs the exact dense scan isolates the retrieval
    approximation, not quantizer noise.  Score parity between the
    fused and unfused flat paths is recorded as ``parity_ok`` and
    flips the exit code (after the json is written).
    """
    from repro.data.synthetic import pq_clustered_corpus
    from repro.kernels.pq_score import score_candidates
    from repro.retrieval import IndexConfig, get_index

    vecs_np, q_np = pq_clustered_corpus(n=n_cand, d=d, num_subspaces=D,
                                        n_queries=batch)
    vecs, q = jnp.asarray(vecs_np), jnp.asarray(q_np)
    ex_ids = np.argsort(-(q_np @ vecs_np.T), axis=1)[:, :k]

    def recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[b].tolist())
                                  & set(ex_ids[b].tolist())) / k
                              for b in range(batch)]))

    backend = dispatch.resolve_backend()
    flat = get_index(IndexConfig(kind="flat_pq", num_subspaces=D,
                                 num_centroids=128, iters=15))
    art = flat.build(jax.random.PRNGKey(0), vecs)
    fused_fn = jax.jit(lambda a, qq: flat.search(a, qq, k))
    t_fused = _time(fused_fn, art, q, iters=5)
    s_fused, i_fused = fused_fn(art, q)

    # unfused: per-query full scan + top_k — B kernel launches, B (N,)
    # score vectors materialized in HBM
    one = jax.jit(lambda a, qq: jax.lax.top_k(
        score_candidates(qq, a["centroids"], a["codes"]), k))

    def unfused(a, qq):
        outs = [one(a, qq[b]) for b in range(batch)]
        return (jnp.stack([s for s, _ in outs]),
                jnp.stack([i for _, i in outs]))
    t_unfused = _time(unfused, art, q, iters=5)
    s_unfused, _ = unfused(art, q)

    err = float(jnp.max(jnp.abs(s_fused - s_unfused)))
    parity_ok = err < 1e-5
    if not parity_ok:
        print(f"WARNING: retrieval topk parity FAILED (max err {err:.2e})")

    nlist, nprobe = 64, 8                    # nprobe = nlist/8
    ivf = get_index(IndexConfig(kind="ivf_pq", num_subspaces=D,
                                num_centroids=128, iters=15,
                                nlist=nlist, nprobe=nprobe,
                                coarse_iters=15))
    art_ivf = ivf.build(jax.random.PRNGKey(0), vecs)
    ivf_fn = jax.jit(lambda a, qq: ivf.search(a, qq, k))
    t_ivf = _time(ivf_fn, art_ivf, q, iters=5)
    _, i_ivf = ivf_fn(art_ivf, q)

    r_flat, r_ivf = recall(i_fused), recall(i_ivf)
    print(f"retrieval top-{k} B={batch} x {n_cand/1e3:.0f}k cands: "
          f"unfused loop {t_unfused*1e3:.1f} ms | fused[{backend}] "
          f"{t_fused*1e3:.1f} ms ({t_unfused/t_fused:.1f}x, parity err "
          f"{err:.1e}) | ivf_pq nprobe {nprobe}/{nlist} "
          f"{t_ivf*1e3:.1f} ms")
    print(f"  recall@{k} vs exact dense scan: flat {r_flat:.3f}, "
          f"ivf {r_ivf:.3f}")
    results["retrieval_topk"] = {
        "n_candidates": n_cand, "dim": d, "num_subspaces": D,
        "batch": batch, "k": k,
        "fused_backend": backend,
        "unfused_loop_ms": t_unfused * 1e3,
        "fused_topk_ms": t_fused * 1e3,
        "fused_vs_unfused_speedup": t_unfused / t_fused,
        "ivf_topk_ms": t_ivf * 1e3,
        "nlist": nlist, "nprobe": nprobe,
        "recall_at_k_flat": r_flat,
        "recall_at_k_ivf": r_ivf,
        "parity_max_err": err,
        "parity_ok": parity_ok,
        "codes_mbytes": n_cand * D / 1e6,
        "dense_mbytes": n_cand * d * 4 / 1e6,
        **_roofline(fused_fn, art, q, measured_s=t_fused),
    }


def bench_retrieval_scale(results: dict, n: int, backend=None,
                          nprobes=(1, 4, 16, 64, 128), k: int = 100,
                          batch: int = 16):
    """Streamed build + nprobe Pareto sweep at corpus scale (DESIGN.md
    §12): a Zipf-clustered ``n``-row corpus is built through the
    streaming driver (sampled codebook fit, blocked assign+encode,
    quantile-capped chained list layout), then searched at each swept
    ``nprobe``, recording recall@``k`` vs the exact dense scan and the
    p50/p99 single-flush search latency — the recall/latency dial the
    operator actually turns.

    Two gates flip the exit code (after the json is written):
    ``recall_ok`` — some swept nprobe reaches recall@k >= 0.95 — and
    ``build_peak_ok`` — the build's peak staged device bytes stayed
    within the config-derived O(sample + block) bound, i.e. the build
    never materialized O(corpus) on device (``BuildStats``,
    retrieval/build.py).  The layout fields record the skew story:
    ``padded_layout_mbytes`` is what the old pad-to-longest-list layout
    would allocate, ``layout_mbytes`` what the chained layout does,
    ``ideal_layout_mbytes`` the un-padded code+id bytes.
    """
    import dataclasses
    from repro.data.synthetic import pq_clustered_corpus
    from repro.retrieval import IndexConfig, get_index, suggest_nlist
    from repro.retrieval.build import build_ivf_artifact

    d, D, K = 64, 8, 128
    n_clusters = min(2048, suggest_nlist(n))
    vecs_np, q_np = pq_clustered_corpus(n=n, d=d, num_subspaces=D,
                                        n_queries=batch,
                                        n_clusters=n_clusters,
                                        cluster_zipf_a=1.3)
    nlist = suggest_nlist(n, max(nprobes))
    cfg = IndexConfig(kind="ivf_pq", num_subspaces=D, num_centroids=K,
                      iters=10, coarse_iters=10, nlist=nlist,
                      nprobe=max(nprobes),
                      train_sample=min(n, 131_072),
                      encode_block=min(n, 131_072),
                      list_cap_quantile=0.9,
                      kernel_backend=backend)
    art_host, stats = build_ivf_artifact(jax.random.PRNGKey(0),
                                         vecs_np, cfg)
    print(f"retrieval scale n={n/1e6:.1f}M nlist={nlist} "
          f"[{dispatch.resolve_backend(backend)}]: build "
          f"{stats.seconds:.1f} s in {stats.blocks} blocks of "
          f"{stats.block_rows} (sample {stats.sample_rows}); peak device "
          f"{stats.peak_device_bytes/1e6:.0f} MB vs bound "
          f"{stats.device_bound_bytes/1e6:.0f} MB vs corpus "
          f"{vecs_np.nbytes/1e6:.0f} MB "
          f"({'OK' if stats.peak_device_ok else 'BLOWN'})")
    layout_mb = (art_host["list_codes"].nbytes
                 + art_host["list_ids"].nbytes) / 1e6
    padded_mb = nlist * stats.list_count_max * (D + 4) / 1e6
    ideal_mb = n * (D + 4) / 1e6
    print(f"  list layout: cap {stats.list_cap} (q=0.9), chain <= "
          f"{stats.max_chain}, {stats.lists_ext} ext lists -> "
          f"{layout_mb:.0f} MB (pad-to-max {padded_mb:.0f} MB, ideal "
          f"{ideal_mb:.0f} MB)")

    art = {name: jnp.asarray(leaf) for name, leaf in art_host.items()}
    q = jnp.asarray(q_np)
    ex_ids = np.argsort(-(q_np @ vecs_np.T), axis=1)[:, :k]
    sweep, best_recall = {}, 0.0
    iters = 30 if n <= 2_000_000 else 10
    for p in nprobes:
        idx = get_index(dataclasses.replace(cfg, nprobe=p))
        fn = jax.jit(lambda a, qq, idx=idx: idx.search(a, qq, k))
        out = fn(art, q)
        jax.block_until_ready(out)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = fn(art, q)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        ids = np.asarray(out[1])
        rec = float(np.mean([np.isin(ids[b], ex_ids[b]).mean()
                             for b in range(batch)]))
        best_recall = max(best_recall, rec)
        p50, p99 = (float(np.percentile(times, q_) * 1e3)
                    for q_ in (50, 99))
        sweep[str(p)] = {"recall_at_k": rec, "p50_ms": p50,
                         "p99_ms": p99}
        print(f"  nprobe={p:>4}: recall@{k} {rec:.3f} | p50 "
              f"{p50:.1f} ms | p99 {p99:.1f} ms")
    recall_ok = best_recall >= 0.95
    if not recall_ok:
        print(f"WARNING: retrieval scale recall@{k} below 0.95 at every "
              f"swept nprobe (best {best_recall:.3f})")
    if not stats.peak_device_ok:
        print("WARNING: retrieval scale build peak device bytes "
              "exceeded the O(sample + block) bound")
    results["retrieval_scale"] = {
        "corpus_rows": n, "dim": d, "num_subspaces": D,
        "num_centroids": K, "nlist": nlist, "k": k, "batch": batch,
        "n_clusters": n_clusters, "cluster_zipf_a": 1.3,
        "backend": dispatch.resolve_backend(backend),
        "build_seconds": stats.seconds,
        "build_blocks": stats.blocks,
        "train_sample": stats.sample_rows,
        "encode_block": stats.block_rows,
        "peak_device_mbytes": stats.peak_device_bytes / 1e6,
        "device_bound_mbytes": stats.device_bound_bytes / 1e6,
        "build_peak_ok": stats.peak_device_ok,
        "corpus_mbytes": vecs_np.nbytes / 1e6,
        "layout_mbytes": layout_mb,
        "padded_layout_mbytes": padded_mb,
        "ideal_layout_mbytes": ideal_mb,
        "list_cap": stats.list_cap,
        "max_chain": stats.max_chain,
        "lists_ext": stats.lists_ext,
        "list_count_max": stats.list_count_max,
        "list_cap_quantile": 0.9,
        "sweep": sweep,
        "recall_at_k_best": best_recall,
        "recall_ok": recall_ok,
    }


def bench_dpq_assign(results: dict, d: int, D: int, K: int, b: int):
    """Training/export-side nearest-centroid assignment through the
    DISPATCHED op with an autotuned ``block_b``.

    The old entry jitted the flat reference, whose (B, D, K) f32
    distance tensor (67 MB at B=8192, D=8, K=256) fell out of cache
    and measured 346 ms against a 0.17 ms roofline bound
    (roofline_fraction 0.0005) — the blocked xla impl keeps each
    (block_b, D, K) slab cache-resident and the
    ``roofline_fraction < 0.001`` suspect gate in ``main`` now flags
    that class of mis-benchmark."""
    k = jax.random.PRNGKey(0)
    cent = jax.random.normal(k, (D, K, d // D))
    e = jax.random.normal(k, (b, D, d // D))
    from repro.kernels.dpq_assign import assign
    backend = dispatch.resolve_backend()
    tuned = next(iter(dispatch.tune("dpq_assign", [(e, cent, None)],
                                    backend=backend).values()))
    assign_fn = jax.jit(lambda e_, c_: assign(e_, c_, backend=backend))
    t_assign = _time(assign_fn, e, cent)
    fl = 2 * b * D * K * (d // D)
    print(f"dpq_assign B={b} [{backend}]: {t_assign*1e3:.1f} ms "
          f"({fl/1e9:.2f} GFLOP -> {fl/t_assign/1e9:.1f} GFLOP/s, "
          f"tuned {tuned})")
    results["dpq_assign"] = {
        "batch": b, "assign_ms": t_assign * 1e3, "gflop": fl / 1e9,
        "backend": backend,
        "tuned_block_b": tuned.get("block_b"),
        **_roofline(assign_fn, e, cent, measured_s=t_assign),
    }


def main(out_json: str = "BENCH_kernels.json", quick: bool = False,
         scale_rows: int = 0, scale_backend: str = None):
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    print("== kernel micro-bench (dispatch-layer paths + byte accounting) ==")
    n, d, D, K = (100_000 if quick else 1_000_000), 64, 8, 256
    results = {
        "jax_backend": jax.default_backend(),
        "resolved_kernel_backend": dispatch.resolve_backend(),
    }
    bench_serving_decode(results, n, d, D, K, batch=4096)
    bench_sharded_decode(results, n, d, D, K, batch=4096)
    bench_rq_decode(results, n, d, M=4, K=K, batch=4096)
    bench_mpe_decode(results, n, d, D, batch=4096)
    bench_engine(results, n, d, D, K,
                 n_requests=50 if quick else 200, req_batch=64)
    bench_hot_cache(results, n, d, D, K,
                    n_requests=60 if quick else 120, req_batch=512)
    bench_async_serving(results, n, d, D, K, req_batch=8,
                        duration_s=1.0 if quick else 2.0,
                        rates=(200, 1000) if quick
                        else (200, 500, 1000, 2000))
    bench_adc(results, d, D, K, n_cand=n)
    bench_retrieval_topk(results, d, D, n_cand=100_000)
    bench_retrieval_scale(
        results, n=scale_rows or (1_000_000 if quick else 10_000_000),
        backend=scale_backend)
    bench_dpq_assign(results, d, D, K, b=8192 if quick else 65_536)

    rf_names = ("serving_decode", "sharded_decode", "rq_decode",
                "mpe_decode", "adc", "retrieval_topk", "dpq_assign")
    # a roofline_fraction this far under the bound usually means the
    # measurement caught compile or an unblocked/cache-thrashing path
    # (the old dpq_assign entry: 346 ms vs a 0.17 ms bound) — flag the
    # entry suspect BEFORE writing the json so the flag is recorded.
    # interpret mode is exempt: the Pallas interpreter is orders of
    # magnitude off the bound by design.
    suspect = []
    if results.get("resolved_kernel_backend") != "interpret":
        for name in rf_names:
            e = results.get(name, {})
            if not e or "skipped" in e:
                continue
            f = e.get("roofline_fraction")
            if f is not None and f < 1e-3:
                e["roofline_suspect"] = True
                suspect.append(name)

    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {out_json}")
    # every gate flips the exit code AFTER the json is written, so CI
    # still uploads the full results for diagnosis
    ok = all(results.get(k, {}).get("parity_ok", True)
             for k in ("sharded_decode", "rq_decode", "mpe_decode",
                       "retrieval_topk", "hot_cache_lookup"))
    ok &= results.get("hot_cache_lookup", {}).get("speedup_ok", True)
    ok &= results.get("rq_decode", {}).get("speedup_ok", True)
    ok &= results.get("mpe_decode", {}).get("speedup_ok", True)
    ok &= results.get("mpe_decode", {}).get("tail_bytes_ok", True)
    ok &= results.get("async_serving", {}).get("slo_ok", True)
    ok &= results.get("retrieval_scale", {}).get("recall_ok", True)
    ok &= results.get("retrieval_scale", {}).get("build_peak_ok", True)

    def roofline_ok(entry):
        if not entry or "skipped" in entry:
            return True
        f = entry.get("roofline_fraction")
        return f is not None and 0.0 < f <= 1.0
    bad_rf = [k for k in rf_names if not roofline_ok(results.get(k, {}))]
    if bad_rf:
        print(f"WARNING: roofline_fraction missing or out of (0, 1] "
              f"for: {', '.join(bad_rf)}")
    ok &= not bad_rf
    if suspect:
        print(f"WARNING: roofline_fraction < 0.001 — suspect timing "
              f"(compile or an unblocked path in the measurement) "
              f"for: {', '.join(suspect)}")
    ok &= not suspect
    return 0 if ok else 1


if __name__ == "__main__":
    # touches no jax device state at import (see its module docstring),
    # so the flag still lands before backend init
    from repro.launch.mesh import force_host_device_count
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_kernels.json")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count for the sharded bench")
    ap.add_argument("--scale-rows", type=int, default=0,
                    help="retrieval_scale corpus rows (default: 1M "
                         "quick / 10M full)")
    ap.add_argument("--scale-backend", default=None,
                    help="kernel backend for the retrieval_scale "
                         "encode (e.g. interpret; default: resolved)")
    a = ap.parse_args()
    force_host_device_count(a.devices)
    raise SystemExit(main(out_json=a.json, quick=a.quick,
                          scale_rows=a.scale_rows,
                          scale_backend=a.scale_backend))
