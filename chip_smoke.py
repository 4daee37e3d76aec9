"""Smoke run of the main path on TPU: train, export and serve deepfm at
its full published vocabularies, through the repo's own entry points.

    python3 chip_smoke.py              # one chip: serve, engine, train
    python3 chip_smoke.py --chips 4    # row-sharded engine on 4 chips vs
                                       # the single-device engine

Phases (one chip):

  serve   ``get_arch("deepfm")`` at full size (39 fields, 24.7M rows,
          d=10): init, export through the pallas ``dpq_assign``, one
          B=512 request through ``serve.serve_ctr``'s jitted
          ``model.serve``.  Checks: the compiled serve step holds a
          Mosaic kernel (``tpu_custom_call``); served rows and scores
          match ``kernel_backend="xla"`` on the same artifact within
          1e-5; export codes agree with an f32 ``precision=HIGHEST``
          argmin on >= 99.9% of 65,536 rows of a 10M-row field.
  engine  ``ServingEngine`` over deepfm's largest field (10M rows,
          MGQE) fed by ``drive_zipf_stream``; outputs match the
          xla-backend engine on the same stream.
  train   ``launch/train.py``'s recsys setup at the registry's
          ``train_batch`` for 3 steps; every loss finite.

With ``--chips 4`` only the sharded-serving comparison runs: a
``ServingEngine`` with codes row-sharded over a ``model=4`` mesh
against a single-device engine on the same stream, bit-identical, with
``memory_stats()`` showing the codes spread over all four devices.

Times printed are smoke timings of one run (compile included where
said), not benchmark numbers.  Any failed check exits non-zero; the
last stdout line is ``{"ok": true, "device": {...}}`` only on success.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SERVE_BATCH = 512
EXPORT_ROWS = 65_536
ENGINE_REQUESTS = 300
ENGINE_REQ_BATCH = 64
ENGINE_MAX_QUEUE = 1024      # rows per flush: ~10 flushes per stream
TRAIN_STEPS = 3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def max_abs_diff(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    check(a.shape == b.shape, f"shape mismatch {a.shape} vs {b.shape}")
    return float(np.max(np.abs(a - b))) if a.size else 0.0


def largest_field(model):
    """(field key, Embedding) of the model's largest-vocab field."""
    embs = model.fields.embs
    i = max(range(len(embs)), key=lambda j: embs[j].cfg.vocab_size)
    return f"f{i}", embs[i]


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def phase_serve(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mgqe
    from repro.kernels.dpq_assign.ref import dpq_assign_ref
    from repro.launch import serve

    n_rows = sum(cfg.field_vocab_sizes)
    print(f"[serve] deepfm: {cfg.n_sparse} fields, {n_rows:,} rows, "
          f"d={cfg.embed_dim}, D={cfg.num_subspaces}; float tables "
          f"{n_rows * cfg.embed_dim * 4 / 1e9:.3f} GB")
    t0 = time.time()
    res = serve.serve_ctr(cfg, SERVE_BATCH)
    print(f"[serve] init + export + first request: "
          f"{time.time() - t0:.1f}s (smoke timing)")
    n_q = sum(e.scheme.supports_sharded_codes for e in res.model.fields.embs)
    art_mb = res.model.fields.serving_size_bits() / 8 / 1e6
    print(f"[serve] {n_q} quantized fields; artifact {art_mb:.1f} MB")

    t0 = time.time()
    hlo = res.step.lower(res.params, res.artifacts,
                         res.request).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    print(f"[serve] serve step recompiled in {time.time() - t0:.2f}s; "
          f"{n_kernels} tpu_custom_call site(s) in its HLO")
    check(n_kernels > 0, "serve step HLO has no tpu_custom_call: the "
          "pallas decode did not reach the compiled program")

    # the same artifact served through the XLA reference decode
    cfg_x = dataclasses.replace(cfg, kernel_backend="xla")
    res_x = serve.serve_ctr(cfg_x, SERVE_BATCH, params=res.params,
                            artifacts=res.artifacts)
    ids = res.request["sparse_ids"]
    rows_p = jax.jit(res.model.fields.serve)(res.artifacts, ids)
    rows_x = jax.jit(res_x.model.fields.serve)(res.artifacts, ids)
    d_rows = max_abs_diff(rows_p, rows_x)
    d_scores = max_abs_diff(res.scores, res_x.scores)
    print(f"[serve] pallas vs xla on the same artifact: rows "
          f"{tuple(rows_p.shape)} max|diff| {d_rows:.3g}, scores "
          f"max|diff| {d_scores:.3g}")
    check(d_rows <= 1e-5, f"served rows differ by {d_rows} > 1e-5")
    check(d_scores <= 1e-5, f"served scores differ by {d_scores} > 1e-5")
    check(bool(np.all(np.isfinite(np.asarray(res.scores)))),
          "non-finite served scores")

    # export codes vs an f32 HIGHEST-precision argmin, on a window of
    # a 10M-row field that straddles its first tier boundary (both
    # centroid budgets K_i are exercised)
    key, emb = largest_field(res.model)
    ecfg = emb.cfg
    check(ecfg.kind == "mgqe", f"largest field is {ecfg.kind}, not mgqe")
    mid = ecfg.tier_boundaries[0]
    lo = max(0, min(mid - EXPORT_ROWS // 2, ecfg.vocab_size - EXPORT_ROWS))
    sl = slice(lo, lo + EXPORT_ROWS)
    p = res.params["fields"][key]
    cent = p["centroids"]
    e_sub = p["emb"][sl].reshape(EXPORT_ROWS, ecfg.num_subspaces,
                                 ecfg.subspace_dim)
    klim = mgqe.k_limit_for_all_rows(ecfg)[sl]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(dpq_assign_ref)(e_sub, cent, klim)
    got = res.artifacts[key]["codes"][sl].astype(jnp.int32)
    agree = float(jnp.mean((got == ref).astype(jnp.float32)))
    print(f"[serve] export codes vs HIGHEST argmin on {key} rows "
          f"[{lo}, {lo + EXPORT_ROWS}): agreement {agree:.6f}")
    check(agree >= 0.999, f"export code agreement {agree} < 0.999")
    return res


def engine_outputs(engine, vocab: int):
    from repro.launch.engine import drive_zipf_stream
    outs = []
    st = drive_zipf_stream(engine, vocab, ENGINE_REQUESTS, ENGINE_REQ_BATCH,
                           outputs=outs)
    return st, outs


def compare_outputs(a, b, exact: bool) -> float:
    import numpy as np
    check(len(a) == len(b) == ENGINE_REQUESTS,
          f"{len(a)} vs {len(b)} results for {ENGINE_REQUESTS} requests")
    worst = 0.0
    for x, y in zip(a, b):
        if exact:
            check(np.array_equal(np.asarray(x), np.asarray(y)),
                  "engine outputs are not bit-identical")
        worst = max(worst, max_abs_diff(x, y))
    return worst


def phase_engine(res):
    from repro.kernels import dispatch
    from repro.launch.engine import ServingEngine
    key, emb = largest_field(res.model)
    vocab = emb.cfg.vocab_size
    art = res.artifacts[key]
    eng = ServingEngine(emb, art, max_queue=ENGINE_MAX_QUEUE)
    check(dispatch.resolve_backend(eng.emb.cfg.kernel_backend) == "pallas",
          "engine decode does not resolve to pallas")
    st, out_p = engine_outputs(eng, vocab)
    print(f"[engine] {key}: vocab {vocab:,}, {st.requests} requests / "
          f"{st.lookups} lookups in {st.flushes} flushes, "
          f"{st.lookups_per_s:,.0f} lookups/s (smoke timing, warm pass "
          f"excluded)")
    eng_x = ServingEngine(emb, art, backend="xla",
                          max_queue=ENGINE_MAX_QUEUE)
    _, out_x = engine_outputs(eng_x, vocab)
    worst = compare_outputs(out_p, out_x, exact=False)
    print(f"[engine] pallas vs xla engine on the same stream: max|diff| "
          f"{worst:.3g}")
    check(worst <= 1e-5, f"engine outputs differ by {worst} > 1e-5")


def phase_train(cfg):
    import numpy as np
    from repro.configs.base import RECSYS_SHAPES
    from repro.launch.train import recsys_setup
    from repro.train.loop import LoopConfig, fit
    batch = next(s.batch for s in RECSYS_SHAPES if s.name == "train_batch")
    state, step, data = recsys_setup(cfg, batch)
    t0 = time.time()
    state, hist = fit(state, step, data,
                      LoopConfig(total_steps=TRAIN_STEPS, log_every=1))
    losses = [h["loss"] for h in hist]
    print(f"[train] {TRAIN_STEPS} steps at B={batch} in "
          f"{time.time() - t0:.1f}s (compile included; smoke timing); "
          f"losses {losses}")
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} logged steps")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss {losses}")


def phase_sharded(cfg, devices):
    import jax
    import numpy as np
    from repro.core import Embedding
    from repro.launch.engine import ServingEngine, embedding_config_of_arch
    from repro.launch.mesh import make_mesh

    ecfg = embedding_config_of_arch("recsys", cfg)
    emb = Embedding(ecfg)
    art = emb.export(emb.init(jax.random.PRNGKey(0)))
    codes_bytes = int(np.prod(art["codes"].shape)) * art["codes"].itemsize
    print(f"[sharded] table: kind={ecfg.kind} vocab={ecfg.vocab_size:,} "
          f"d={ecfg.dim}; codes {codes_bytes / 1e6:.1f} MB")
    single = ServingEngine(emb, art, max_queue=ENGINE_MAX_QUEUE)

    mesh = make_mesh((4,), ("model",), devices=devices)
    before = [d.memory_stats()["bytes_in_use"] for d in devices]
    sharded = ServingEngine(emb, art, mesh=mesh,
                            max_queue=ENGINE_MAX_QUEUE)
    jax.block_until_ready(sharded.artifact)
    grown = [d.memory_stats()["bytes_in_use"] - b
             for d, b in zip(devices, before)]
    shards = {s.device: s.data.shape for s in
              sharded.artifact["codes"].addressable_shards}
    print(f"[sharded] codes shards {sorted(set(shards.values()))} on "
          f"{len(shards)} devices; bytes_in_use grew by "
          f"{[round(g / 1e6, 1) for g in grown]} MB per device")
    check(len(shards) == 4, f"codes on {len(shards)} devices, not 4")
    per_shard = codes_bytes / 4
    check(all(g >= 0.9 * per_shard for g in grown),
          "a device did not receive its codes shard")
    check(grown[0] < 0.5 * codes_bytes,
          "device 0 holds most of the codes: they were not split")

    st1, out1 = engine_outputs(single, ecfg.vocab_size)
    st4, out4 = engine_outputs(sharded, ecfg.vocab_size)
    compare_outputs(out1, out4, exact=True)
    print(f"[sharded] {st4.requests} requests / {st4.lookups} lookups: "
          f"sharded == single-device, bit-identical; lookups/s "
          f"{st4.lookups_per_s:,.0f} sharded vs {st1.lookups_per_s:,.0f} "
          f"single (smoke timing)")


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded engine comparison")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_BACKEND"):
        fail("REPRO_KERNEL_BACKEND is set; the smoke run measures the "
             "backend dispatch picks on its own")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no src/repro beside {os.path.basename(__file__)}: run it "
             f"from a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()

    import jax
    from repro.configs.registry import get_arch
    from repro.kernels import dispatch

    all_devices = jax.devices()
    platform = all_devices[0].platform
    if platform != "tpu":
        fail(f"JAX platform is {platform!r}, not 'tpu': this smoke run "
             f"needs a TPU chip")
    if len(all_devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(all_devices)} "
             f"device(s)")
    devices = all_devices[:args.chips]
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(all_devices)}; compile cache {cache}")
    for op in ("mgqe_decode", "dpq_assign"):
        impl = dispatch.get_impl(op)
        check(impl is dispatch.registered_ops()[op]["pallas"],
              f"{op} does not resolve to its pallas kernel")

    _, cfg = get_arch("deepfm")
    t0 = time.time()
    if args.chips == 4:
        phase_sharded(cfg, devices)
    else:
        res = phase_serve(cfg)
        phase_engine(res)
        del res
        phase_train(cfg)
    print(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(all_devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
