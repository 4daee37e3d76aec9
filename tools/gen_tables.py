"""Regenerate doc tables: EXPERIMENTS.md rows from results/*.json, and
the README backend/variant support matrix (``--support-matrix``).

The support matrix is *introspected*, not hand-written: variants come
from ``repro.core.types``, backends from the kernel dispatch registry,
and sharded-serving support from ``repro.sharding.quantized`` — so the
table in README.md cannot drift from the code.  Regenerate with:

    python tools/gen_tables.py --support-matrix
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def fmt(r):
    uf = r.get("useful_frac")
    rf = r.get("roofline_frac")
    opts = r.get("opts", "")
    return ("| {a} | {s} | {o} | {c:.1f} | {m:.1f} | {k:.1f} | {dom} | "
            "{uf} | {rf} | {p:.1f} |").format(
        a=r["arch"], s=r["shape"], o=opts or "—",
        c=r["compute_ms"], m=r["memory_ms"], k=r["collective_ms"],
        dom=r["dominant"],
        uf="—" if uf is None else f"{uf:.3f}",
        rf="—" if rf is None else f"{rf:.3f}", p=r["peak_gb"])


HDR = ("| arch | shape | opts | compute ms | memory ms | collective ms | "
       "bound | useful | roofline | peak GB/dev |\n"
       "|---|---|---|---|---|---|---|---|---|---|")


def support_matrix():
    """Markdown matrix: table scheme x decode backend x placement.

    Every cell is PROBED, not hardcoded: rows are enumerated from the
    scheme plugin registry (every registered quantized scheme and its
    variants — a new plugin shows up with zero edits here), backend
    columns come from the kernel dispatch registry, the single-device
    cell from an actual init -> export -> serve round trip, and the
    sharded cell from the sharding layer's own capability check plus
    its artifact placement specs — so the README table cannot drift
    from the code (CI gates on the output matching).
    """
    import jax
    from repro.core.api import Embedding
    from repro.core.schemes import registered_kinds, scheme_class
    from repro.kernels import dispatch
    from repro.sharding.quantized import supports_sharding
    from repro.sharding.rules import quantized_artifact_specs

    backends = sorted(dispatch.registered_ops()["mgqe_decode"])
    schemes = []
    for kind in registered_kinds():
        cls = scheme_class(kind)
        if not cls.supports_sharded_codes:
            continue  # the matrix covers quantized-table schemes
        for var in cls.variants():
            label = f"`{kind}`" + (f" ({var})" if var != "-" else "")
            schemes.append((label, kind, var))

    def probe(fn):
        try:
            fn()
            return "✓"
        except Exception:
            return "—"

    def probe_hot_rows(cfg):
        """End-to-end check of the hot-row decode-ahead hook
        (Scheme.precompute_hot_rows, DESIGN.md §9): export with
        hot_rows must attach a spec-shaped dense block."""
        import dataclasses
        hcfg = dataclasses.replace(cfg, hot_rows=8)
        e = Embedding(hcfg)
        hot = e.export(e.init(jax.random.PRNGKey(0)))["hot"]
        assert tuple(hot.shape) == (8, hcfg.dim), hot.shape

    def probe_async(emb, art):
        """End-to-end check of the async front-end (DESIGN.md §10):
        wrap the engine, submit through the deadline-batched flush
        thread, and get host result rows back."""
        import numpy as np
        from repro.launch.async_engine import AsyncServingEngine
        from repro.launch.engine import ServingEngine
        with AsyncServingEngine(ServingEngine(emb, art),
                                max_wait_us=100.0) as a:
            out = a.lookup(np.arange(4), timeout=60)
        assert out.shape == (4, emb.cfg.dim), out.shape

    notes = {"pallas": "TPU hw", "xla": "any", "interpret": "any, slow"}
    lines = ["| scheme | " + " | ".join(
        f"`{b}` ({notes.get(b, 'any')})" for b in backends)
        + " | single-device | sharded codes | hot rows | async engine |",
        "|---" * (len(backends) + 5) + "|"]
    for label, kind, var in schemes:
        cfg = scheme_class(kind).probe_config(var)
        emb = Embedding(cfg)
        art = emb.export(emb.init(jax.random.PRNGKey(0)))
        ids = jax.numpy.arange(8)
        # registration, not resolution: pallas only resolves on a TPU
        cells = [probe(lambda b=b: dispatch.registered_ops()
                       ["mgqe_decode"][b]) for b in backends]
        cells.append(probe(lambda: emb.serve(art, ids)))
        cells.append("✓" if supports_sharding(kind, var)
                     and probe(lambda: quantized_artifact_specs(cfg)) == "✓"
                     else "—")
        cells.append(probe(lambda: probe_hot_rows(cfg)))
        cells.append(probe(lambda: probe_async(emb, art)))
        lines.append(f"| {label} | " + " | ".join(cells) + " |")

    # retrieval index kinds (src/repro/retrieval/, DESIGN.md §8):
    # rows from the index registry, backend columns from the fused
    # pq_topk dispatch entry, search/sharded cells probed end-to-end
    from repro.retrieval import get_index, index_class as idx_class, \
        registered_index_kinds
    r_backends = sorted(dispatch.registered_ops()["pq_topk"])
    lines.append("")
    lines.append("Retrieval index kinds (`repro.retrieval`, batched "
                 "top-k through the fused `pq_topk` dispatch):")
    lines.append("")
    lines.append("| index | " + " | ".join(
        f"`{b}` ({notes.get(b, 'any')})" for b in r_backends)
        + " | batched top-k | sharded rows |")
    lines.append("|---" * (len(r_backends) + 3) + "|")
    vecs = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    for kind in registered_index_kinds():
        index = get_index(idx_class(kind).probe_config())
        art = index.build(jax.random.PRNGKey(1), vecs)
        cells = [probe(lambda b=b: dispatch.registered_ops()["pq_topk"][b])
                 for b in r_backends]
        cells.append(probe(lambda: index.search(art, vecs[:4], 5)))
        cells.append("✓" if index.supports_sharded
                     and probe(lambda: index.artifact_shard_specs(art))
                     == "✓" else "—")
        lines.append(f"| `{kind}` | " + " | ".join(cells) + " |")
    return "\n".join(lines)


def main(paths):
    for p in paths:
        rows = json.load(open(p))
        print(f"\n### {p} ({len(rows)} rows)\n")
        print(HDR)
        key = lambda r: -(max(r["compute_ms"], r["memory_ms"],
                              r["collective_ms"]))
        for r in sorted(rows, key=key):
            print(fmt(r))


if __name__ == "__main__":
    if "--support-matrix" in sys.argv:
        print(support_matrix())
    else:
        main(sys.argv[1:] or ["results/dryrun_single.json",
                              "results/dryrun_multi.json",
                              "results/hillclimb.json"])
