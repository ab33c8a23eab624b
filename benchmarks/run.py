"""Benchmark orchestrator — one section per paper table/figure.

  fig2    memory consumption, orig(pool) vs opt(DSA)       (paper Fig. 2)
  fig3    allocation latency, pool search vs O(1) arena    (paper Fig. 3)
  fig4    heuristic runtime + exact-vs-heuristic objective (paper Fig. 4/§5.2)
  sec53   seq2seq variable-length reoptimization           (paper §5.3)
  serve   beyond-paper: DSA on LLM serving KV traces
  remat   beyond-paper: profile-guided rematerialization for training
  unified beyond-paper: one HBM arena for concurrent serve + fine-tune
  scenarios beyond-paper: SLO/goodput matrix on trace-replay traffic
  roofline (optional, needs results/dryrun)                (EXPERIMENTS §Roofline)

Prints ``name,us_per_call,derived`` CSV per line.
Env: BENCH_QUICK=1 (or --quick) for the fast variant (used by CI/tests).
``--trace PATH`` installs one global tracer across every section and writes
the merged Perfetto timeline to PATH; ``--metrics`` installs one global
MetricsRegistry and dumps the Prometheus scrape to ``BENCH_metrics.prom``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import traceback

SUMMARY_JSON = os.environ.get("BENCH_SUMMARY_JSON", "BENCH_summary.json")


def write_summary(quick: bool, failures: int) -> None:
    """Consolidate the per-section BENCH_*.json files (plus the list of
    emitted trace artifacts) into one ``BENCH_summary.json``."""
    sections = {}
    for path in sorted(glob.glob("BENCH_*.json")):
        if os.path.abspath(path) == os.path.abspath(SUMMARY_JSON):
            continue
        key = os.path.basename(path)[len("BENCH_"):-len(".json")]
        try:
            with open(path) as f:
                sections[key] = json.load(f)
        except (OSError, ValueError) as e:
            sections[key] = {"error": str(e)}
    summary = {
        "quick": quick,
        "failures": failures,
        "sections": sections,
        "traces": sorted(glob.glob("TRACE_*.json")),
    }
    with open(SUMMARY_JSON, "w") as f:
        json.dump(summary, f, indent=2)
    print(f"# wrote {SUMMARY_JSON} ({len(sections)} sections, "
          f"{len(summary['traces'])} traces)")


def _import_benches():
    try:
        from . import (bench_alloc_time, bench_heuristic, bench_memory,
                       bench_remat, bench_reopt, bench_serving, bench_unified,
                       scenarios)
    except ImportError:
        # script mode (`python benchmarks/run.py`): repo root + src on path,
        # then import the benchmarks namespace package absolutely
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for p in (root, os.path.join(root, "src")):
            if p not in sys.path:
                sys.path.insert(0, p)
        from benchmarks import (bench_alloc_time, bench_heuristic,
                                bench_memory, bench_remat, bench_reopt,
                                bench_serving, bench_unified, scenarios)
    return (bench_alloc_time, bench_heuristic, bench_memory, bench_remat,
            bench_reopt, bench_serving, bench_unified, scenarios)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast variant (same as BENCH_QUICK=1)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="install one global tracer across all sections and "
                         "write the merged Perfetto timeline to PATH")
    ap.add_argument("--metrics", action="store_true",
                    help="install one global MetricsRegistry and dump the "
                         "Prometheus scrape to BENCH_metrics.prom")
    args, _ = ap.parse_known_args()
    quick = args.quick or bool(int(os.environ.get("BENCH_QUICK", "0")))
    (bench_alloc_time, bench_heuristic, bench_memory, bench_remat,
     bench_reopt, bench_serving, bench_unified, scenarios) = _import_benches()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    sections = [
        ("fig2", bench_memory.main),
        ("fig3", bench_alloc_time.main),
        ("fig4", bench_heuristic.main),
        ("sec53", bench_reopt.main),
        ("serve", bench_serving.main),
        ("remat", bench_remat.main),
        ("unified", bench_unified.main),
        ("scenarios", scenarios.main),
    ]

    from contextlib import ExitStack

    from repro.obs import (ChromeTraceBuilder, MetricsRegistry, Tracer,
                           use_registry, use_tracer)
    stack = ExitStack()
    tracer = registry = None
    if args.trace:
        tracer = stack.enter_context(use_tracer(Tracer(capacity=1 << 20)))
    if args.metrics:
        registry = stack.enter_context(use_registry(MetricsRegistry()))

    failures = 0
    with stack:
        for name, fn in sections:
            t0 = time.time()
            try:
                fn(quick=quick)
                print(f"# section {name} done in {time.time() - t0:.1f}s")
            except Exception:
                failures += 1
                print(f"# section {name} FAILED:", file=sys.stderr)
                traceback.print_exc()

    if tracer is not None:
        tb = ChromeTraceBuilder()
        tb.add_events(tracer.events())
        tb.write(args.trace)
        print(f"# wrote {args.trace} ({len(tracer.events())} events, "
              f"{tracer.n_dropped} dropped)")
    if registry is not None:
        with open("BENCH_metrics.prom", "w") as f:
            f.write(registry.to_prometheus_text())
        print(f"# wrote BENCH_metrics.prom ({len(registry.metrics())} metrics)")

    # roofline section (only if dry-run artifacts exist)
    dr = os.environ.get("DRYRUN_DIR", "results/dryrun")
    if os.path.isdir(dr):
        try:
            from repro.launch import roofline
            cells = roofline.load_cells(dr, mesh="single")
            print("# Roofline: name,us_per_call,derived")
            for c in cells:
                dom_s = {"compute": c.compute_s, "memory": c.memory_s,
                         "collective": c.coll_s}[c.dominant]
                print(f"roofline/{c.arch}/{c.shape},{dom_s * 1e6:.1f},"
                      f"dominant={c.dominant};compute_s={c.compute_s:.4g};"
                      f"memory_s={c.memory_s:.4g};coll_s={c.coll_s:.4g};"
                      f"useful_ratio={c.useful_ratio:.3f}")
        except Exception:
            failures += 1
            traceback.print_exc()
    write_summary(quick, failures)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
