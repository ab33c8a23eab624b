"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics untraced, the per-layer ones with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``compared``: each number that decided ``correct`` beside its limit.  The
same numbers close standard error.

Without an accelerator, or with fewer chips than the cell asks for, it
prints no result and exits 1.  Compiled programs are kept in
``<checkout>/.jax_cache``, so only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths(root: str, bench: str) -> None:
    for p in (bench, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the persistent compilation cache lives in the checkout, at a fixed
    # path; the program reads this variable and sets no other
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")


def find_chips(chips: int, require_chip: bool):
    """(platform, device kind, count), or None when there is no
    accelerator or too few chips."""
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        return None
    return devs[0].platform, devs[0].device_kind, len(devs)


def main(argv=None, *, root: str = ROOT, bench: str = BENCH,
         require_chip: bool = True, t_proc0: float = T_PROC0,
         fault=None) -> int:
    """``require_chip=False`` and ``fault`` exist for the harness's own
    tests: the first skips the look for a chip, the second is called with
    the engine before serving, to break the timed path underneath."""
    args = parse(argv)
    setup_paths(root, bench)
    import spec as spec_lib
    sp = spec_lib.Spec(root, bench)
    wl = sp.workload(args.workload)
    cfg_file = sp.config(wl["config"])
    family = sp.family(cfg_file["family"])
    mix = sp.traffic(wl["traffic"])
    cell_load = sp.cell(args.workload)

    found = find_chips(int(wl["chips"]), require_chip)
    if found is None:
        log(f"no accelerator with {wl['chips']} chip(s) found; no result")
        return 1
    platform, kind, count = found

    import jax
    if platform == "cpu":       # the harness's tests: no cache to keep
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import check
    import cell as cell_lib
    from yardstick import peaks_for
    peaks = peaks_for("TPU v5 lite" if platform == "cpu" else kind)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, "bench_out",
                                 f"trace-{args.workload}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = cell_lib.run_cell(cfg_file, family, mix, cell_load, seed=args.seed,
                            seconds=args.seconds, traced=bool(args.trace),
                            peaks=peaks, t_proc0=t_proc0,
                            trace_dir=trace_dir, fault=fault)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"set-up {run.setup_s:.3f} s; {len(run.steps)} engine steps; "
        f"{run.compiles_in_window} compiles in the window; peak "
        f"{run.memory_peak_bytes} B; set-up stages " +
        ", ".join(f"{n} {s:.3f}" for n, s in run.setup_stages))

    metrics = {}
    for m in sp.metrics(args.workload, bool(args.trace)):
        v = sp.reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t_chk = time.perf_counter()
    got = check.compare(run, family, cfg_file, mix, args.seed)
    correct, compared = check.judge(got, cfg_file["check"]["limits"])
    log(f"check: {got} in {time.perf_counter() - t_chk:.1f} s")

    win = run.window_requests()
    failed = sum(1 for r in win if r.first is None)
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": len(win),
              "failed": failed, "metrics": metrics, "device": device}
    dt = run.device_trace
    if args.trace and dt:
        device["busy_s"] = dt["busy_s"]
        device["window_s"] = dt["window_s"]     # the part the trace holds
        device["span_s"] = dt["span_s"]         # the whole window span
        log(f"trace holds {dt['window_s']:.3f} s of the {dt['span_s']:.3f} s "
            f"window: programs held on every device {dt['held_n']} of "
            f"{len(run.window_decodes())} decode and "
            f"{len(run.window_prefills())} prefill calls")
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              dt["device_ops"]],
                               "idle_gaps": [list(x) for x in
                                             dt["idle_gaps"]]}
    result["compared"] = compared
    for name, c in compared.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
