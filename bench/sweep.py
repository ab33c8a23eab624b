"""Find a cell's knee: the highest offered rate whose queue does not grow.

    python bench/sweep.py --workload <cell> --rates 2,3,4 --seeds 7,8 --seconds 51

In one process, serve the cell's traffic at each seed and rate in turn
(fresh weights and engine each time) and print one JSON line per pair: tokens/s, the median and p95 time to first token by quarter of the
window, and the backlog (requests due in the window without a first token)
at the window's end.  Where the queue grows, the last quarter's median
time to first token climbs past the first's and the backlog keeps the
arrivals of seconds.  The benchmark's own runs never run this; a cell's
fixed rate (``cells/<workload>.json``) is 0.8 of the knee it found.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def quarters(run) -> list:
    lo, hi = run.window
    q = (hi - lo) / 4
    end = run.steps[-1][1] if run.steps else hi
    out = []
    for k in range(4):
        ttft = sorted((r.first if r.first is not None else end) - r.due
                      for r in run.window_requests()
                      if lo + k * q <= r.due < lo + (k + 1) * q)
        out.append(round(ttft[len(ttft) // 2] * 1e3, 1) if ttft else None)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    import run as run_lib
    run_lib.setup_paths(ROOT, BENCH)
    import spec as spec_lib
    sp = spec_lib.Spec(ROOT, BENCH)
    wl = sp.workload(args.workload)
    found = run_lib.find_chips(int(wl["chips"]), True)
    if found is None:
        print("no accelerator found", file=sys.stderr)
        return 1
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import cell as cell_lib
    from stats import percentile
    from yardstick import peaks_for
    cfg, mix = sp.config(wl["config"]), sp.traffic(wl["traffic"])
    family = sp.family(cfg["family"])
    pairs = [(int(s), float(r)) for s in args.seeds.split(",")
             for r in args.rates.split(",")]
    for seed, rate in pairs:
        t0 = time.perf_counter()
        run = cell_lib.run_cell(cfg, family, mix, {"rate_per_s": rate},
                                seed=seed, seconds=args.seconds,
                                traced=False, peaks=peaks_for(found[1]),
                                t_proc0=t0)
        w1 = run.window[1]
        out = {"seed": seed, "rate": rate,
               "tokens_per_s": sp.reader("tokens_per_s")(run),
               "ttft_p95_ms": percentile(run.ttft_ms(), 95),
               "ttft_p50_ms": percentile(run.ttft_ms(), 50),
               "itl_p95_ms": sp.reader("itl_p95_ms")(run),
               "ttft_median_by_quarter_ms": quarters(run),
               "backlog_at_end": sum(1 for r in run.window_requests()
                                     if r.first is None or r.first >= w1),
               "failed": sum(1 for r in run.window_requests()
                             if r.first is None),
               "preempted": sum(r.preempted for r in run.requests),
               "gen_lag_p95_ms": sp.reader("gen_lag_p95_ms")(run),
               "decode_step_ms": sp.reader("decode_step_ms")(run),
               "engine_step_ms_mean": 1e3 * sum(
                   e - s for s, e in run.steps if run.in_window(e)) / max(
                   1, sum(1 for _, e in run.steps if run.in_window(e))),
               "decode_batch_mean": sp.reader("decode_batch_mean")(run),
               "setup_s": run.setup_s}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
