"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed, in one process: serve the cell's traffic at its own load for
a short window, then compare a sample of the served requests with the
float32 reference (the program's reading) and, at the same positions, read
the fp8 control (the reading of the step below the configuration's
precision).  Each reading goes through the same comparison with the
configuration's limits that decides ``correct`` in a run: the program has
to pass it, the control has to fail it.  The benchmark's own runs never run
this.  One JSON line per seed, then a summary line: the largest program
reading and the smallest control reading.
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


def main(argv=None, *, root: str = ROOT, bench: str = BENCH,
         require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as run_lib
    run_lib.setup_paths(root, bench)
    import spec as spec_lib
    sp = spec_lib.Spec(root, bench)
    wl = sp.workload(args.workload)
    cfg_file = sp.config(wl["config"])
    family = sp.family(cfg_file["family"])
    mix = sp.traffic(wl["traffic"])
    found = run_lib.find_chips(int(wl["chips"]), require_chip)
    if found is None:
        print("no accelerator found", file=sys.stderr)
        return 1
    import jax
    if found[0] == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import cell as cell_lib
    import check
    from yardstick import peaks_for
    peaks = peaks_for("TPU v5 lite" if found[0] == "cpu" else found[1])
    prog, ctl = [], []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = cell_lib.run_cell(cfg_file, family, mix,
                                sp.cell(args.workload), seed=seed,
                                seconds=args.seconds, traced=False,
                                peaks=peaks, t_proc0=t0)
        got = check.compare(run, family, cfg_file, mix, seed, control=True)
        got["seed"] = seed
        got["failed"] = sum(1 for r in run.window_requests()
                            if r.first is None)
        got["seconds"] = time.perf_counter() - t0
        limits = cfg_file["check"]["limits"]
        got["program_correct"] = check.judge(got, limits)[0]
        got["control_correct"] = check.judge(
            dict(got, logit_gap_max=got.get("control_gap_max")), limits)[0]
        print(json.dumps(got), flush=True)
        if "logit_gap_max" in got:
            prog.append(got["logit_gap_max"])
            ctl.append(got["control_gap_max"])
    print(json.dumps({"program_max": max(prog, default=None),
                      "control_min": min(ctl, default=None),
                      "program": prog, "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
