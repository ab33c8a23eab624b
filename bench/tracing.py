"""Reduce a JAX profiler trace to the numbers the benchmark reports.

The trace is read with ``jax.profiler.ProfileData`` and nothing else.  From
the device planes it takes the operations (line ``XLA Ops``) and the
programs (line ``XLA Modules``); from the host planes the spans the harness
wrote with ``TraceAnnotation`` (names starting with ``bench.``).  The
measured window is the ``bench.window`` span, so host and device are read on
the profiler's own clock.

  * busy: the union of operation intervals inside the window, averaged
    over the devices; idle is the rest of the window;
  * per-program device time: module durations inside the window, grouped
    by ``program_kind``;
  * the operations that took most time, each named by its program's kind
    and its HLO name, and the idle time grouped by the innermost host span
    that was open at the middle of each gap.

The profiler puts host and device on one clock only to about a
millisecond (a recorded v5e trace had the device ~1.2 ms behind), so a
gap's host span is right for gaps longer than that.

The profiler stops recording a device's operations after some millions of
events (~6.1 M on a v5e) while the host spans go on.  A trace is cut where
a device's last recorded operation ends and an engine step (``bench.step``)
starts after it inside the window: that step ran programs the trace does
not hold.  Every number is then read from the window's start to that end,
the part the trace holds (``window_s``; the whole span is ``span_s``), and
``recorded`` picks the host's calls whose programs it holds.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"


def program_kind(module_name: str) -> str:
    """Which of the program's compiled steps a device module is."""
    if "prefill_fn" in module_name:
        return "prefill"
    if "_step_fn" in module_name:
        return "decode"
    return "other"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def extract(pd) -> dict:
    """Plain event lists, (start_ns, end_ns, name), from ``ProfileData``."""
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                for ev in line.events:
                    dest[plane.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
            elif not is_device:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _innermost(spans, starts, t, look_back: int = 64):
    """The latest-started span (other than the window) open at ``t``;
    ``spans`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - look_back), -1):
        s, e, name = spans[j]
        if e > t and name != WINDOW_SPAN:
            return name
    return "no host span"


def _op_label(name: str, mods, mstarts, t) -> str:
    """``<program kind>/<op>``: the HLO text of an op event cut to its
    name, prefixed with the kind of the module running at ``t``."""
    op = name.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(mstarts, t) - 1
    kind = program_kind(mods[i][2]) if i >= 0 and mods[i][1] > t else "?"
    return f"{kind}/{op}"


def recorded_end(ex: dict, hi: int) -> int:
    """Where the trace stops holding the window that ends at ``hi``: the
    end of the last recorded operation of the device whose recording
    stopped first, where an engine step starts after it before ``hi``;
    else ``hi``."""
    end = min(max(e for _, e, _ in evs) for evs in ex["ops"].values())
    if end < hi and any(name == STEP_SPAN and end < s < hi
                        for s, _, name in ex["spans"]):
        return end
    return hi


def recorded(calls: list, dt: dict, kind: str) -> list:
    """The first of ``calls`` (the window's calls of one kind, in order), as
    many as every device holds programs of ``kind``: the calls whose device
    time is ``dt["program_s"][kind]``."""
    return calls[:dt["held_n"].get(kind, 0)]


def reduce(ex: dict, top: int = 10) -> Optional[dict]:
    """Numbers of the window span, as far as the trace holds it; None if
    the trace holds no window or no device operation."""
    win = [s for s in ex["spans"] if s[2] == WINDOW_SPAN]
    if not win or not ex["ops"]:
        return None
    lo, span_hi = win[0][0], win[0][1]
    hi = recorded_end(ex, span_hi)
    if hi <= lo:
        return None
    window_s = (hi - lo) * 1e-9
    busy, gaps, op_time = [], [], defaultdict(float)
    prog_time, prog_count = defaultdict(float), defaultdict(int)
    for dev, evs in ex["ops"].items():
        clipped = _clip([(s, e) for s, e, _ in evs], lo, hi)
        merged = union(clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        mods = sorted(ex["modules"].get(dev, []))
        mstarts = [m[0] for m in mods]
        for s, e, name in evs:
            if e > lo and s < hi:
                op_time[_op_label(name, mods, mstarts, s)] += \
                    (min(e, hi) - max(s, lo)) * 1e-9
    dev_counts = []
    for dev, evs in ex["modules"].items():
        n = defaultdict(int)
        for s, e, name in evs:
            if s >= lo and e <= hi:
                k = program_kind(name)
                prog_time[k] += (e - s) * 1e-9
                n[k] += 1
        dev_counts.append(n)
        for k, c in n.items():
            prog_count[k] += c
    spans = sorted(s for s in ex["spans"] if s[1] > lo and s[0] < hi)
    starts = [s[0] for s in spans]
    idle_by = defaultdict(float)
    for s, e in gaps:
        idle_by[_innermost(spans, starts, (s + e) // 2)] += (e - s) * 1e-9
    n_dev = len(ex["ops"])
    busy_s = sum(busy) / n_dev
    return {
        "window_s": window_s,
        "span_s": (span_hi - lo) * 1e-9,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "program_s": dict(prog_time),
        "program_n": dict(prog_count),
        "held_n": {k: min(n[k] for n in dev_counts) for k in prog_count},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v / n_dev) for k, v in idle_by.items()),
                            key=lambda kv: -kv[1])[:top],
        "n_devices": n_dev,
    }


def reduce_dir(trace_dir: str) -> Optional[dict]:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(extract(ProfileData.from_file(path)))
