"""One general generator of serving traffic, driven by a mix's data file.

A mix (``traffic/<name>.json``) gives the arrival process, the length
distributions, and the phases of a run: warm-up, the measured window, and
the drain, the most the run waits after the window for every request due
in it to get its first token (arrivals go on meanwhile, unmeasured).  A
cell's own file (``cells/<workload>.json``) gives its fixed rate.

Every seed gets the same work.  The sizes and the gaps between arrivals
are drawn once from the mix's ``shape_seed``; ``--seed`` draws the prompt
tokens and, where the mix's ``order`` is ``"shuffled"``, shuffles sizes and
gaps.  With ``"fixed"`` every seed replays one schedule, so that a tail set
by how many long requests overlap does not change from seed to seed.

The length sampler is the seeded lognormal of ``repro.serving.loadgen``
(median, sigma, clipped), with arrivals on the wall clock in seconds.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass

import numpy as np

PHASES = ("warmup", "window", "drain")


@dataclass
class Req:
    rid: int
    due: float            # seconds after serving starts
    prompt_len: int
    gen_len: int
    phase: str


def lognormal(rng: random.Random, median: float, sigma: float, lo: int,
              hi: int) -> int:
    v = rng.lognormvariate(math.log(max(1.0, median)), sigma)
    return max(lo, min(hi, int(round(v))))


def _sizes(rng: random.Random, mix: dict, n: int) -> list[tuple[int, int]]:
    p, o = mix["prompt"], mix["output"]
    return [(lognormal(rng, p["median"], p["sigma"], p["min"], p["max"]),
             lognormal(rng, o["median"], o["sigma"], o["min"], o["max"]))
            for _ in range(n)]


def _poisson_times(rng: random.Random, rate: float, span: float,
                   n: int) -> list[float]:
    """``n`` arrivals of a Poisson process on [0, span), given that count:
    exponential gaps, normalised so that they fill the span."""
    gaps = [rng.expovariate(rate) for _ in range(n + 1)]
    scale = span / sum(gaps)
    return [g * scale for g in gaps[:n]]


def schedule(mix: dict, rate: float, window_s: float, seed: int) -> list[Req]:
    """Requests of the warm-up, the window and the drain, sorted by due
    time.  The window starts at ``mix["warmup_s"]``."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    if mix["order"] not in ("fixed", "shuffled"):
        raise ValueError(f"unknown order {mix['order']!r}")
    spans = {"warmup": float(mix["warmup_s"]), "window": float(window_s),
             "drain": float(mix["drain_s"])}
    base = random.Random(mix["shape_seed"])
    shuffle = random.Random(seed)
    out, t0, rid = [], 0.0, 0
    for phase in PHASES:
        span = spans[phase]
        n = int(round(rate * span))
        gaps = _poisson_times(base, rate, span, n)
        sizes = _sizes(base, mix, n)
        if mix["order"] == "shuffled":
            shuffle.shuffle(gaps)
            shuffle.shuffle(sizes)
        t = t0
        for g, (p, o) in zip(gaps, sizes):
            t += g
            out.append(Req(rid=rid, due=t, prompt_len=p, gen_len=o,
                           phase=phase))
            rid += 1
        t0 += span
    return out


def prompt_tokens(reqs: list[Req], vocab: int, seed: int) -> dict:
    """rid -> prompt ids, uniform over the real vocabulary."""
    rng = np.random.default_rng(seed)
    return {r.rid: rng.integers(0, vocab, r.prompt_len, dtype=np.int32)
            for r in reqs}


def sample_trace(mix: dict, n: int) -> list[tuple[int, int, int]]:
    """The planner's sample trace, (prompt_len, gen_len, arrival step): ``n``
    requests of the mix's sizes, all in flight from step 0, so that the
    pool is planned for the concurrency the deployment provisions.  The
    sizes come from the mix's ``shape_seed`` alone, so every seed gets the
    same pool."""
    sizes = _sizes(random.Random(mix["shape_seed"] + 1), mix, n)
    return [(p, g, 0) for p, g in sizes]
