"""Decide ``correct``: the served tokens against the float32 reference.

Once the window has closed and the program's state is freed, a sample of
the requests due in the window that finished, drawn from the seed and always
holding the longest of them, is replayed through the reference teacher
forced: prompt plus served tokens.  At the position before each served
token the reference reads that token's logit and its own best one.  The
number compared is the widest gap.  A greedy token that rounding in the
program put past a near tie lies below the best by about the rounding;
a wrong token, a broken KV merge or a broken cache lies below it by the
spread of the logits.
"""
from __future__ import annotations

import math
import random

import numpy as np

from reference import served_gaps


def sample(run, mix: dict, seed: int) -> list:
    """rids of the finished window requests to check: the longest, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    done = [r for r in run.window_requests() if r.rid in run.served]
    if not done:
        return []
    chk = mix["check"]
    longest = max(done, key=lambda r: (r.prompt_len + r.gen_len, -r.rid))
    rest = [r for r in done if r.rid != longest.rid]
    random.Random(seed).shuffle(rest)
    out, n = [longest], len(run.served[longest.rid])
    for r in rest:
        if n >= chk["min_tokens"] or len(out) >= chk["max_requests"]:
            break
        out.append(r)
        n += len(run.served[r.rid])
    return [r.rid for r in out]


def compare(run, family, cfg_file: dict, mix: dict, seed: int,
            control: bool = False) -> dict:
    """The numbers compared, with what was checked, against ``family``'s
    reference.  ``control`` also reads the fp8 control at the same
    positions."""
    rids = sample(run, mix, seed)
    out = {"requests": len(rids), "tokens": 0}
    if not rids:
        return out
    bad_ids = [rid for rid in rids
               if not all(0 <= t < run.yardstick.vocab
                          for t in run.served[rid])]
    out["out_of_vocab"] = len(bad_ids)
    reqs = [(np.asarray(run.prompts[rid]), np.asarray(run.served[rid]))
            for rid in rids]
    ref = family.reference(cfg_file, seed)
    ctl = family.reference(cfg_file, seed, "fp8") if control else None
    out.update(served_gaps(ref, reqs, mix["output"]["max"], ctl))
    return out


def judge(got: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number compared beside its limit: every number
    has to be there, finite and at most its limit."""
    correct = got.get("requests", 0) > 0 and got.get("out_of_vocab", 1) == 0
    compared = {}
    for name, limit in limits.items():
        value = got.get(name)
        if value is not None and not math.isfinite(value):
            value = None                # no reading: not correct
        compared[name] = {"value": value, "limit": limit}
        correct = correct and value is not None and value <= limit
    return correct, compared
