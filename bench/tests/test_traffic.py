"""The generator: determined by the seed, and the same work for every seed."""
import json
import os
from collections import Counter

import numpy as np
import pytest

import traffic

MIX = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "chat-poisson.json")))


def _key(reqs):
    return [(r.rid, round(r.due, 9), r.prompt_len, r.gen_len, r.phase)
            for r in reqs]


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 20.0, 30.0, 2 ** 31 + 12345)
    b = traffic.schedule(MIX, 20.0, 30.0, 2 ** 31 + 12345)
    assert _key(a) == _key(b)
    ta = traffic.prompt_tokens(a, 151936, 2 ** 31 + 12345)
    tb = traffic.prompt_tokens(b, 151936, 2 ** 31 + 12345)
    assert all(np.array_equal(ta[k], tb[k]) for k in ta)


def test_seeds_reorder_the_same_work():
    mix = {**MIX, "order": "shuffled"}
    a = traffic.schedule(mix, 20.0, 30.0, 1)
    b = traffic.schedule(mix, 20.0, 30.0, 2)
    assert _key(a) != _key(b)
    for phase in traffic.PHASES:
        pa = [r for r in a if r.phase == phase]
        pb = [r for r in b if r.phase == phase]
        assert len(pa) == len(pb) == round(20.0 * {
            "warmup": MIX["warmup_s"], "window": 30.0,
            "drain": MIX["drain_s"]}[phase])
        assert Counter((r.prompt_len, r.gen_len) for r in pa) == \
            Counter((r.prompt_len, r.gen_len) for r in pb)


def test_fixed_order_is_one_schedule_for_every_seed():
    assert MIX["order"] == "fixed"
    a = traffic.schedule(MIX, 20.0, 30.0, 1)
    assert _key(a) == _key(traffic.schedule(MIX, 20.0, 30.0, 2 ** 31 + 9))
    shuffled = traffic.schedule({**MIX, "order": "shuffled"}, 20.0, 30.0, 1)
    assert Counter((r.prompt_len, r.gen_len, r.phase) for r in a) == \
        Counter((r.prompt_len, r.gen_len, r.phase) for r in shuffled)
    ta = traffic.prompt_tokens(a, 151936, 1)
    tb = traffic.prompt_tokens(a, 151936, 2)
    assert not all(np.array_equal(ta[k], tb[k]) for k in ta)


def test_unknown_order_is_refused():
    with pytest.raises(ValueError, match="order"):
        traffic.schedule({**MIX, "order": "sorted"}, 20.0, 30.0, 1)


def test_phases_and_bounds():
    reqs = traffic.schedule(MIX, 10.0, 20.0, 7)
    w0, w1 = MIX["warmup_s"], MIX["warmup_s"] + 20.0
    for r in reqs:
        lo, hi = {"warmup": (0, w0), "window": (w0, w1),
                  "drain": (w1, w1 + MIX["drain_s"])}[r.phase]
        assert lo <= r.due < hi
        assert MIX["prompt"]["min"] <= r.prompt_len <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= r.gen_len <= MIX["output"]["max"]
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)


def test_sample_trace_is_the_mix_in_flight_at_once():
    a = traffic.sample_trace(MIX, 32)
    assert a == traffic.sample_trace(MIX, 32)
    assert len(a) == 32 and {x[2] for x in a} == {0}
    for p, g, _ in a:
        assert MIX["prompt"]["min"] <= p <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= g <= MIX["output"]["max"]
