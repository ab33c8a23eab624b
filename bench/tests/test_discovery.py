"""Discovery by name: a configuration, a traffic mix, a cell and a metric
added as new files, with new entries in BENCHMARK.json, are found without
an edit to any file that was there."""
import hashlib
import json
import os

import spec as spec_lib
import tiny


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = tiny.make_root(str(tmp_path))
    before = _digests(root)
    b = os.path.join(root, "bench")
    cfg = dict(tiny.TINY_CONFIG, name="tiny2", num_hidden_layers=3)
    with open(os.path.join(b, "configs", "tiny2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "burst.json"), "w") as f:
        json.dump(dict(tiny.TINY_TRAFFIC, warmup_s=0.25), f)
    with open(os.path.join(b, "cells", "tiny2.burst.json"), "w") as f:
        json.dump({"rate_per_s": 9.0}, f)
    with open(os.path.join(b, "metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(1 for s, e in run.steps if run.in_window(e))\n")
    doc_path = os.path.join(root, "BENCHMARK.json")
    doc = json.load(open(doc_path))
    doc["configs"].append({"name": "tiny2", "source": "test",
                           "file": "bench/configs/tiny2.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny2.burst", "config": "tiny2",
                             "traffic": "burst", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                             "better": "higher", "source": "host_clock",
                             "layer": "engine", "moves": "tokens_per_s",
                             "workloads": ["tiny2.burst"]})
    json.dump(doc, open(doc_path, "w"))

    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())  # nothing edited

    sp = spec_lib.Spec(root, b)
    wl = sp.workload("tiny2.burst")
    assert sp.config(wl["config"])["num_hidden_layers"] == 3
    assert sp.traffic(wl["traffic"])["warmup_s"] == 0.25
    assert sp.cell("tiny2.burst")["rate_per_s"] == 9.0
    names = [m["name"] for m in sp.metrics("tiny2.burst", traced=True)]
    assert "steps_in_window" in names
    assert "steps_in_window" not in [
        m["name"] for m in sp.metrics("tiny.chat", traced=True)]

    class Run:
        steps = [(0.0, 1.0), (1.0, 2.0), (2.0, 9.0)]

        @staticmethod
        def in_window(t):
            return 0.5 <= t < 5.0
    assert sp.reader("steps_in_window")(Run) == 2


def test_every_listed_metric_has_a_reader():
    root = os.path.dirname(os.path.dirname(tiny.BENCH))
    sp = spec_lib.Spec(tiny.REPO, tiny.BENCH)
    for group in ("end_to_end", "per_layer"):
        for m in sp.doc[group]:
            assert callable(sp.reader(m["name"])), m["name"]
    for w in sp.doc["workloads"]:
        assert sp.cell(w["name"])["rate_per_s"] > 0
        sp.traffic(w["traffic"])
        sp.config(w["config"])
    assert root


def test_family_is_this_roots_file(tmp_path):
    """Each root's ``families/<name>.py`` is its own: a family file that
    differs in another root is read from there, and the importable one is
    the ``families`` package's module."""
    root = tiny.make_root(str(tmp_path))
    b = os.path.join(root, "bench")
    path = os.path.join(b, "families", "dense_gqa.py")
    with open(path, "a") as f:
        f.write("\nPLANTED = 1\n")
    here = spec_lib.Spec(tiny.REPO).family("dense_gqa")
    there = spec_lib.Spec(root, b).family("dense_gqa")
    assert there.__file__ == path and there.PLANTED == 1
    assert not hasattr(here, "PLANTED")
    assert spec_lib.Spec(tiny.REPO).family("dense_gqa") is here
