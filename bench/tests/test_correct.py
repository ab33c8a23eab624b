"""``correct`` on whole runs of a tiny cell on the CPU: true for the program
as it is, false with the timed path broken underneath, and false for the
fp8 control put in the program's place.

These skip the harness's look for a chip and drive the rest of a run:
set-up, the wall-clock window, the drain, and the comparison with the
float32 reference.
"""
import io
import json
import sys
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def run_cell(root, fault=None, seed=SEED, workload="tiny.chat"):
    import run as run_lib
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run_lib.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "2", "--trace", "0"],
                          root=root, bench=f"{root}/bench",
                          require_chip=False, fault=fault)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def altered_token(engine):
    """Every decoded token is replaced by its neighbour id, in the served
    stream and in the token buffer the next step reads."""
    raw = engine.runner.step_greedy
    vocab = engine.model.cfg.vocab_size

    def step(params, cache, tokens, slots):
        nxt, new_tokens, new_cache = raw(params, cache, tokens, slots)
        nxt = (np.asarray(nxt) + 1) % vocab
        new_tokens = new_tokens.at[jnp.asarray(slots)].set(
            jnp.asarray(nxt, jnp.int32))
        return nxt, new_tokens, new_cache
    engine.runner.step_greedy = step


def state_unchanged(engine):
    """The decode step hands back the cache it was given (no KV written,
    positions not advanced).  The CPU runner does not donate, so the old
    cache is still valid."""
    raw = engine.runner.step_greedy
    assert not engine.runner.donate

    def step(params, cache, tokens, slots):
        nxt, new_tokens, _ = raw(params, cache, tokens, slots)
        return nxt, new_tokens, cache
    engine.runner.step_greedy = step


def test_sound_run_is_correct(root):
    out = run_cell(root)
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    c = out["compared"]["logit_gap_max"]
    assert c["value"] <= c["limit"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_broken_timed_path_is_not_correct(root, fault):
    out = run_cell(root, fault=fault)
    assert out["correct"] is False
    c = out["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]


def test_fp8_control_fails_the_limit(root):
    """The control at the tiny size: the reference computed in fp8 at the
    positions of the program's served tokens."""
    import check
    import run as run_lib
    import spec as spec_lib
    from cell import run_cell as drive
    from yardstick import peaks_for
    run_lib.setup_paths(root, f"{root}/bench")
    sp = spec_lib.Spec(root, f"{root}/bench")
    cfg, mix = sp.config("tiny"), sp.traffic("tiny-chat")
    jax.config.update("jax_enable_compilation_cache", False)
    family = sp.family(cfg["family"])
    rec = drive(cfg, family, mix, sp.cell("tiny.chat"), seed=SEED + 1,
                seconds=2.0, traced=False, peaks=peaks_for("TPU v5 lite"),
                t_proc0=0.0)
    got = check.compare(rec, family, cfg, mix, SEED + 1, control=True)
    limit = cfg["check"]["limits"]["logit_gap_max"]
    print(got, file=sys.stderr)
    assert got["logit_gap_max"] <= limit < got["control_gap_max"]
