"""Serving arena (paper §4 as a serving feature) + the batched engine."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import Transformer
from repro.runtime.serve_lib import (Request, ServingArena,
                                     cache_bytes_per_token, request_blocks,
                                     state_bytes)
from repro.serving import GenRequest, ServeEngine


def _trace():
    return [Request(rid=1, prompt_len=64, gen_len=32, arrival=0),
            Request(rid=2, prompt_len=128, gen_len=16, arrival=8),
            Request(rid=3, prompt_len=32, gen_len=48, arrival=24),
            Request(rid=4, prompt_len=64, gen_len=32, arrival=40)]


def test_cache_bytes_per_token_by_family():
    dense = get_config("qwen2-0.5b")
    assert cache_bytes_per_token(dense) == \
        dense.n_layers * 2 * dense.n_kv_heads * dense.resolved_head_dim * 2
    ssm = get_config("mamba2-130m")
    assert cache_bytes_per_token(ssm) == 0          # O(1) state only
    assert state_bytes(ssm) > 0
    hyb = get_config("recurrentgemma-9b")
    assert cache_bytes_per_token(hyb) == 0          # local attn windows are O(1)
    assert state_bytes(hyb) > 0


def test_arena_beats_pool_on_staggered_trace():
    cfg = get_config("qwen2-0.5b")
    arena = ServingArena(cfg, _trace())
    cmp = arena.compare_pool()
    assert cmp["dsa_peak"] <= cmp["pool_peak"]
    assert cmp["dsa_peak"] < cmp["naive_peak"]
    assert cmp["dsa_peak"] >= cmp["lower_bound"]


def test_arena_reoptimizes_on_longer_request():
    cfg = get_config("qwen2-0.5b")
    arena = ServingArena(cfg, _trace())
    arena.reset_epoch()
    arena.admit(Request(rid=1, prompt_len=64, gen_len=32, arrival=0))
    # request 2 runs 8x longer than profiled -> §4.3 replan
    arena.admit(Request(rid=2, prompt_len=128, gen_len=128, arrival=8))
    assert arena.stats()["n_reopt"] == 1


def test_request_blocks_lifetimes():
    cfg = get_config("qwen2-0.5b")
    prof = request_blocks(_trace(), cfg)
    assert prof.n == 4
    b = {blk.bid: blk for blk in prof.blocks}
    assert b[1].start == 0 and b[1].end == 32
    assert b[2].start == 8 and b[2].end == 24


def test_engine_generates_greedy_reference(rng_key):
    cfg = get_config("qwen2-0.5b").smoke()
    model = Transformer(cfg)
    params = model.init(rng_key)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (6,), 0, cfg.vocab_size)

    # reference: naive greedy decode via full forward each step
    toks = list(prompt)
    out_ref = []
    for _ in range(5):
        logits = model.forward(params, jnp.asarray(toks)[None, :])
        nxt = int(jnp.argmax(logits[0, -1]))
        out_ref.append(nxt)
        toks.append(nxt)

    # relocated engine: the request is queued, never manually submitted
    eng = ServeEngine(model, params, max_batch=2, max_len=16,
                      sample_trace=[Request(1, 6, 5, 0)])
    eng.run([GenRequest(rid=1, prompt=prompt, gen_len=5)])
    assert eng.completed[1] == out_ref
    # exact replay of the profiled trace: O(1) allocs, no replanning
    assert eng.kv.arena.stats()["n_reopt"] == 0

    # lazy relocation shim still resolves for old call sites
    from repro.runtime import serve_lib
    assert serve_lib.ServeEngine is ServeEngine


@pytest.mark.parametrize("attn_mode", ["gather", "paged"])
def test_greedy_never_picks_vocab_padding(rng_key, attn_mode):
    """The vocabulary is padded (qwen2-0.5b: 151,936 -> 152,064); the pad
    rows are weights like any other, and greedy serving must not emit them."""
    cfg = get_config("qwen2-0.5b").smoke().with_overrides(vocab_size=300)
    assert cfg.padded_vocab > cfg.vocab_size
    model = Transformer(cfg)
    params = model.init(rng_key)
    table = "lm_head" if "lm_head" in params else "embed"
    # pad rows that outscore every real row: an ungated argmax picks them
    params[table] = params[table].at[cfg.vocab_size:].multiply(1e3)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (6,), 0, cfg.vocab_size)
    toks, out_ref = list(prompt), []
    for _ in range(5):
        logits = model.forward(params, jnp.asarray(toks)[None, :])[0, -1]
        assert int(jnp.argmax(logits)) >= cfg.vocab_size    # trap is armed
        out_ref.append(int(jnp.argmax(logits[:cfg.vocab_size])))
        toks.append(out_ref[-1])

    eng = ServeEngine(model, params, max_batch=2, max_len=16,
                      sample_trace=[Request(1, 6, 5, 0)], attn_mode=attn_mode)
    eng.run([GenRequest(rid=1, prompt=prompt, gen_len=5)])
    assert eng.completed[1] == out_ref
