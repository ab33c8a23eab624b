"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment requirement c)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import RunOpts, Transformer

KEYS = jax.random.split(jax.random.PRNGKey(0), 12)


@pytest.mark.parametrize("b,s,kv,g,hd,causal,window,dtype", [
    (2, 128, 2, 2, 64, True, 0, jnp.float32),
    (1, 200, 1, 4, 32, True, 0, jnp.float32),     # ragged seq
    (2, 256, 2, 1, 64, True, 64, jnp.bfloat16),   # sliding window
    (1, 128, 4, 2, 128, False, 0, jnp.float32),   # non-causal (whisper cross)
    (1, 96, 2, 3, 64, True, 32, jnp.float32),     # window + ragged
    (3, 64, 1, 1, 16, True, 0, jnp.bfloat16),     # tiny dims
])
def test_flash_attention_matches_ref(b, s, kv, g, hd, causal, window, dtype):
    q = jax.random.normal(KEYS[0], (b, s, kv, g, hd), dtype)
    k = jax.random.normal(KEYS[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(KEYS[2], (b, s, kv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    qh = q.reshape(b, s, kv * g, hd).transpose(0, 2, 1, 3)
    r = ref.ref_attention_bhsd(qh, k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               causal=causal, window=window)
    r = r.transpose(0, 2, 1, 3).reshape(b, s, kv, g, hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.abs(out.astype(jnp.float32) -
                         r.astype(jnp.float32)).max()) < tol


def test_flash_attention_q_offset_decode_window():
    """Chunk-of-decode usage: q positions offset into the sequence."""
    b, sq, sk, kv, g, hd = 1, 8, 128, 2, 2, 32
    q = jax.random.normal(KEYS[3], (b, sq, kv, g, hd))
    k = jax.random.normal(KEYS[4], (b, sk, kv, hd))
    v = jax.random.normal(KEYS[5], (b, sk, kv, hd))
    out = ops.flash_attention(q, k, v, causal=True, q_offset=100,
                              block_q=8, block_k=64)
    qh = q.reshape(b, sq, kv * g, hd).transpose(0, 2, 1, 3)
    r = ref.ref_attention_bhsd(qh, k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=True,
                               q_offset=100)
    r = r.transpose(0, 2, 1, 3).reshape(b, sq, kv, g, hd)
    assert float(jnp.abs(out - r).max()) < 2e-5


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 64, 4, 16, 1, 8, 16),
    (1, 100, 2, 8, 2, 4, 32),    # ragged + grouped B/C
    (1, 32, 8, 4, 4, 16, 8),
])
def test_ssd_scan_matches_ref(b, s, h, p, g, n, chunk):
    x = jax.random.normal(KEYS[6], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(KEYS[7], (b, s, h)))
    a_log = jax.random.normal(KEYS[8], (h,)) * 0.5
    bm = jax.random.normal(KEYS[9], (b, s, g, n))
    cm = jax.random.normal(KEYS[10], (b, s, g, n))
    d_skip = jnp.ones((h,))
    y, hf = ops.ssd_scan(x, dt, a_log, bm, cm, d_skip, chunk=chunk)
    a = -jnp.exp(a_log)
    yr, hr = ref.ref_ssd(x * dt[..., None], dt * a, bm, cm)
    yr = yr + x * d_skip[None, None, :, None]
    assert float(jnp.abs(y - yr).max()) < 1e-3
    assert float(jnp.abs(hf - hr).max()) < 1e-3


@pytest.mark.parametrize("b,s,l,block", [
    (2, 64, 32, 16),
    (1, 100, 16, 32),            # ragged
    (4, 16, 8, 16),              # single block
])
def test_rglru_scan_matches_ref(b, s, l, block):
    a = jax.nn.sigmoid(jax.random.normal(KEYS[11], (b, s, l)))
    bb = jax.random.normal(KEYS[0], (b, s, l))
    h0 = jax.random.normal(KEYS[1], (b, l))
    y = ops.rglru_scan(a, bb, h0, block=block)
    yr = ref.ref_rglru(a, bb, h0)
    assert float(jnp.abs(y - yr).max()) < 1e-4


@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-9b", "qwen2-0.5b"])
def test_model_kernel_path_matches_xla(arch, rng_key):
    cfg = get_config(arch).smoke()
    m_x = Transformer(cfg, RunOpts(use_kernels=False))
    impl = "pallas" if arch == "qwen2-0.5b" else "auto"
    m_k = Transformer(cfg, RunOpts(use_kernels=True, attention_impl=impl,
                                   ssd_chunk=8))
    params = m_x.init(rng_key)
    tokens = jax.random.randint(rng_key, (2, 24), 0, cfg.vocab_size)
    err = float(jnp.abs(m_x.forward(params, tokens) -
                        m_k.forward(params, tokens)).max())
    assert err < 5e-3


def test_vmem_budget_guard():
    """The planner rejects block shapes that overflow VMEM (paper's planning
    at the VMEM level) and the wrapper enforces it."""
    from repro.core.planner import MemoryPlanner
    from repro.kernels.flash_attention import vmem_blocks
    chk = MemoryPlanner.check_vmem(vmem_blocks(2048, 2048, 2048, jnp.float32))
    assert not chk["fits"]
    q = jnp.ones((1, 2048, 1, 1, 2048), jnp.float32)
    k = jnp.ones((1, 2048, 1, 2048), jnp.float32)
    with pytest.raises(AssertionError, match="VMEM"):
        ops.flash_attention(q, k, k, block_q=2048, block_k=2048)


@pytest.mark.parametrize("value,want", [
    (None, False), ("", False), ("0", False), ("false", False),
    ("1", True), ("yes", True)])
def test_interpret_mode_only_on_request(monkeypatch, value, want):
    """No backend sniffing: interpret mode comes from the environment (or an
    explicit argument), never from finding no chip."""
    if value is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    assert ops.interpret_requested() is want
