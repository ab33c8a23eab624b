"""Compile the main-path Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (what every other kernel test runs) cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, VMEM overruns,
primitives Mosaic does not lower.  These tests AOT-compile each kernel at
qwen2-0.5b widths in bf16 for one chip of a ``v5e:2x2`` topology described by
the installed TPU compiler, and check that the kernel really is in the
program (``tpu_custom_call``) and was not lowered as plain XLA.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest worker
imports this file.  Keep these tests in this one file so that only the worker
that runs them loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops

CFG = get_config("qwen2-0.5b")
KV = CFG.n_kv_heads                               # 2
GROUP = CFG.n_heads // CFG.n_kv_heads             # 7
HD = CFG.resolved_head_dim                        # 64
BATCH = 8                                         # the runner's top bucket


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered to Mosaic"
    return compiled


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("page_tokens", [16, 32])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, page_tokens, dtype):
    pages_per_req = 512 // page_tokens + 1
    n_pool = BATCH * pages_per_req
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((BATCH, KV, GROUP, HD), dtype)
    pool = sds((n_pool, KV, page_tokens, HD), dtype)
    tables = sds((BATCH, pages_per_req), jnp.int32)
    pos = sds((BATCH,), jnp.int32)
    fn = lambda q, k, v, t, p: kops.paged_attention(q, k, v, t, p,
                                                    interpret=False)
    compiled = _compile(fn, q, pool, pool, tables, pos)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("seq", [256, 512])
def test_flash_kernel_compiles_for_v5e(one_chip, seq):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    q = sds((1, seq, KV, GROUP, HD))
    kv = sds((1, seq, KV, HD))
    fn = lambda q, k, v: kops.flash_attention(q, k, v, causal=True,
                                              interpret=False)
    _compile(fn, q, kv, kv)
