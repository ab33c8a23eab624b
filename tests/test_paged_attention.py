"""Differential oracle for the Pallas paged-attention decode kernel.

The kernel consumes page-table indirection directly (scalar-prefetch
BlockSpec index_maps), so its failure modes are silent layout bugs: a wrong
page fetched, a partial last page unmasked, a padded table entry leaking into
the softmax.  Every test here is therefore differential — the kernel must
match BOTH independent implementations to tight tolerance:

  * ``ref_paged_attention`` — pure-jnp gather-then-softmax over the same
    page table (independent of the Pallas pipeline);
  * the contiguous path — pages gathered into a contiguous cache and run
    through ``attend_decode`` (the gather-execution baseline the paged
    engine replaces).

Cases sweep ragged per-row positions, fragmented non-monotonic page tables,
partial last pages, zero-padded table tails, the runner bucket ladder
B in {1, 2, 4, 8}, and both f32 and bf16.  Runs on CPU via interpret mode
(conftest sets REPRO_PALLAS_INTERPRET=1)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.ref import ref_paged_attention
from repro.models.attention import attend_decode, attend_paged_decode

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _case(seed, b, kv, g, hd, pt, maxp, *, dtype=jnp.float32,
          positions=None, fragmented=True):
    """A random paged-decode problem with the live-engine invariants:
    per-row pages disjoint, in-bounds, fragmented (non-monotonic) when
    asked, table tail zero-padded exactly like the engine's rows."""
    rng = np.random.default_rng(seed)
    n_pool = b * maxp + 3                   # a few never-referenced pages
    if positions is None:
        positions = rng.integers(0, maxp * pt, size=b)
    positions = np.asarray(positions, np.int32)
    order = rng.permutation(n_pool) if fragmented else np.arange(n_pool)
    tables = np.zeros((b, maxp), np.int32)
    used = 0
    for i in range(b):
        need = math.ceil((int(positions[i]) + 1) / pt)
        tables[i, :need] = order[used:used + need]
        used += need
    q = jnp.asarray(rng.standard_normal((b, kv, g, hd)), dtype)
    k_pages = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)), dtype)
    v_pages = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)), dtype)
    return q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(positions)


def _contiguous(q, k_pages, v_pages, tables, positions):
    """Gather-execution baseline: pages copied into a contiguous cache, then
    the engine's contiguous decode attention."""
    b, kv, g, hd = q.shape
    pt = k_pages.shape[2]
    maxp = tables.shape[1]

    def gather(pages):              # (B,maxp,kv,pt,hd) -> (B,maxp*pt,kv,hd)
        return pages[tables].transpose(0, 1, 3, 2, 4).reshape(
            b, maxp * pt, kv, hd)

    return attend_decode(q[:, None], gather(k_pages), gather(v_pages),
                         positions)[:, 0]


def _check(q, k_pages, v_pages, tables, positions, tol):
    out = paged_attention_decode(q, k_pages, v_pages, tables, positions,
                                 interpret=True)
    ref = ref_paged_attention(q, k_pages, v_pages, tables, positions)
    ctg = _contiguous(q, k_pages, v_pages, tables, positions)
    assert out.shape == q.shape and out.dtype == q.dtype
    err_ref = float(jnp.abs(out.astype(jnp.float32) -
                            ref.astype(jnp.float32)).max())
    err_ctg = float(jnp.abs(out.astype(jnp.float32) -
                            ctg.astype(jnp.float32)).max())
    assert err_ref < tol, f"kernel vs ref: {err_ref}"
    assert err_ctg < tol, f"kernel vs contiguous: {err_ctg}"


# ---------------------------------------------------------------------------
# deterministic sweep: bucket ladder x dtype
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b", [1, 2, 4, 8])
def test_kernel_matches_ref_and_contiguous(b, dtype):
    case = _case(seed=17 * b, b=b, kv=2, g=2, hd=32, pt=8, maxp=3,
                 dtype=dtype)
    _check(*case, tol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_and_mha_shapes(dtype):
    # single kv head with wide group, and group=1 (MHA-as-GQA degenerate)
    _check(*_case(seed=3, b=4, kv=1, g=4, hd=32, pt=8, maxp=2, dtype=dtype),
           tol=TOL[dtype])
    _check(*_case(seed=4, b=4, kv=3, g=1, hd=16, pt=4, maxp=4, dtype=dtype),
           tol=TOL[dtype])


def test_partial_and_boundary_positions():
    """Positions straddling page boundaries: first token, exactly one full
    page, first token of the next page, and the full table."""
    pt, maxp = 8, 3
    for pos in (0, pt - 1, pt, 2 * pt - 1, maxp * pt - 1):
        case = _case(seed=100 + pos, b=4, kv=2, g=2, hd=32, pt=pt, maxp=maxp,
                     positions=[pos, 0, maxp * pt - 1, pos])
        _check(*case, tol=TOL[jnp.float32])


def test_table_indirection_is_honored():
    """Relabeling the pool through a permutation (and remapping the tables
    through its inverse) must not change the output — proves the kernel
    reads pages through the table, not by position."""
    q, k_pages, v_pages, tables, positions = _case(
        seed=9, b=4, kv=2, g=2, hd=32, pt=8, maxp=3)
    rng = np.random.default_rng(99)
    n_pool = k_pages.shape[0]
    perm = rng.permutation(n_pool)
    inv = np.empty(n_pool, np.int64)
    inv[perm] = np.arange(n_pool)
    out = paged_attention_decode(q, k_pages, v_pages, tables, positions,
                                 interpret=True)
    out2 = paged_attention_decode(q, k_pages[inv], v_pages[inv],
                                  jnp.asarray(perm, jnp.int32)[tables],
                                  positions, interpret=True)
    assert float(jnp.abs(out - out2).max()) == 0.0


def test_padded_table_tail_is_inert():
    """Zero-padded table entries (the engine's short rows) alias page 0 for
    every row — corrupting page 0 beyond any row's position must not change
    anything, corrupting it inside a row's range must."""
    q, k_pages, v_pages, tables, positions = _case(
        seed=21, b=3, kv=2, g=2, hd=32, pt=8, maxp=4,
        positions=[5, 11, 20])            # rows use 1, 2, 3 of 4 pages
    out = paged_attention_decode(q, k_pages, v_pages, tables, positions,
                                 interpret=True)
    poisoned = k_pages.at[jnp.asarray(tables)[0, 0]].set(0.0)
    changed = paged_attention_decode(q, poisoned, v_pages, tables, positions,
                                     interpret=True)
    assert float(jnp.abs(out[0] - changed[0]).max()) > 0  # in-range page read
    # rows 1 and 2 never reference row 0's page: untouched
    assert float(jnp.abs(out[1:] - changed[1:]).max()) == 0.0


def test_models_layer_impl_parity():
    """attend_paged_decode must agree between impl='pallas' and impl='ref'
    — the switch the engine exposes via RunOpts.paged_attn_impl."""
    q, k_pages, v_pages, tables, positions = _case(
        seed=31, b=4, kv=2, g=2, hd=32, pt=8, maxp=3)
    q5 = q[:, None]                                     # (B,1,kv,g,hd)
    a = attend_paged_decode(q5, k_pages, v_pages, tables, positions,
                            impl="pallas")
    b_ = attend_paged_decode(q5, k_pages, v_pages, tables, positions,
                             impl="ref")
    assert a.shape == q5.shape
    assert float(jnp.abs(a - b_).max()) < TOL[jnp.float32]


def test_kernel_is_jittable():
    """The serving hot path traces the kernel inside the runner executables;
    the wrapper must trace cleanly with tables/positions as device args."""
    case = _case(seed=5, b=2, kv=2, g=2, hd=32, pt=8, maxp=2)
    fn = jax.jit(lambda *a: paged_attention_decode(*a, interpret=True))
    eager = paged_attention_decode(*case, interpret=True)
    assert float(jnp.abs(fn(*case) - eager).max()) < 1e-6


# ---------------------------------------------------------------------------
# property: any ragged/fragmented batch agrees with both oracles
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(data=st.data())
    def test_property_ragged_fragmented_batches(data):
        b = data.draw(st.sampled_from([1, 2, 3, 4, 8]), label="batch")
        pt = data.draw(st.sampled_from([4, 8]), label="page_tokens")
        maxp = data.draw(st.integers(1, 4), label="pages_per_req")
        kv = data.draw(st.sampled_from([1, 2]), label="kv_heads")
        g = data.draw(st.sampled_from([1, 2, 4]), label="group")
        dtype = data.draw(st.sampled_from([jnp.float32, jnp.bfloat16]),
                          label="dtype")
        positions = data.draw(
            st.lists(st.integers(0, maxp * pt - 1),
                     min_size=b, max_size=b), label="positions")
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        case = _case(seed=seed, b=b, kv=kv, g=g, hd=16, pt=pt, maxp=maxp,
                     dtype=dtype, positions=positions)
        _check(*case, tol=TOL[dtype])
