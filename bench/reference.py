"""What every family's float32 reference shares, and the comparison.

A family's reference (``families/<name>.py``) is written from the published
description of its architecture in ``jax.numpy`` at ``Precision.HIGHEST``
with the pieces here: the matrix product, RMSNorm, rotary position
embedding.  It imports nothing of the program: the sizes come from a
configuration file of ``configs/``, and the weights are drawn again from
the seed, one layer at a time, so that the reference of a 12B-class stage
fits on one chip after the program's state has been freed.

``quant="fp8"`` is the control: every matrix product takes both operands
through float8 e4m3 with one absmax scale per tensor, the step below the
bfloat16 the configurations state.  It has to fail the comparison.

``served_gaps`` is the comparison itself.  It reads a reference through
``final_hidden(seqs)``, ``head()``, ``vocab`` and ``q`` (the quantizer of
its matrix products) alone, so it serves every family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def ident(x):
    return x


def ein(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HI,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (B, S, H, hd); pos: (S,).  Rotates (x1, x2), the two halves."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv             # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gap_rows(h, table, rows, served, vocab, q):
    """Per row: (best logit, logit of ``served``, argmax) over real ids."""
    lg = ein("rd,vd->rv", jnp.take(h, rows, axis=0), table, q)[:, :vocab]
    best = lg.max(-1)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return best, got, jnp.argmax(lg, -1).astype(jnp.int32)


_gap_rows_ref = jax.jit(_gap_rows, static_argnames=("vocab", "q"))


def served_gaps(ref, requests: list, pad_rows: int, control=None) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over ``requests`` = [(prompt, served tokens)].

    Each request runs teacher forced: the reference sees the prompt and the
    served tokens, and at the position before each served token reads that
    token's logit against its best one.  With ``control`` (the fp8
    reference), also the widest gap of the token the control puts first at
    the same positions.  Rows are padded to ``pad_rows`` per request."""
    seqs = [np.concatenate([np.asarray(p), np.asarray(o[:-1], np.int32)])
            for p, o in requests]
    h_ref = ref.final_hidden(seqs)
    h_ctl = control.final_hidden(seqs) if control is not None else None
    table = ref.head()
    tctl = control.head() if control is not None else None
    vocab = ref.vocab
    gaps, ctl_gaps, n_tok, n_same = [], [], 0, 0
    for i, (p, o) in enumerate(requests):
        g = len(o)
        if g > pad_rows:
            raise ValueError(f"{g} served tokens > pad_rows {pad_rows}")
        rows = np.full((pad_rows,), len(p) - 1, np.int32)
        rows[:g] = np.arange(len(p) - 1, len(p) - 1 + g)
        served = np.zeros((pad_rows,), np.int32)
        served[:g] = o
        best, got, top = _gap_rows_ref(h_ref[i], table, jnp.asarray(rows),
                                       jnp.asarray(served), vocab=vocab,
                                       q=ident)
        best, got, top = (np.asarray(a)[:g] for a in (best, got, top))
        gaps.append(float(np.max(best - got)))
        n_tok += g
        n_same += int(np.sum(top == np.asarray(o)))
        if control is not None:
            _, _, ctop = _gap_rows_ref(h_ctl[i], tctl, jnp.asarray(rows),
                                       jnp.asarray(served), vocab=vocab,
                                       q=control.q)
            ctop = np.asarray(ctop)[:g]
            lg = _gap_rows_ref(h_ref[i], table, jnp.asarray(rows),
                               jnp.asarray(np.pad(ctop, (0, pad_rows - g))),
                               vocab=vocab, q=ident)
            cb, cg = (np.asarray(a)[:g] for a in lg[:2])
            ctl_gaps.append(float(np.max(cb - cg)))
    out = {"logit_gap_max": max(gaps), "tokens": n_tok,
           "same_as_reference": n_same, "requests": len(requests)}
    if control is not None:
        out["control_gap_max"] = max(ctl_gaps)
    return out
