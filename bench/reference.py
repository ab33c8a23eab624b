"""Plain float32 reference of a dense GQA decoder, and its fp8 control.

Written from the published description of the Qwen2 and Mistral decoders
(pre-norm RMSNorm blocks, rotary position embedding on the first and second
halves of each head, grouped-query attention with a causal mask, a SwiGLU
MLP, an LM head that is the embedding table when the configuration ties
them), in ``jax.numpy`` at ``Precision.HIGHEST``.  It imports nothing of the
program: the sizes come from a configuration file of ``configs/``, and the
weights are drawn again from the seed by ``weights.py``, one layer at a
time, so that the reference of a 12B-class stage fits on one chip after the
program's state has been freed.

``quant="fp8"`` is the control: every matrix product takes both operands
through float8 e4m3 with one absmax scale per tensor, the step below the
bfloat16 the configurations state.  It has to fail the comparison.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import weights as wlib
from yardstick import Dims

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ident(x):
    return x


def _ein(spec, a, b, q):
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


def layer(x, w, dims: Dims, theta: float, eps: float, q=_ident):
    """One decoder layer over x: (B, S, d) float32."""
    s = x.shape[1]
    pos = jnp.arange(s)
    h = rms_norm(x, w["attn_norm"], eps)
    qh = _ein("bsd,dnh->bsnh", h, w["wq"], q)
    kh = _ein("bsd,dnh->bsnh", h, w["wk"], q)
    vh = _ein("bsd,dnh->bsnh", h, w["wv"], q)
    if dims.qkv_bias:
        qh, kh, vh = qh + w["bq"], kh + w["bk"], vh + w["bv"]
    qh, kh = rope(qh, pos, theta), rope(kh, pos, theta)
    g = dims.n_heads // dims.n_kv_heads
    kh = jnp.repeat(kh, g, axis=2)          # q head i reads kv head i // g
    vh = jnp.repeat(vh, g, axis=2)
    scores = _ein("bqnh,bknh->bnqk", qh, kh, q) / np.sqrt(dims.head_dim)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = _ein("bnqk,bknh->bqnh", p, vh, q)
    x = x + _ein("bsnh,nhd->bsd", ctx, w["wo"], q)
    h = rms_norm(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_ein("bsd,df->bsf", h, w["w_gate"], q)) * \
        _ein("bsd,df->bsf", h, w["w_up"], q)
    return x + _ein("bsf,fd->bsd", a, w["w_down"], q)


@dataclass
class Reference:
    """Teacher-forced logits of whole sequences, layer by layer.

    ``dims``, ``theta`` and ``eps`` are the configuration file's; ``seed``
    the run's.  Sequences are padded to
    ``seq_len`` and run ``rows`` at a time, so the programs compile once
    per configuration."""
    dims: Dims
    theta: float
    eps: float
    seed: int
    seq_len: int
    rows: int = 2
    quant: str = "none"

    def __post_init__(self):
        q = _fp8 if self.quant == "fp8" else _ident
        dims, theta, eps = self.dims, self.theta, self.eps
        self._key = wlib.seed_key(self.seed)
        self._layer_w = jax.jit(lambda k, l: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            wlib.layer_weights(k, l, dims)))
        self._layer = jax.jit(lambda x, w: layer(x, w, dims, theta, eps, q))
        self._q = q

    def _table(self, name):
        return wlib.table(self._key, name, self.dims).astype(jnp.float32)

    def final_hidden(self, seqs: list) -> list:
        """Normed last hidden states, (seq_len, d) float32 per sequence."""
        n, S = len(seqs), self.seq_len
        rows = self.rows
        nb = -(-n // rows)
        toks = np.zeros((nb * rows, S), np.int32)
        for i, t in enumerate(seqs):
            if len(t) > S:
                raise ValueError(f"sequence of {len(t)} > seq_len {S}")
            toks[i, :len(t)] = t
        emb = self._table("embed")
        xs = [jnp.take(emb, jnp.asarray(toks[b * rows:(b + 1) * rows]),
                       axis=0) for b in range(nb)]
        del emb
        for li in range(self.dims.layers):
            w = self._layer_w(self._key, li)
            xs = [self._layer(x, w) for x in xs]
            del w
        fnorm = jnp.ones((self.dims.d_model,), jnp.float32)
        out = []
        for x in xs:
            x = rms_norm(x, fnorm, self.eps)
            out.extend(x[i] for i in range(x.shape[0]))
        return out[:n]

    def head(self):
        return self._table("embed" if self.dims.tied else "lm_head")


def _gap_rows(h, table, rows, served, vocab, q):
    """Per row: (best logit, logit of ``served``, argmax) over real ids."""
    lg = _ein("rd,vd->rv", jnp.take(h, rows, axis=0), table, q)[:, :vocab]
    best = lg.max(-1)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return best, got, jnp.argmax(lg, -1).astype(jnp.int32)


_gap_rows_ref = jax.jit(_gap_rows, static_argnames=("vocab", "q"))


def served_gaps(ref: Reference, requests: list, pad_rows: int,
                control: "Reference | None" = None) -> dict:
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
    vocab = ref.dims.vocab
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
                                       q=_ident)
        best, got, top = (np.asarray(a)[:g] for a in (best, got, top))
        gaps.append(float(np.max(best - got)))
        n_tok += g
        n_same += int(np.sum(top == np.asarray(o)))
        if control is not None:
            _, _, ctop = _gap_rows_ref(h_ctl[i], tctl, jnp.asarray(rows),
                                       jnp.asarray(served), vocab=vocab,
                                       q=control._q)
            ctop = np.asarray(ctop)[:g]
            lg = _gap_rows_ref(h_ref[i], table, jnp.asarray(rows),
                               jnp.asarray(np.pad(ctop, (0, pad_rows - g))),
                               vocab=vocab, q=_ident)
            cb, cg = (np.asarray(a)[:g] for a in lg[:2])
            ctl_gaps.append(float(np.max(cb - cg)))
    out = {"logit_gap_max": max(gaps), "tokens": n_tok,
           "same_as_reference": n_same, "requests": len(requests)}
    if control is not None:
        out["control_gap_max"] = max(ctl_gaps)
    return out
