"""Dense GQA decoders: Qwen2 and Mistral as published.

Pre-norm RMSNorm blocks, rotary position embedding on the first and second
halves of each head, grouped-query attention with a causal mask, a SwiGLU
MLP, and an LM head that is the embedding table when the configuration
ties them; optional biases on q, k and v.  The program serves them as
``repro.models.Transformer`` with the one-kind pattern ``("attn",)``.

Weights: every matrix is normal with standard deviation 1/sqrt(its
contraction width), the tables ``TABLE_STD``, the QKV biases ``BIAS_STD``,
the norm weights 1.  At these scales a random 24-layer stack is not
chaotic, so rounding in the program moves a logit by about its own size and
no more, and a logit comparison with a tight limit is possible.  Each leaf
is drawn from the seed's key folded with the leaf's id and then its layer.

The yardstick's FLOP counts follow the analytic model that
``repro.launch.roofline.fwd_flops_per_token`` used when this benchmark was
written (SwiGLU MLP, LM head over the padded vocabulary); a decode step's
least bytes are the weights once, each row's live KV once and the new KV.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import weights as wlib
from reference import ein, fp8, ident, rms_norm, rope
from yardstick import Peaks

# leaf -> stable id (folded into the key: never renumber, only append)
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
                "mlp_norm", "w_gate", "w_up", "w_down")
TABLES = {"embed": 100, "lm_head": 101}
TABLE_STD = 0.02
BIAS_STD = 0.1


# -- yardstick ----------------------------------------------------------------

@dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, as a configuration file gives them,
    and the benchmark's arithmetic on them."""
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    tied: bool
    qkv_bias: bool
    bytes_per_param: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        d, nh = c["hidden_size"], c["num_attention_heads"]
        v = c["vocab_size"]
        return cls(layers=c["num_hidden_layers"], d_model=d, n_heads=nh,
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or d // nh,
                   d_ff=c["intermediate_size"], vocab=v,
                   padded_vocab=-(-v // 256) * 256,
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(c.get("qkv_bias", False)))

    # -- parameters -------------------------------------------------------------
    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        if self.qkv_bias:
            attn += hd * (self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff + 2 * d

    def table_params(self) -> int:
        return self.padded_vocab * self.d_model

    def params(self) -> int:
        tables = self.table_params() * (1 if self.tied else 2)
        return self.layers * self.layer_params() + tables + self.d_model

    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.n_kv_heads * self.head_dim * \
            self.bytes_per_param

    def kv_bytes(self, contexts) -> int:
        """KV bytes that running requests of ``contexts`` tokens each hold:
        every layer keeps every token."""
        return sum(int(c) for c in contexts) * self.kv_bytes_per_token()

    # -- FLOPs --------------------------------------------------------------------
    def flops_per_token(self, context: float) -> float:
        """Forward FLOPs of one token that attends to ``context`` positions."""
        d, hd, nh, kv = self.d_model, self.head_dim, self.n_heads, \
            self.n_kv_heads
        proj = 2.0 * (d * nh * hd + 2 * d * kv * hd + nh * hd * d)
        scores = 4.0 * nh * hd * context
        mlp = 3 * 2.0 * d * self.d_ff
        return self.layers * (proj + scores + mlp) + \
            2.0 * d * self.padded_vocab

    def prefill_flops(self, prompt_len: int) -> float:
        """Useful FLOPs of a causal prefill of ``prompt_len`` tokens: token t
        attends to t + 1 positions.  The LM head runs once, on the last
        token, as the program's prefill returns only its logits."""
        n = prompt_len
        head = 2.0 * self.d_model * self.padded_vocab
        body = self.flops_per_token(0.0) - head
        ctx_sum = n * (n + 1) / 2.0
        return n * body + 4.0 * self.n_heads * self.head_dim * \
            self.layers * ctx_sum + head

    def decode_flops(self, contexts) -> float:
        """One decode step over rows whose new token attends to ``contexts``
        positions each (the row's cached tokens plus itself)."""
        return sum(self.flops_per_token(c) for c in contexts)

    # -- bytes ---------------------------------------------------------------------
    def decode_weight_bytes(self, rows: int) -> int:
        """Weights a decode step has to read once: every layer, the final
        norm, the LM head in full, and the embedding rows of the step's
        tokens (the whole table when it is also the head)."""
        b = self.bytes_per_param
        weights = (self.layers * self.layer_params() + self.d_model) * b
        weights += self.table_params() * b
        if not self.tied:
            weights += rows * self.d_model * b
        return weights

    def decode_bytes(self, contexts) -> int:
        """Least HBM traffic of one decode step: weights once, each row's
        live KV read once, and the new token's KV written."""
        rows = len(contexts)
        live = self.kv_bytes(max(0, int(c) - 1) for c in contexts)
        return self.decode_weight_bytes(rows) + live + \
            rows * self.kv_bytes_per_token()

    def decode_least_time(self, step, peaks: Peaks) -> tuple[float, str]:
        """(seconds, which bound binds) of one decode ``step`` (its
        ``contexts``) at the roofline."""
        t_flops = self.decode_flops(step.contexts) / peaks.bf16_flops
        t_bytes = self.decode_bytes(step.contexts) / peaks.hbm_bw
        return (t_bytes, "memory") if t_bytes >= t_flops else \
            (t_flops, "compute")


def yardstick(cfg_file: dict) -> Dims:
    return Dims.from_config(cfg_file)


# -- the program --------------------------------------------------------------

def program_want(c: dict) -> dict:
    """The program config's fields and the values the file states."""
    return {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "resolved_head_dim": Dims.from_config(c).head_dim,
            "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"],
            "qkv_bias": c.get("qkv_bias", False), "dtype": c["torch_dtype"],
            "block_pattern": ("attn",), "tail_pattern": (), "act": "swiglu",
            "norm": "rmsnorm", "n_experts": 0}


def check_against(cfg, want: dict) -> None:
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")


def check_program(cfg, cfg_file: dict) -> None:
    """The program must run the sizes the file states (the reference reads
    the file, so a drift between the two would compare different
    models)."""
    check_against(cfg, program_want(cfg_file))


# -- weights ------------------------------------------------------------------

def layer_leaf_shapes(dims: Dims) -> dict:
    """name -> (shape of one layer's leaf, std, or None for a ones leaf)."""
    d, nh, kv, hd, ff = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                         dims.head_dim, dims.d_ff)
    out = {
        "attn_norm": ((d,), None),
        "wq": ((d, nh, hd), 1 / math.sqrt(d)),
        "wk": ((d, kv, hd), 1 / math.sqrt(d)),
        "wv": ((d, kv, hd), 1 / math.sqrt(d)),
        "wo": ((nh, hd, d), 1 / math.sqrt(nh * hd)),
        "mlp_norm": ((d,), None),
        "w_gate": ((d, ff), 1 / math.sqrt(d)),
        "w_up": ((d, ff), 1 / math.sqrt(d)),
        "w_down": ((ff, d), 1 / math.sqrt(ff)),
    }
    if dims.qkv_bias:
        out["bq"] = ((nh, hd), "bias")
        out["bk"] = ((kv, hd), "bias")
        out["bv"] = ((kv, hd), "bias")
    return {k: out[k] for k in LAYER_LEAVES if k in out}


def layer_leaf(seed_k, name: str, layer, dims: Dims, dtype=jnp.bfloat16):
    """One layer's leaf ``name`` (``layer`` may be traced)."""
    shape, std = layer_leaf_shapes(dims)[name]
    if std == "bias":
        std = BIAS_STD
    key = jax.random.fold_in(
        jax.random.fold_in(seed_k, LAYER_LEAVES.index(name)), layer)
    return wlib.draw(key, shape, std, dtype)


def layer_weights(k, layer, dims: Dims, dtype=jnp.bfloat16) -> dict:
    """Every leaf of one layer, as the served dtype gives them."""
    return {n: layer_leaf(k, n, layer, dims, dtype)
            for n in layer_leaf_shapes(dims)}


def table(k, name: str, dims: Dims, dtype=jnp.bfloat16):
    """The embedding (or the untied head) over the padded vocabulary."""
    key = jax.random.fold_in(k, TABLES[name])
    return wlib.draw(key, (dims.padded_vocab, dims.d_model), TABLE_STD,
                     dtype)


def canonical(k, dims: Dims, dtype=jnp.bfloat16) -> dict:
    """The whole model in the benchmark's layout, layers stacked on axis 0,
    from the key ``k`` of ``weights.seed_key``.  Jitted with ``k`` as an
    argument, each leaf is drawn, scaled and cast in one fused program."""
    layers = {}
    for name in layer_leaf_shapes(dims):
        layers[name] = jax.vmap(
            lambda l, n=name: layer_leaf(k, n, l, dims, dtype))(
                jnp.arange(dims.layers))
    out = {"embed": table(k, "embed", dims, dtype),
           "final_norm": jnp.ones((dims.d_model,), dtype),
           "layers": layers}
    if not dims.tied:
        out["lm_head"] = table(k, "lm_head", dims, dtype)
    return out


def to_program(c: dict) -> dict:
    """The benchmark's layout -> the tree ``repro.models.Transformer`` reads
    for a one-kind ``("attn",)`` pattern.  Its RMS norms scale by
    ``1 + scale``, so a norm weight w is handed over as w - 1."""
    L = c["layers"]
    attn = {"norm": {"scale": L["attn_norm"] - 1},
            "wq": L["wq"], "wk": L["wk"], "wv": L["wv"], "wo": L["wo"]}
    for b in ("bq", "bk", "bv"):
        if b in L:
            attn[b] = L[b]
    out = {"embed": c["embed"],
           "final_norm": {"scale": c["final_norm"] - 1},
           "pattern": {"0": {
               "attn": attn,
               "mlp_norm": {"scale": L["mlp_norm"] - 1},
               "mlp": {"w_gate": L["w_gate"], "w_up": L["w_up"],
                       "w_down": L["w_down"]}}}}
    if "lm_head" in c:
        out["lm_head"] = c["lm_head"]
    return out


def program_params(seed: int, cfg_file: dict, model):
    """The program's parameters, made on the device in one jitted call."""
    dims = Dims.from_config(cfg_file)
    dtype = jnp.dtype(cfg_file["torch_dtype"])
    return wlib.on_device(lambda k: to_program(canonical(k, dims, dtype)),
                          seed, jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))


# -- reference ----------------------------------------------------------------

def layer(x, w, dims: Dims, theta: float, eps: float, q=ident):
    """One decoder layer over x: (B, S, d) float32."""
    s = x.shape[1]
    pos = jnp.arange(s)
    h = rms_norm(x, w["attn_norm"], eps)
    qh = ein("bsd,dnh->bsnh", h, w["wq"], q)
    kh = ein("bsd,dnh->bsnh", h, w["wk"], q)
    vh = ein("bsd,dnh->bsnh", h, w["wv"], q)
    if dims.qkv_bias:
        qh, kh, vh = qh + w["bq"], kh + w["bk"], vh + w["bv"]
    qh, kh = rope(qh, pos, theta), rope(kh, pos, theta)
    g = dims.n_heads // dims.n_kv_heads
    kh = jnp.repeat(kh, g, axis=2)          # q head i reads kv head i // g
    vh = jnp.repeat(vh, g, axis=2)
    scores = ein("bqnh,bknh->bnqk", qh, kh, q) / np.sqrt(dims.head_dim)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = ein("bnqk,bknh->bqnh", p, vh, q)
    x = x + ein("bsnh,nhd->bsd", ctx, w["wo"], q)
    h = rms_norm(x, w["mlp_norm"], eps)
    a = jax.nn.silu(ein("bsd,df->bsf", h, w["w_gate"], q)) * \
        ein("bsd,df->bsf", h, w["w_up"], q)
    return x + ein("bsf,fd->bsd", a, w["w_down"], q)


@dataclass
class Reference:
    """Teacher-forced final hidden states of whole sequences, layer by
    layer, and the head to read logits with.

    ``dims``, ``theta`` and ``eps`` are the configuration file's; ``seed``
    the run's.  Sequences are padded to ``seq_len`` and run ``rows`` at a
    time, so the programs compile once per configuration."""
    dims: Dims
    theta: float
    eps: float
    seed: int
    seq_len: int
    rows: int = 2
    quant: str = "none"

    def __post_init__(self):
        self.q = fp8 if self.quant == "fp8" else ident
        dims, theta, eps, q = self.dims, self.theta, self.eps, self.q
        self.vocab = dims.vocab
        self._key = wlib.seed_key(self.seed)
        self._layer_w = jax.jit(lambda k, l: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            layer_weights(k, l, dims)))
        self._layer = jax.jit(
            lambda x, w: layer(x, w, dims, theta, eps, q))

    def _table(self, name):
        return table(self._key, name, self.dims).astype(jnp.float32)

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


def reference(cfg_file: dict, seed: int, quant: str = "none") -> Reference:
    """The float32 reference of ``cfg_file`` for ``seed``; ``quant="fp8"``
    is its control."""
    return Reference(dims=Dims.from_config(cfg_file),
                     theta=float(cfg_file["rope_theta"]),
                     eps=float(cfg_file["rms_norm_eps"]), seed=seed,
                     seq_len=cfg_file["engine"]["max_len"],
                     rows=cfg_file["check"]["rows"], quant=quant)
