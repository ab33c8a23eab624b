"""The benchmark's own arithmetic: chip peaks, FLOPs and bytes of a step.

Kept apart from the program on purpose, so that a change to the program
cannot change how it is judged.  The FLOP counts follow the analytic model
that ``repro.launch.roofline.fwd_flops_per_token`` used when this benchmark
was written (dense GQA decoder, SwiGLU MLP, LM head over the padded
vocabulary); they read the sizes from a configuration file of ``configs/``,
never from the program's config objects.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    device_kind: str
    bf16_flops: float          # FLOP/s
    hbm_bytes: int
    hbm_bw: float              # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        device_kind="TPU v5 lite", bf16_flops=197e12, hbm_bytes=16 * 2 ** 30,
        hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The row of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, as a configuration file gives them."""
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
        kv = self.kv_bytes_per_token()
        rows = len(contexts)
        live = sum(max(0, int(c) - 1) for c in contexts)
        return self.decode_weight_bytes(rows) + live * kv + rows * kv

    def decode_least_time(self, contexts, peaks: Peaks) -> tuple[float, str]:
        """(seconds, which bound binds) of one decode step at the roofline."""
        t_flops = self.decode_flops(contexts) / peaks.bf16_flops
        t_bytes = self.decode_bytes(contexts) / peaks.hbm_bw
        return (t_bytes, "memory") if t_bytes >= t_flops else \
            (t_flops, "compute")
