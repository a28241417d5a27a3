"""Operations and bytes that the work needs, computed from shapes alone.

These are the numerators of every utilization and roofline share the
benchmark reports. They count what the algorithm needs, not what a program
happens to do: causal attention counts the query-key pairs at or below the
diagonal, the vocabulary counts its real rows (not the padding), and a
training step counts forward plus backward with nothing recomputed.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2  # bytes


@dataclass(frozen=True)
class Dense:
    """A dense GQA decoder, in the sizes of a Hugging Face ``config.json``."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    qk_norm: bool = False

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim")
                   or c["hidden_size"] // c["num_attention_heads"],
                   ffn=c["intermediate_size"], vocab=c["vocab_size"],
                   qk_norm=bool(c.get("qk_norm", False)))

    @property
    def layer_matmul_params(self) -> int:
        d, h, kv, dh = self.hidden, self.heads, self.kv_heads, self.head_dim
        return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * self.ffn

    @property
    def body_matmul_params(self) -> int:
        return self.layers * self.layer_matmul_params

    @property
    def head_params(self) -> int:
        return self.hidden * self.vocab

    @property
    def norm_params(self) -> int:
        qk = 2 * self.head_dim if self.qk_norm else 0
        return self.layers * (2 * self.hidden + qk) + self.hidden


def causal_pairs(batch: int, seq: int) -> int:
    """Query-key pairs at or below the diagonal."""
    return batch * seq * (seq + 1) // 2


def attention_fwd_flops(m: Dense, batch: int, seq: int) -> int:
    """Scores and the weighted sum of values, all layers, forward only."""
    return m.layers * 4 * m.heads * m.head_dim * causal_pairs(batch, seq)


def train_step_flops(m: Dense, batch: int, seq: int) -> int:
    """Forward and backward of one step: 6 per matmul parameter per token
    (output head included) plus three times the causal attention forward."""
    tokens = batch * seq
    return (6 * (m.body_matmul_params + m.head_params) * tokens
            + 3 * attention_fwd_flops(m, batch, seq))


def prefill_flops(m: Dense, batch: int, prompt: int) -> int:
    """Prefill of ``batch`` prompts: the body over every position, the head
    at the last position only (that is all prefill returns)."""
    return (2 * m.body_matmul_params * batch * prompt
            + 2 * m.head_params * batch
            + attention_fwd_flops(m, batch, prompt))


def decode_step_bytes(m: Dense, batch: int, pos: int) -> int:
    """HBM bytes one decode step must read at position ``pos`` (the new
    token's): every bf16 weight once, the head's rows once, and the keys and
    values of positions 0..pos of every layer."""
    weights = (m.body_matmul_params + m.head_params + m.norm_params) * BF16
    kv = m.layers * 2 * batch * (pos + 1) * m.kv_heads * m.head_dim * BF16
    return weights + kv


def flash_fwd_cost(batch: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int) -> tuple[int, int]:
    """(FLOPs, HBM bytes) of one causal flash-attention forward call: q, k
    and v read once, the output written once."""
    flops = 4 * heads * head_dim * causal_pairs(batch, seq)
    moved = (2 * batch * heads * seq * head_dim
             + 2 * batch * kv_heads * seq * head_dim) * BF16
    return flops, moved


def roofline_seconds(flops: float, moved: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = moved / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
