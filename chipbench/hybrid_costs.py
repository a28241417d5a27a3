"""Operations and bytes of a Mamba-2 / attention hybrid (GraniteMoeHybrid
without experts), computed from shapes alone, as ``flops.py`` computes them
for a dense decoder: what the algorithm needs, causal pairs only, the head
at the real vocabulary.
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.flops import BF16, causal_pairs

F32 = 4  # bytes


@dataclass(frozen=True)
class Hybrid:
    """The sizes of a GraniteMoeHybrid ``config.json`` with no experts."""
    layers: int
    attn_layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    m_heads: int
    m_head_dim: int
    m_state: int
    m_groups: int
    m_conv: int
    chunk: int

    @classmethod
    def from_config(cls, c: dict) -> "Hybrid":
        return cls(layers=c["num_hidden_layers"],
                   attn_layers=list(c["layer_types"]).count("attention"),
                   hidden=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
                   ffn=c.get("shared_intermediate_size") or c["intermediate_size"],
                   vocab=c["vocab_size"], m_heads=c["mamba_n_heads"],
                   m_head_dim=c["mamba_d_head"], m_state=c["mamba_d_state"],
                   m_groups=c["mamba_n_groups"], m_conv=c["mamba_d_conv"],
                   chunk=c["mamba_chunk_size"])

    @property
    def mamba_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.m_groups * self.m_state

    @property
    def attn_matmul_params(self) -> int:
        d, h, kv, dh = self.hidden, self.heads, self.kv_heads, self.head_dim
        return d * h * dh + 2 * d * kv * dh + h * dh * d

    @property
    def mamba_matmul_params(self) -> int:
        return self.hidden * (self.inner + self.conv_channels + self.m_heads) \
            + self.inner * self.hidden

    @property
    def body_matmul_params(self) -> int:
        return (self.attn_layers * self.attn_matmul_params
                + self.mamba_layers * self.mamba_matmul_params
                + self.layers * 3 * self.hidden * self.ffn)

    @property
    def params(self) -> int:
        """Every weight: matmuls, the tied embedding, norms, conv taps and
        bias, dt_bias, A_log and D, the gated norm."""
        per_mamba = (self.m_conv + 1) * self.conv_channels + 3 * self.m_heads + self.inner
        return (self.body_matmul_params + self.mamba_layers * per_mamba
                + (2 * self.layers + 1) * self.hidden + -(-self.vocab // 256) * 256 * self.hidden)


def ssd_fwd_cost(batch: int, seq: int, heads: int, head_dim: int, groups: int,
                 state: int, chunk: int) -> tuple[int, int]:
    """(FLOPs, HBM bytes) of one SSD scan call over ``seq`` positions (a
    multiple of ``chunk``). Per chunk: each group's C Bᵀ scores at the
    causal pairs, and per head the scores' product with x, the state's
    contribution to y and the state's update. Bytes: x, dt, cumulative dt A,
    B, C and the initial state read once; y and the final state written once."""
    pairs = chunk * (chunk + 1) // 2
    per_chunk = (groups * 2 * pairs * state
                 + heads * (2 * pairs * head_dim + 4 * chunk * head_dim * state))
    flops = batch * (seq // chunk) * per_chunk
    moved = (2 * batch * seq * heads * head_dim * BF16  # x, y
             + 2 * batch * heads * seq * F32  # dt, cumulative dt A
             + 2 * batch * seq * groups * state * BF16  # B, C
             + 2 * batch * heads * head_dim * state * F32)  # initial, final state
    return flops, moved


def prefill_flops(m: Hybrid, batch: int, prompt: int) -> int:
    """Prefill of ``batch`` prompts: the body's matmuls at every position,
    the head at the last, causal attention of the attention layers, and the
    SSD scan of the Mamba-2 layers over the prompt in whole chunks."""
    ssd, _ = ssd_fwd_cost(batch, -(-prompt // m.chunk) * m.chunk, m.m_heads,
                          m.m_head_dim, m.m_groups, m.m_state, m.chunk)
    return (2 * m.body_matmul_params * batch * prompt
            + 2 * m.hidden * m.vocab * batch
            + m.attn_layers * 4 * m.heads * m.head_dim * causal_pairs(batch, prompt)
            + m.mamba_layers * ssd)


def decode_step_bytes(m: Hybrid, batch: int, pos: int) -> int:
    """HBM bytes one decode step must move at position ``pos`` (the new
    token's): every bf16 weight once, the keys and values of positions
    0..pos of the attention layers, and the Mamba-2 layers' fp32 states and
    bf16 conv tails, each read and written once."""
    weights = m.params * BF16
    kv = m.attn_layers * 2 * batch * (pos + 1) * m.kv_heads * m.head_dim * BF16
    state = batch * m.m_heads * m.m_head_dim * m.m_state * F32
    tail = batch * (m.m_conv - 1) * m.conv_channels * BF16
    return weights + kv + m.mamba_layers * 2 * (state + tail)
