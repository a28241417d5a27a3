"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, Mamba-2 and attention 9:1
(attention at position 5 of each 10-layer period, no position embedding),
GQA 32/8 at head_dim 64, SwiGLU d_ff=8192 on every layer, vocab=100352,
tied embeddings; Mamba-2 64 heads x 64, d_state 128, 1 group, conv 4, chunk
256; Granite's embedding, attention, residual and logits multipliers
[hf:ibm-granite/granite-4.0-h-micro config.json]."""
from .base import MambaConfig, ModelConfig

_GRANITE = dict(embedding_multiplier=12.0, attention_multiplier=0.015625,
                residual_multiplier=0.22, logits_scaling=8.0)


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro", family="hybrid",
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_head=64,
        d_ff=8192, vocab_size=100352, rope=False, rope_theta=1e4,
        mamba=MambaConfig(d_state=128, d_conv=4, expand=2, n_heads=64, d_head=64,
                          n_groups=1, chunk=256),
        attn_period=10, attn_offset=5, tie_embeddings=True, norm_eps=1e-5,
        **_GRANITE,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro-smoke", family="hybrid",
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=512, rope=False, rope_theta=1e4,
        mamba=MambaConfig(d_state=16, d_conv=4, expand=2, n_heads=4, d_head=32,
                          n_groups=1, chunk=16),
        attn_period=2, attn_offset=1, tie_embeddings=True, norm_eps=1e-5,
        **_GRANITE,
    )
