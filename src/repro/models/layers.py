"""Shared neural building blocks (pure JAX, dtype-disciplined).

Norms and softmax statistics accumulate in fp32; matmuls run in the model
compute dtype (bf16 on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                  eps: float = 1e-5) -> jax.Array:
    """Mamba-2's gated norm: RMSNorm of y * silu(z), in fp32, over the last
    axis; returned in y's dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rmsnorm(g, scale, eps).astype(y.dtype)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    """SwiGLU FFN: (silu(x@w1) * (x@w3)) @ w2."""
    gate = jax.nn.silu(x @ w1)
    return (gate * (x @ w3)) @ w2


# ---------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the rotary half-dims: [head_dim // 2]."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, Dh]; positions: [B, S] (int)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions3: jax.Array, theta: float, sections: tuple[int, ...]
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are split into sections
    (temporal / height / width), each rotated by its own position stream.

    x: [B, S, H, Dh]; positions3: [3, B, S]; sum(sections) == Dh // 2.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)  # [half]
    # pick the position stream per frequency index
    section_ids = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # [half] in {0,1,2}
    pos = positions3[section_ids, :, :]  # [half, B, S]
    angles = jnp.einsum("hbs,h->bsh", pos.astype(jnp.float32), freqs)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- loss
def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Mean token cross-entropy. Uses a one-hot contraction so vocab-sharded
    logits never gather (see distributed/sharding.py)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
