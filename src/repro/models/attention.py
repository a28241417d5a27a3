"""Attention: blockwise (flash-style) GQA with causal/sliding-window masking,
plus single-token decode attention against a KV cache.

The training/prefill path chunks queries and recomputes per-chunk under
``jax.checkpoint`` — O(S·chunk) live score memory instead of O(S²), which is
the flash-attention memory behaviour expressed in pure JAX (the Pallas TPU
kernel in ``repro.kernels.flash_attention`` implements the same math with
explicit VMEM tiling; ``use_pallas`` selects it on TPU backends).

GQA is computed in grouped form [B, KV, G, ...] so repeated K/V heads are
never materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _grouped(q: jax.Array, n_kv: int) -> jax.Array:
    """[B, S, H, Dh] -> [B, S, KV, G, Dh]."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _attn_chunk(
    q: jax.Array,  # [B, qc, KV, G, Dh]
    k: jax.Array,  # [B, Sk, KV, Dh]
    v: jax.Array,  # [B, Sk, KV, Dh]
    q_pos: jax.Array | None,  # [qc] global query positions (None = no mask)
    k_pos: jax.Array | None,  # [Sk]
    window: int | None,
    scale: float,
) -> jax.Array:
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if q_pos is not None:
        valid = k_pos[None, :] <= q_pos[:, None]  # causal
        if window is not None:
            valid &= k_pos[None, :] > (q_pos[:, None] - window)
        scores = jnp.where(valid[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def attention(
    q: jax.Array,  # [B, Sq, H, Dh]
    k: jax.Array,  # [B, Sk, KV, Dh]
    v: jax.Array,  # [B, Sk, KV, Dh]
    *,
    causal: bool = True,
    window: int | None = None,
    q_chunk: int = 1024,
    unroll_chunks: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Full-sequence attention, query-chunked when Sq > q_chunk; scores
    scaled by ``scale`` (Dh ** -0.5 where None).

    ``unroll_chunks`` replaces the chunk loop with a static python loop so
    XLA's cost analysis sees every chunk (used by the dry-run cost modules;
    the runtime default keeps the loop for O(1) HLO size)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    scale = d**-0.5 if scale is None else scale
    qg = _grouped(q, kv)
    q_pos = jnp.arange(sq, dtype=jnp.int32) if causal else None
    k_pos = jnp.arange(k.shape[1], dtype=jnp.int32) if causal else None
    if sq <= q_chunk or sq % q_chunk != 0:
        out = _attn_chunk(qg, k, v, q_pos, k_pos, window, scale)
        return out.reshape(b, sq, h, d)

    n_chunks = sq // q_chunk
    qs = qg.reshape(b, n_chunks, q_chunk, kv, h // kv, d)
    qs = jnp.moveaxis(qs, 1, 0)  # [n_chunks, B, qc, KV, G, Dh]
    pos = (
        q_pos.reshape(n_chunks, q_chunk)
        if q_pos is not None
        else jnp.zeros((n_chunks, q_chunk), jnp.int32)
    )

    @jax.checkpoint
    def one_chunk(args):
        q_c, pos_c = args
        return _attn_chunk(q_c, k, v, pos_c if causal else None, k_pos, window, scale)

    if unroll_chunks:
        outs = [one_chunk((qs[i], pos[i])) for i in range(n_chunks)]
        out = jnp.stack(outs, axis=0)
    else:
        out = jax.lax.map(one_chunk, (qs, pos))  # [n_chunks, B, qc, KV, G, Dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, d)
    return out


def decode_attention(
    q: jax.Array,  # [B, 1, H, Dh]
    k_cache: jax.Array,  # [B, S_cache, KV, Dh]
    v_cache: jax.Array,  # [B, S_cache, KV, Dh]
    pos: jax.Array,  # scalar int32: position of the new token
    k: jax.Array,  # [B, 1, KV, Dh]: the new token's key
    v: jax.Array,  # [B, 1, KV, Dh]: the new token's value
    *,
    scale: float | None = None,
) -> jax.Array:
    """One-token attention against the cache as it stood before this token,
    scores scaled by ``scale`` (Dh ** -0.5 where None).

    The cache's slots below ``pos`` and the new token's own ``k``, ``v``
    share one softmax; slot ``pos`` mod S_cache, which the new token takes,
    is left out. In a sliding-window ring that has wrapped, that slot holds
    the token the window drops (RoPE was applied at insert, so slot order is
    irrelevant to the math). Nothing here writes the cache:
    :func:`cache_insert` does."""
    b, _, h, d = q.shape
    kv = k_cache.shape[2]
    s = k_cache.shape[1]
    qg = _grouped(q, kv)  # [B, 1, KV, G, Dh]
    scale = d**-0.5 if scale is None else scale

    def scores_of(keys):
        return jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, keys, preferred_element_type=jnp.float32
        ) * scale

    idx = jnp.arange(s, dtype=jnp.int32)
    valid = (idx < pos) & (idx != jnp.mod(pos, s))
    scores = jnp.where(valid[None, None, None, None, :], scores_of(k_cache), NEG_INF)
    own = scores_of(k)  # [B, KV, G, 1, 1]
    top = jnp.maximum(scores.max(axis=-1, keepdims=True), own)
    e, e_own = jnp.exp(scores - top), jnp.exp(own - top)
    total = e.sum(axis=-1, keepdims=True) + e_own
    out = jnp.einsum("bkgqs,bskd->bqkgd", (e / total).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bkgqs,bskd->bqkgd", (e_own / total).astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
    return out.astype(v_cache.dtype).reshape(b, 1, h, d)


def cache_insert(cache: jax.Array, row: jax.Array, layer, pos: jax.Array) -> jax.Array:
    """Write one layer's new token row ``row`` [B, 1, KV, Dh] into the stacked
    cache [n, B, S_cache, KV, Dh] at ``layer``, slot ``pos`` mod S_cache (ring
    semantics). A donated ``cache`` is updated in place."""
    slot = jnp.mod(pos, cache.shape[2])
    return jax.lax.dynamic_update_slice(
        cache, row[None].astype(cache.dtype), (layer, 0, slot, 0, 0))
