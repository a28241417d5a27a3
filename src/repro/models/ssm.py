"""Attention-free mixers: RWKV6 (Finch) time-mix and Mamba selective scan,
and the single-token update of Mamba-2's SSD state.

RWKV6 and Mamba-1 provide three execution paths:
  - ``*_naive``  : step-by-step ``lax.scan`` over time — the oracle, used for
                   tests and as the decode single-step math,
  - ``*_chunked``: chunk-parallel formulation used by train/prefill (pure
                   JAX; the Pallas kernels in ``repro.kernels`` mirror this
                   blocking with VMEM tiles),
  - ``*_step``   : single-token decode update.

Mamba-2's prefill and training run the SSD scan of ``kernels/ssd.py`` on
TPU and its chunked jnp form ``kernels.ref.ssd_chunked`` elsewhere;
:func:`ssd_step` is its decode update.

Numerics: RWKV6's per-channel log-decay is clamped to ``-MAX_DECAY`` per step
and chunks are kept short (16) so ``exp(±Σ log w)`` stays inside fp32 range —
the clamp is applied identically in every path, so they agree bitwise-ish
(allclose at fp32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MAX_DECAY = 4.0  # clamp on exp(w_raw): decay factor >= exp(-4) per step
RWKV_CHUNK = 16
MAMBA_CHUNK = 256


# ====================================================================== RWKV6
def rwkv6_decay(w_raw: jax.Array) -> jax.Array:
    """Raw decay projection -> log decay in [-MAX_DECAY, 0)."""
    return -jnp.minimum(jnp.exp(w_raw.astype(jnp.float32)), MAX_DECAY)


def rwkv6_naive(
    r: jax.Array,  # [B, S, H, Dh]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # [B, S, H, Dh] log decay (negative)
    u: jax.Array,  # [H, Dh] bonus
    state0: jax.Array | None = None,  # [B, H, Dh, Dh]
) -> tuple[jax.Array, jax.Array]:
    """Oracle: out_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ); S_t = diag(w_t) S_{t-1} + k_t v_tᵀ."""
    b, s, h, dh = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, dh, dh), jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, lw_t = inp  # [B, H, Dh]
        kv = k_t[..., :, None] * v_t[..., None, :]  # [B,H,Dh,Dh]
        out = jnp.einsum("bhd,bhde->bhe", r_t, S + u[None, :, :, None] * kv)
        S = jnp.exp(lw_t)[..., :, None] * S + kv
        return S, out

    seq = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, logw)
    )
    state, out = jax.lax.scan(step, state0, seq)
    return jnp.moveaxis(out, 0, 1).astype(r.dtype), state


def rwkv6_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, state0: jax.Array | None = None, chunk: int = RWKV_CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel WKV: intra-chunk via masked score matrix, cross-chunk
    via the carried state. Matches :func:`rwkv6_naive` to fp32 tolerance."""
    b, s, h, dh = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    if s % chunk != 0:  # fall back (decode tails etc.)
        return rwkv6_naive(r, k, v, logw, u, state0)
    n = s // chunk

    def to_chunks(t):
        return jnp.moveaxis(
            t.astype(jnp.float32).reshape(b, n, chunk, h, dh), 1, 0
        )  # [n, B, L, H, Dh]

    rs, ks, vs, lws = map(to_chunks, (r, k, v, logw))

    def chunk_fn(S_in, inp):
        r_c, k_c, v_c, lw_c = inp  # [B, L, H, Dh]
        la = jnp.cumsum(lw_c, axis=1)  # inclusive log-decay products
        q_ = r_c * jnp.exp(la - lw_c)  # r_t * A_{t-1}
        k_ = k_c * jnp.exp(-la)  # k_s / A_s
        scores = jnp.einsum("blhd,bmhd->bhlm", q_, k_)
        tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)  # s < t strictly
        diag = jnp.einsum("blhd,hd,blhd->bhl", r_c, u.astype(jnp.float32), k_c)
        scores = scores * tri[None, None]
        scores = scores + jnp.einsum("bhl,lm->bhlm", diag, jnp.eye(chunk, dtype=jnp.float32))
        intra = jnp.einsum("bhlm,bmhd->blhd", scores, v_c)
        cross = jnp.einsum("blhd,bhde->blhe", q_, S_in)
        out = intra + cross
        la_last = la[:, -1]  # [B, H, Dh]
        kd = k_c * jnp.exp(la_last[:, None] - la)
        S_out = S_in * jnp.exp(la_last)[..., None] + jnp.einsum(
            "blhd,blhe->bhde", kd, v_c
        )
        return S_out, out

    state, outs = jax.lax.scan(jax.checkpoint(chunk_fn), state0, (rs, ks, vs, lws))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dh)
    return out.astype(r.dtype), state


def rwkv6_step(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, state: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Single-token decode. Inputs [B, H, Dh]; state [B, H, Dh, Dh]."""
    r, k, v, logw = (t.astype(jnp.float32) for t in (r, k, v, logw))
    kv = k[..., :, None] * v[..., None, :]
    out = jnp.einsum("bhd,bhde->bhe", r, state + u[None, :, :, None] * kv)
    new_state = jnp.exp(logw)[..., :, None] * state + kv
    return out, new_state


# ====================================================================== Mamba
def mamba_conv(
    x: jax.Array,  # [B, S, Di]
    conv_w: jax.Array,  # [Di, K]
    conv_b: jax.Array,  # [Di]
    conv_state: jax.Array | None = None,  # [B, K-1, Di] trailing context
) -> jax.Array:
    """Depthwise causal conv along time via K shifted adds."""
    k = conv_w.shape[-1]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], k - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, S+K-1, Di]
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i : i + x.shape[1], :] * conv_w[:, i][None, None, :]
    return out + conv_b[None, None, :]


def mamba_scan_naive(
    u: jax.Array,  # [B, S, Di]  (post-conv, post-silu input)
    dt: jax.Array,  # [B, S, Di]
    A: jax.Array,  # [Di, St]
    B_: jax.Array,  # [B, S, St]
    C_: jax.Array,  # [B, S, St]
    h0: jax.Array | None = None,  # [B, Di, St]
) -> tuple[jax.Array, jax.Array]:
    """Oracle selective scan: h_t = exp(dt A) h_{t-1} + dt·B_t·u_t; y_t = C_t·h_t."""
    b, s, di = u.shape
    st = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, di, st), jnp.float32)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        a = jnp.exp(dt_t[..., None] * A[None])  # [B, Di, St]
        h = a * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    seq = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (u, dt, B_, C_)
    )
    h, ys = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), h


def mamba_scan_chunked(
    u: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array, C_: jax.Array,
    h0: jax.Array | None = None, chunk: int = MAMBA_CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """Chunked scan: outer ``lax.scan`` over chunks (rematerialized), inner
    sequential scan within a chunk. Keeps backward-pass residuals at
    O(S/chunk · state) instead of O(S · state)."""
    b, s, di = u.shape
    if s % chunk != 0 or s <= chunk:
        return mamba_scan_naive(u, dt, A, B_, C_, h0)
    st = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, di, st), jnp.float32)
    n = s // chunk

    def to_chunks(t, width):
        return jnp.moveaxis(
            t.astype(jnp.float32).reshape(b, n, chunk, width), 1, 0
        )

    us, dts = to_chunks(u, di), to_chunks(dt, di)
    bs, cs = to_chunks(B_, st), to_chunks(C_, st)

    def chunk_fn(h, inp):
        u_c, dt_c, b_c, c_c = inp

        def step(hh, s_inp):
            u_t, dt_t, b_t, c_t = s_inp
            a = jnp.exp(dt_t[..., None] * A[None])
            hh = a * hh + (dt_t * u_t)[..., None] * b_t[:, None, :]
            y = jnp.einsum("bds,bs->bd", hh, c_t)
            return hh, y

        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (u_c, dt_c, b_c, c_c))
        h, ys = jax.lax.scan(step, h, seq)
        return h, jnp.moveaxis(ys, 0, 1)

    h, ys = jax.lax.scan(jax.checkpoint(chunk_fn), h0, (us, dts, bs, cs))
    out = jnp.moveaxis(ys, 0, 1).reshape(b, s, di)
    return out.astype(u.dtype), h


def mamba_step(
    u_t: jax.Array,  # [B, Di]
    dt_t: jax.Array,  # [B, Di]
    A: jax.Array,  # [Di, St]
    b_t: jax.Array,  # [B, St]
    c_t: jax.Array,  # [B, St]
    h: jax.Array,  # [B, Di, St]
) -> tuple[jax.Array, jax.Array]:
    u_t, dt_t, b_t, c_t = (t.astype(jnp.float32) for t in (u_t, dt_t, b_t, c_t))
    a = jnp.exp(dt_t[..., None] * A[None])
    h = a * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
    y = jnp.einsum("bds,bs->bd", h, c_t)
    return y.astype(u_t.dtype), h


def ssd_step(
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B, H] fp32, after softplus
    A: jax.Array,  # [H] fp32
    B_: jax.Array,  # [B, G, N]
    C_: jax.Array,  # [B, G, N]
    h: jax.Array,  # [B, H, P, N] fp32
) -> tuple[jax.Array, jax.Array]:
    """One Mamba-2 decode step: h = exp(dt A) h + dt x Bᵀ; y = h C, heads
    ``g * H/G`` to ``(g + 1) * H/G - 1`` on group ``g``. The state is read and
    written once, in fp32; y in x's dtype."""
    r = x.shape[1] // B_.shape[1]
    bh = jnp.repeat(B_.astype(jnp.float32), r, axis=1)[:, :, None, :]  # [B, H, 1, N]
    ch = jnp.repeat(C_.astype(jnp.float32), r, axis=1)[:, :, None, :]
    h = (jnp.exp(dt * A)[..., None, None] * h
         + (dt[..., None] * x.astype(jnp.float32))[..., None] * bh)
    return jnp.sum(h * ch, axis=-1).astype(x.dtype), h
