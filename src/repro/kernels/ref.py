"""Pure-jnp oracles for every Pallas kernel (self-contained, no model deps).

These are the ground truth the kernel tests sweep against; they are also the
math-identical fallbacks the model uses on non-TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(
    q: jax.Array,  # [B, Sq, H, Dh]
    k: jax.Array,  # [B, Sk, KV, Dh]
    v: jax.Array,  # [B, Sk, KV, Dh]
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Direct softmax attention with GQA head repetition; scores scaled by
    ``scale`` (Dh ** -0.5 where None)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d**-0.5 if scale is None else scale)
    if causal:
        rows = jnp.arange(sq)[:, None]
        cols = jnp.arange(k.shape[1])[None, :]
        mask = cols <= rows
        if window is not None:
            mask &= cols > rows - window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def rwkv6_ref(
    r: jax.Array,  # [B, S, H, Dh]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # [B, S, H, Dh] (negative log decays)
    u: jax.Array,  # [H, Dh]
    state0: jax.Array | None = None,  # [B, H, Dh, Dh] fp32
) -> tuple[jax.Array, jax.Array]:
    """Sequential WKV recurrence:
    out_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ); S_t = diag(w_t) S_{t-1} + k_t v_tᵀ."""
    b, s, h, dh = r.shape
    S = (
        jnp.zeros((b, h, dh, dh), jnp.float32) if state0 is None
        else state0.astype(jnp.float32)
    )

    def step(S, inp):
        r_t, k_t, v_t, lw_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        out = jnp.einsum("bhd,bhde->bhe", r_t, S + u[None, :, :, None] * kv)
        S = jnp.exp(lw_t)[..., :, None] * S + kv
        return S, out

    seq = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, logw))
    S, out = jax.lax.scan(step, S, seq)
    return jnp.moveaxis(out, 0, 1).astype(r.dtype), S


def mamba_ref(
    u: jax.Array,  # [B, S, Di]
    dt: jax.Array,  # [B, S, Di]
    A: jax.Array,  # [Di, St]
    B_: jax.Array,  # [B, S, St]
    C_: jax.Array,  # [B, S, St]
    h0: jax.Array | None = None,  # [B, Di, St] fp32
) -> tuple[jax.Array, jax.Array]:
    """Sequential selective scan:
    h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t; y_t = h_t · C_t."""
    b, s, di = u.shape
    st = A.shape[-1]
    h = jnp.zeros((b, di, st), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        a = jnp.exp(dt_t[..., None] * A[None].astype(jnp.float32))
        h = a * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bds,bs->bd", h, c_t)

    seq = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (u, dt, B_, C_))
    h, ys = jax.lax.scan(step, h, seq)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), h


def ssd_chunked(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H] fp32, after softplus
    A: jax.Array,  # [H] fp32, negative
    B_: jax.Array,  # [B, S, G, N]
    C_: jax.Array,  # [B, S, G, N]
    h0: jax.Array | None = None,  # [B, H, P, N] fp32
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Mamba-2's SSD scan in its chunked form (arXiv:2405.21060, section 6):
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ; y_t = h_t C_t. Within a chunk
    the decay-masked product C Bᵀ, across chunks the carried state. Heads
    ``g * R`` to ``g * R + R - 1`` (``R = H / G``) read group ``g``'s B and
    C, as ``kernels/ssd.py`` does.
    Returns (y in x's dtype, final state fp32)."""
    b, s, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    r = h // g
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    pad = -s % chunk
    f32 = [t.astype(jnp.float32) for t in (x, dt, B_, C_)]
    xs, dts, bs, cs_ = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                        .reshape(b, -1, chunk, *t.shape[2:]) for t in f32)
    xs = xs.reshape(*xs.shape[:3], g, r, p)  # [B, nc, L, G, R, P]
    dts = dts.reshape(*dts.shape[:3], g, r)
    cum = jnp.cumsum(dts * A.astype(jnp.float32).reshape(g, r), axis=2)  # [B, nc, L, G, R]
    hp = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cs_, bs, precision=hp)
    seg = jnp.moveaxis(cum, 2, -1)  # [B, nc, G, R, L]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg[..., :, None] - seg[..., None, :], -jnp.inf))
    xdt = xs * dts[..., None]
    y = jnp.einsum("bcgls,bcgrls,bcsgrp->bclgrp", scores, decay, xdt, precision=hp)
    last = seg[..., -1:]  # [B, nc, G, R, 1]
    states = jnp.einsum("bcsgn,bcgrs,bcsgrp->bcgrpn", bs, jnp.exp(last - seg), xdt,
                        precision=hp)

    def carry(hc, inp):
        st, tot = inp  # [B, G, R, P, N], [B, G, R]
        return jnp.exp(tot)[..., None, None] * hc + st, hc

    hend, hstart = jax.lax.scan(carry, h0.astype(jnp.float32).reshape(b, g, r, p, n),
                                (jnp.moveaxis(states, 1, 0),
                                 jnp.moveaxis(last[..., 0], 1, 0)))
    y = y + jnp.einsum("bclgn,cbgrpn,bcgrl->bclgrp", cs_, hstart, jnp.exp(seg),
                       precision=hp)
    y = y.reshape(b, -1, h, p)[:, :s]
    return y.astype(x.dtype), hend.reshape(b, h, p, n)
