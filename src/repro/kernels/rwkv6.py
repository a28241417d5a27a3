"""RWKV6 chunked WKV recurrence as a Pallas TPU kernel.

Blocking: grid ``(B, H, S/L)`` with the chunk axis innermost (sequential on
TPU); the [Dh, Dh] WKV state lives in fp32 VMEM scratch carried across
chunks and re-initialized per (batch, head). Within a chunk the recurrence
is closed-form: an L x L masked score matrix (intra-chunk), a state
read-out (cross-chunk), and a rank-L state update — three small MXU matmuls
instead of L sequential vector ops, which is the TPU-native reshaping of the
RWKV CUDA kernel's per-timestep loop.

Chunks are short (L=16) and decays are clamped (see models/ssm.py MAX_DECAY)
so the exp(±cumsum(log w)) factors stay inside fp32 range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, lw_ref,  # [1, 1, L, Dh]
    u_ref,  # [1, 1, Dh]
    s0_ref,  # [1, 1, Dh, Dh]
    o_ref,  # [1, 1, L, Dh]
    sout_ref,  # [1, 1, Dh, Dh]
    state_scr,  # VMEM [Dh, Dh] fp32
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # [L, Dh]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # [1, Dh]

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum); HIGHEST keeps the log-decays at fp32 accuracy
    tril = jnp.where(cols <= rows, 1.0, 0.0)
    la = jax.lax.dot_general(
        tril, lw, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [L, Dh] inclusive log-decay
    q_ = r * jnp.exp(la - lw)  # r_t * A_{t-1}
    k_ = k * jnp.exp(-la)  # k_s / A_s
    scores = jax.lax.dot_general(
        q_, k_, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L]
    scores = jnp.where(cols < rows, scores, 0.0)  # strictly lower triangular
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # [L, 1]
    scores = scores + jnp.where(cols == rows, bonus, 0.0)  # diag(bonus)
    intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, Dh]
    S = state_scr[...]
    cross = jax.lax.dot_general(
        q_, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, Dh_v]
    o_ref[0, 0, :, :] = (intra + cross).astype(o_ref.dtype)

    la_last = la[-1:, :]  # [1, Dh]
    kd = k * jnp.exp(la_last - la)  # [L, Dh]
    state_scr[...] = S * jnp.exp(la_last).T + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_chunks - 1)
    def _done():
        sout_ref[0, 0, :, :] = state_scr[...]


def rwkv6_bhsd(
    r: jax.Array,  # [B, H, S, Dh]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # [B, H, S, Dh]
    u: jax.Array,  # [H, Dh]
    state0: jax.Array,  # [B, H, Dh, Dh] fp32
    *,
    chunk: int = 16,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, h, s, d = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    grid = (b, h, nc)
    kernel = functools.partial(_rwkv6_kernel, chunk=chunk, n_chunks=nc)
    out, state = pl.pallas_call(
        kernel,
        name="rwkv6",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, d), lambda b_, h_, ic: (h_, 0, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(h, 1, d), state0)
    return out, state
