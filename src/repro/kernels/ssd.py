"""Mamba-2's state-space dual (SSD) scan as a Pallas TPU kernel (forward).

The chunked form of arXiv:2405.21060, section 6. Within a chunk of ``L``
positions the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ``,
``y_t = h_t C_t`` is one masked product: with ``cs`` the chunk's cumulative
``dt A``,

    y_i = sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j + exp(cs_i) h_0 C_i

and the state leaving the chunk is ``exp(cs_L) h_0 + sum_j exp(cs_L - cs_j)
dt_j x_j B_jᵀ``. Every term is an MXU product; the decays are differences of
cumulative sums taken only at or below the diagonal, so no exponent is
positive however fast the state decays.

Blocking: grid ``(B, H / hb, S / L)``. Batch rows and blocks of ``hb`` heads
are parallel; chunks run in sequence with each head's ``[P, N]`` fp32 state
in VMEM scratch. One grid step reads a ``[L, hb * P]`` tile of x (heads side
by side, as the model lays them out), ``[hb, L]`` rows of ``dt`` and of
``cs``, and the ``[L, N]`` B and C of the heads' group, whose ``C Bᵀ`` scores
all ``hb`` heads share. The x tile is transposed once in VMEM so that each
head is a sublane-aligned ``[P, L]`` slab, and every product runs with
positions on lanes. Scores, decays and accumulators are fp32.

y comes out ``[B, S, H * P]``, three-dimensional, and the final state
``[B, H, P, N]`` fp32. Tracing a call adds 1 to the counter ``ssd.calls`` and
its sequences' chunks, ``B * S / L``, to ``ssd.chunks``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs


def head_block(heads: int, groups: int, head_dim: int) -> int:
    """Heads per grid step: 8 where the tiles allow it (a multiple of 8 rows
    of ``dt``, a multiple of 128 lanes of x, inside one group), else all."""
    per_group = heads // groups
    if per_group % 8 == 0 and (8 * head_dim) % 128 == 0:
        return 8
    if groups != 1:
        raise ValueError(f"no head block for {heads} heads in {groups} groups of "
                         f"head_dim {head_dim}")
    return heads


def _ssd_kernel(
    x_ref,  # [1, L, hb*P]
    dt_ref, cs_ref,  # [1, hb, L] fp32
    b_ref, c_ref,  # [1, L, N]
    h0_ref,  # [1, hb, P, N] fp32
    y_ref,  # [1, L, hb*P]
    hout_ref,  # [1, hb, P, N] fp32
    h_scr,  # VMEM [hb, P, N] fp32
    yt_scr,  # VMEM [hb*P, L] fp32
    *,
    hb: int,
    p: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    L = x_ref.shape[1]
    xt = x_ref[0].astype(jnp.float32).T  # [hb*P, L]
    b = b_ref[0].astype(jnp.float32)  # [L, N]
    c = c_ref[0].astype(jnp.float32)
    # scores_t[j, i] = B_j . C_i, shared by every head of the group
    scores_t = jax.lax.dot_general(b, c, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)  # [L, L]
    j = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = j <= i
    for h in range(hb):
        cs = cs_ref[0, h:h + 1, :]  # [1, L]: cumulative dt*A, non-increasing
        dt = dt_ref[0, h:h + 1, :]
        cs_last = jnp.min(cs, axis=1, keepdims=True)  # [1, 1]: the chunk's total
        decay_t = jnp.exp(jnp.where(causal, cs - cs.T, -jnp.inf))  # [L(j), L(i)]
        xdt = xt[h * p:(h + 1) * p, :] * dt  # [P, L]
        state = h_scr[h]  # [P, N]
        y = jax.lax.dot_general(xdt, scores_t * decay_t, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y += jax.lax.dot_general(state, c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * jnp.exp(cs)
        yt_scr[h * p:(h + 1) * p, :] = y
        h_scr[h] = state * jnp.exp(cs_last) + jax.lax.dot_general(
            xdt * jnp.exp(cs_last - cs), b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    y_ref[0] = yt_scr[...].T.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _done():
        hout_ref[0] = h_scr[...]


def ssd_bshp(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H] fp32, after softplus
    A: jax.Array,  # [H] fp32, negative
    B_: jax.Array,  # [B, S, G, N]
    C_: jax.Array,  # [B, S, G, N]
    h0: jax.Array,  # [B, H, P, N] fp32
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, S, H, P] in x's dtype, final state [B, H, P, N] fp32).
    A length that is not a multiple of ``chunk`` is padded with ``dt = 0``,
    which leaves the state as it was."""
    b, s, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    hb = head_block(h, g, p)
    pad = -s % chunk
    sp = s + pad
    nc = sp // chunk
    if pad:
        x, dt, B_, C_ = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, B_, C_))
    dta = (dt * A).reshape(b, nc, chunk, h)
    cs = jnp.cumsum(dta, axis=2).reshape(b, sp, h)
    dt_t, cs_t = jnp.swapaxes(dt, 1, 2), jnp.swapaxes(cs, 1, 2)  # [B, H, S]
    obs.count("ssd.calls")
    obs.count("ssd.chunks", b * nc)

    per_group = h // g
    seq = lambda b_, ih, ic: (b_, ic, ih)  # noqa: E731
    row = lambda b_, ih, ic: (b_, ih, ic)  # noqa: E731
    grp = lambda b_, ih, ic: (b_, ic, ih * hb // per_group)  # noqa: E731
    state = lambda b_, ih, ic: (b_, ih, 0, 0)  # noqa: E731
    kernel = functools.partial(_ssd_kernel, hb=hb, p=p, n_chunks=nc)
    y, hout = pl.pallas_call(
        kernel,
        name="ssd_fwd",
        grid=(b, h // hb, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hb * p), seq),
            pl.BlockSpec((1, hb, chunk), row),
            pl.BlockSpec((1, hb, chunk), row),
            pl.BlockSpec((1, chunk, n), grp),
            pl.BlockSpec((1, chunk, n), grp),
            pl.BlockSpec((1, hb, p, n), state),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hb * p), seq),
            pl.BlockSpec((1, hb, p, n), state),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, h * p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32),
                        pltpu.VMEM((hb * p, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x.reshape(b, sp, h * p), dt_t, cs_t, B_.reshape(b, sp, g * n),
      C_.reshape(b, sp, g * n), h0.astype(jnp.float32))
    return y.reshape(b, sp, h, p)[:, :s], hout
