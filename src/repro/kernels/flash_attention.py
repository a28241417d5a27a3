"""Flash attention as a Pallas TPU kernel (forward).

TPU-native blocking (not a CUDA port): the grid is ``(B, H, Sq/bq, Sk/bk)``
with the KV-block axis innermost — TPU grids execute sequentially over the
last dimension, so the online-softmax running statistics (m, l) and the
output accumulator live in VMEM scratch that persists across KV blocks and
is re-initialized when a new query block begins. Q/K/V tiles stream
HBM→VMEM via BlockSpecs; the MXU sees [bq, Dh] x [Dh, bk] matmuls with Dh
padded to the 128-lane register width.

GQA is handled in the K/V index_map (query head h reads KV head ``h // G``)
so repeated heads are never materialized in HBM. Causal and sliding-window
masks are applied from block-relative iotas.

Scratch layout follows the official JAX flash kernel convention: m/l are
[bq, 128] with lane-broadcast values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref,  # [1, 1, bq|bk, Dh]
    o_ref,  # [1, 1, bq, Dh]
    m_scr, l_scr, acc_scr,  # [bq, 128], [bq, 128], [bq, Dh]
    *,
    scale: float,
    causal: bool,
    window: int | None,
    bq: int,
    bk: int,
    nk: int,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)  # [bq, Dh]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, Dh]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]

    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0:1]  # [bq, 1]
    l_prev = l_scr[:, 0:1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked blocks: keep p exactly 0 (avoids exp(NEG-NEG)=1 poison)
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)  # [bq, bk]
    alpha = jnp.where(m_prev > 0.5 * NEG_INF, jnp.exp(m_prev - m_new), 1.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _done():
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # [B, H, Sq, Dh]
    k: jax.Array,  # [B, KV, Sk, Dh]
    v: jax.Array,  # [B, KV, Sk, Dh]
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=d**-0.5, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
