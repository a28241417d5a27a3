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
masks are applied from block-relative iotas, and only where a block
straddles the diagonal or the window's edge. Blocks wholly outside the mask
are skipped: the K/V index_map clamps the KV block to the query block's
``kv_span``, so a skipped grid step repeats the block before it and the
pipeline copies nothing. Tracing a call adds its grid blocks, blocks computed
and blocks computed with the mask to the counters ``flash.blocks``,
``flash.blocks_run`` and ``flash.blocks_masked``.

Scratch layout follows the official JAX flash kernel convention: m/l are
[bq, 128] with lane-broadcast values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs

NEG_INF = -1e30


def kv_span(iq, *, bq: int, bk: int, nk: int, causal: bool, window: int | None,
            xp=jnp):
    """First and last KV block that query block ``iq`` needs: the blocks at
    or below the causal diagonal, and inside the sliding window if any.
    ``iq`` is a grid index in the kernel, or with ``xp=np`` an array of them
    on the host."""
    if not causal:
        return 0 * iq, 0 * iq + nk - 1
    hi = xp.minimum((iq * bq + bq - 1) // bk, nk - 1)
    lo = 0 * iq if window is None else xp.maximum(iq * bq - window + 1, 0) // bk
    return lo, hi


def block_unmasked(iq, ik, *, bq: int, bk: int, causal: bool, window: int | None):
    """Whether every (row, col) of block ``(iq, ik)`` passes the mask, so the
    block needs none."""
    if not causal:
        return True
    full = ik * bk + bk - 1 <= iq * bq
    if window is not None:
        full = full & (ik * bk > iq * bq + bq - 1 - window)
    return full


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
    shape = dict(bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, Dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        if masked:
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
        p = jnp.exp(s - m_new)  # [bq, bk]
        if masked:
            # fully-masked rows: keep p exactly 0 (avoids exp(NEG-NEG)=1 poison)
            p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
        alpha = jnp.where(m_prev > 0.5 * NEG_INF, jnp.exp(m_prev - m_new), 1.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # blocks outside [lo, hi] lie wholly outside the mask: skipping them
        # leaves the running state as a fully masked block would
        lo, hi = kv_span(iq, nk=nk, **shape)
        needed = (lo <= ik) & (ik <= hi)
        unmasked = block_unmasked(iq, ik, **shape)
        pl.when(needed & unmasked)(lambda: step(False))
        pl.when(needed & jnp.logical_not(unmasked))(lambda: step(True))
    else:
        step(False)

    @pl.when(ik == nk - 1)
    def _done():
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def block_counts(sq: int, sk: int, *, bq: int, bk: int, causal: bool,
                 window: int | None) -> tuple[int, int, int]:
    """(grid blocks, blocks computed, blocks computed with the mask) of one
    head of one sequence, from the functions the kernel runs."""
    nq, nk = sq // bq, sk // bk
    iq, ik = np.arange(nq)[:, None], np.arange(nk)[None, :]
    shape = dict(bq=bq, bk=bk, causal=causal, window=window)
    lo, hi = kv_span(iq, nk=nk, xp=np, **shape)
    run = (lo <= ik) & (ik <= hi)
    full = block_unmasked(iq, ik, **shape)
    return nq * nk, int(run.sum()), int((run & np.logical_not(full)).sum())


def flash_attention_bhsd(
    q: jax.Array,  # [B, H, Sq, Dh]
    k: jax.Array,  # [B, KV, Sk, Dh]
    v: jax.Array,  # [B, KV, Sk, Dh]
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
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
    shape = dict(bq=bq, bk=bk, causal=causal, window=window)

    blocks, run, masked = block_counts(sq, sk, **shape)
    for name, n in (("blocks", blocks), ("blocks_run", run), ("blocks_masked", masked)):
        obs.count(f"flash.{name}", b * h * n)

    def kv_index(b_, h_, iq, ik):
        # a skipped step repeats the block of the step before, so the
        # pipeline copies nothing for it
        lo, hi = kv_span(iq, nk=nk, **shape)
        return (b_, h_ // g, jnp.minimum(jnp.maximum(ik, lo), hi), 0)

    kernel = functools.partial(_flash_fwd_kernel, scale=d**-0.5 if scale is None else scale,
                               nk=nk, **shape)
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
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
