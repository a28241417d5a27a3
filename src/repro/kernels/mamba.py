"""Mamba selective scan as a Pallas TPU kernel.

Blocking: grid ``(B, Di/bd, S/L)`` — channel blocks are parallel (each owns
an independent [bd, St] state slice; Mamba's recurrence never mixes
channels), the chunk axis is innermost/sequential with the fp32 state in
VMEM scratch. Within a chunk the timestep loop runs over VMEM-resident
tiles (``fori_loop`` over L), so HBM traffic is one read of u/dt/B/C and one
write of y per element — the memory-bound optimum for this op; the CUDA
version's warp-parallel scan becomes block-sequential VPU work here because
TPU has no cross-lane shuffle, and channel-block parallelism supplies the
occupancy instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(
    u_ref, dt_ref,  # [1, L, bd]
    a_ref,  # [bd, St]
    b_ref, c_ref,  # [1, L, St]
    h0_ref,  # [1, bd, St]
    y_ref,  # [1, L, bd]
    hout_ref,  # [1, bd, St]
    h_scr,  # VMEM [bd, St] fp32
    u_scr, dt_scr, y_scr,  # VMEM [L, bd] fp32
    b_scr, c_scr,  # VMEM [L, St] fp32
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)  # [bd, St]

    # Mosaic cannot slice values at a traced offset, nor address one bf16 row
    # at an arbitrary sublane: stage the chunk in fp32 VMEM and move one
    # timestep row at a time through the scratch refs
    for ref, scr in ((u_ref, u_scr), (dt_ref, dt_scr), (b_ref, b_scr), (c_ref, c_scr)):
        scr[...] = ref[0].astype(jnp.float32)

    def step(t, h):
        row = pl.ds(t, 1)
        dt_t = dt_scr[row, :].T  # [bd, 1]
        u_t = u_scr[row, :].T
        b_t = b_scr[row, :]  # [1, St]
        c_t = c_scr[row, :]
        a = jnp.exp(dt_t * A)  # [bd, St]
        h = a * h + (dt_t * u_t) * b_t
        y_scr[row, :] = jnp.sum(h * c_t, axis=1, keepdims=True).T  # [1, bd]
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h
    y_ref[0, :, :] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _done():
        hout_ref[0, :, :] = h


def mamba_scan_bsd(
    u: jax.Array,  # [B, S, Di]
    dt: jax.Array,  # [B, S, Di]
    A: jax.Array,  # [Di, St]
    B_: jax.Array,  # [B, S, St]
    C_: jax.Array,  # [B, S, St]
    h0: jax.Array,  # [B, Di, St] fp32
    *,
    chunk: int = 64,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, s, di = u.shape
    st = A.shape[-1]
    bd = min(block_d, di)
    assert s % chunk == 0 and di % bd == 0, (s, chunk, di, bd)
    nc, nd = s // chunk, di // bd
    grid = (b, nd, nc)
    kernel = functools.partial(_mamba_kernel, chunk=chunk, n_chunks=nc)
    y, h = pl.pallas_call(
        kernel,
        name="mamba_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, id_, ic: (b_, ic, id_)),
            pl.BlockSpec((1, chunk, bd), lambda b_, id_, ic: (b_, ic, id_)),
            pl.BlockSpec((bd, st), lambda b_, id_, ic: (id_, 0)),
            pl.BlockSpec((1, chunk, st), lambda b_, id_, ic: (b_, ic, 0)),
            pl.BlockSpec((1, chunk, st), lambda b_, id_, ic: (b_, ic, 0)),
            pl.BlockSpec((1, bd, st), lambda b_, id_, ic: (b_, id_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, id_, ic: (b_, ic, id_)),
            pl.BlockSpec((1, bd, st), lambda b_, id_, ic: (b_, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, di), u.dtype),
            jax.ShapeDtypeStruct((b, di, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, st), jnp.float32)]
        + [pltpu.VMEM((chunk, bd), jnp.float32)] * 3
        + [pltpu.VMEM((chunk, st), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u, dt, A, B_, C_, h0)
    return y, h
