"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True (kernel bodies run on CPU)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.flash_attention import NEG_INF
from repro.models import ssm as model_ssm

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False


def rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(0, 1, shape), dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kv,dh,causal,window",
    [
        (2, 128, 128, 4, 4, 64, True, None),
        (1, 256, 256, 8, 2, 64, True, None),  # GQA 4:1
        (2, 128, 128, 4, 1, 128, True, None),  # MQA
        (1, 256, 256, 4, 4, 64, True, 64),  # sliding window
        (1, 128, 128, 2, 2, 96, False, None),  # encoder (non-causal), Dh=96
        (2, 64, 64, 4, 2, 32, True, 16),
        # S = 31 * 128, the longest served prompt: 992-wide tiles
        (1, 3968, 3968, 2, 1, 64, True, None),
        (1, 3968, 3968, 2, 1, 64, True, 1000),
        (1, 3968, 3968, 2, 1, 64, False, None),
    ],
)
def test_flash_attention_vs_ref(b, sq, sk, h, kv, dh, causal, window, dtype):
    rng = np.random.default_rng(hash((b, sq, h, kv, dh)) % 2**31)
    q = rand(rng, (b, sq, h, dh), dtype)
    k = rand(rng, (b, sk, kv, dh), dtype)
    v = rand(rng, (b, sk, kv, dh), dtype)
    got = ops.flash_attention(q, k, v, causal, window, True)
    want = ref.attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_attention_block_sweep():
    """Block shape must not change the math."""
    from repro.kernels.flash_attention import flash_attention_bhsd

    rng = np.random.default_rng(0)
    q = rand(rng, (1, 2, 256, 64), jnp.float32)
    k = rand(rng, (1, 2, 256, 64), jnp.float32)
    v = rand(rng, (1, 2, 256, 64), jnp.float32)
    outs = []
    for bq, bk in [(64, 64), (128, 256), (256, 64), (256, 256)]:
        outs.append(
            np.asarray(
                flash_attention_bhsd(
                    q, k, v, causal=True, window=None,
                    block_q=bq, block_k=bk, interpret=True,
                )
            )
        )
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


def _full_grid_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale, causal, window, bq, bk, nk):
    """A full-grid flash kernel, the oracle of the skipping schedule: every
    KV block of every query block is computed, each under the mask."""
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[:, 0:1]
    l_prev = l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
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


def _full_grid_flash(q, k, v, causal, window, bq, bk):
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    nq, nk = sq // bq, sk // bk
    kernel = functools.partial(_full_grid_kernel, scale=d**-0.5, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=True,
    )(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "s,block,h,kv,causal,window",
    [
        (256, 64, 2, 2, True, None),
        (384, 128, 4, 2, True, None),  # GQA
        (256, 64, 2, 1, True, 64),  # window of one block
        (384, 64, 2, 2, True, 100),  # window edge inside blocks
        (256, 64, 2, 2, False, None),
    ],
)
def test_flash_skip_bit_identical_to_full_grid(s, block, h, kv, causal, window, dtype):
    """Skipping the blocks outside the mask, and not masking the blocks
    wholly inside it, leaves every bit of the output as it was."""
    from repro.kernels.flash_attention import flash_attention_bhsd

    rng = np.random.default_rng(s + block + h + (window or 0))
    q = rand(rng, (1, h, s, 64), dtype)
    k = rand(rng, (1, kv, s, 64), dtype)
    v = rand(rng, (1, kv, s, 64), dtype)
    got = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                               block_q=block, block_k=block, interpret=True)
    want = _full_grid_flash(q, k, v, causal, window, block, block)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize(
    "s,bq,bk,causal,window,closed",
    [
        # square tiles: the n(n+1)/2 blocks on and below the diagonal, the n
        # on it masked
        (512, 64, 64, True, None, lambda n: (n * n, n * (n + 1) // 2, n)),
        # bq = m * bk: the m blocks under each query tile's diagonal masked
        (512, 128, 32, True, None,
         lambda nq: (nq * 4 * nq, 4 * nq * (nq + 1) // 2, 4 * nq)),
        # window of w blocks: min(iq, w) + 1 blocks a row, its two edges masked
        (512, 64, 64, True, 3 * 64,
         lambda n: (n * n, sum(min(i, 3) + 1 for i in range(n)), n + max(0, n - 3))),
        (512, 64, 128, False, None, lambda nq: (nq * nq // 2, nq * nq // 2, 0)),
    ],
)
def test_flash_block_counters(s, bq, bk, causal, window, closed):
    from repro import obs
    from repro.kernels.flash_attention import block_counts, flash_attention_bhsd

    b, h = 2, 3
    want = tuple(b * h * n for n in closed(s // bq))
    assert tuple(b * h * n for n in block_counts(
        s, s, bq=bq, bk=bk, causal=causal, window=window)) == want
    x = jax.ShapeDtypeStruct((b, h, s, 64), jnp.float32)
    before = obs.counters()
    jax.eval_shape(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=True), x, x, x)
    after = obs.counters()
    got = tuple(after[f"flash.{n}"] - before.get(f"flash.{n}", 0)
                for n in ("blocks", "blocks_run", "blocks_masked"))
    assert got == want


@pytest.mark.parametrize(
    "s,bq,bk,causal,window",
    [
        (3968, 496, 128, True, None),  # S = 31 * 128, as the longest prompt
        (3968, 128, 992, True, 1000),
        (3968, 992, 496, False, None),
        (512, 128, 64, True, 100),
        (512, 64, 256, True, None),
    ],
)
def test_flash_uneven_tiles_vs_ref(s, bq, bk, causal, window):
    from repro.kernels.flash_attention import flash_attention_bhsd

    rng = np.random.default_rng(s + bq + bk)
    q = rand(rng, (1, s, 2, 64), jnp.float32)
    k = rand(rng, (1, s, 1, 64), jnp.float32)
    v = rand(rng, (1, s, 1, 64), jnp.float32)
    got = flash_attention_bhsd(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                               causal=causal, window=window, block_q=bq,
                               block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 1, 2)),
                               np.asarray(want), **TOL[jnp.float32])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("s", [64, 512, 1024, 2048, 3968, 4096])
def test_flash_tiles_rule(s, d, dtype):
    """The largest tiles that divide S in whole sublane tiles, up to a
    1024 x 1024 score tile and fewer query rows past Dh 128."""
    bq, bk = ops.flash_tiles(s, s, d, dtype)
    sublane = 32 // jnp.dtype(dtype).itemsize
    cap_q, cap_k = 1024 * 128 // max(d, 128), 1024
    for t, cap in ((bq, cap_q), (bk, cap_k)):
        assert s % t == 0 and t % sublane == 0 and t <= cap
        assert not any(s % u == 0 for u in range(t + sublane, cap + 1, sublane))
    if d <= 128:
        assert (bq, bk) == {3968: (992, 992)}.get(s, (min(s, 1024),) * 2)


def test_flash_attention_grad_matches_ref():
    rng = np.random.default_rng(1)
    q = rand(rng, (1, 64, 2, 32), jnp.float32)
    k = rand(rng, (1, 64, 2, 32), jnp.float32)
    v = rand(rng, (1, 64, 2, 32), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(jnp.square(ops.flash_attention(q, k, v, True, None, True)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(ref.attention_ref(q, k, v, True, None)))

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,dh,chunk", [(2, 64, 2, 32, 16), (1, 128, 4, 64, 16),
                                            (1, 32, 1, 128, 16)])
def test_rwkv6_kernel_vs_ref(b, s, h, dh, chunk, dtype):
    rng = np.random.default_rng(42)
    r = rand(rng, (b, s, h, dh), dtype)
    k = rand(rng, (b, s, h, dh), dtype)
    v = rand(rng, (b, s, h, dh), dtype)
    logw = -jnp.abs(rand(rng, (b, s, h, dh), jnp.float32)) - 0.05
    u = rand(rng, (h, dh), jnp.float32)
    s0 = jnp.asarray(rng.normal(0, 0.3, (b, h, dh, dh)), jnp.float32)
    got, gstate = ops.rwkv6(r, k, v, logw.astype(dtype), u, s0, True)
    want, wstate = ref.rwkv6_ref(r, k, v, logw.astype(dtype), u, s0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(gstate), np.asarray(wstate),
                               rtol=3e-3 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-3 if dtype == jnp.bfloat16 else 1e-4)


def test_rwkv6_model_chunked_vs_naive():
    """The model's jnp chunked path == the naive oracle (independent of the
    Pallas kernel)."""
    rng = np.random.default_rng(7)
    b, s, h, dh = 2, 48, 2, 16
    r, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, h, dh)), jnp.float32) for _ in range(3))
    logw = -jnp.abs(jnp.asarray(rng.normal(0, 1, (b, s, h, dh)), jnp.float32)) - 0.02
    u = jnp.asarray(rng.normal(0, 1, (h, dh)), jnp.float32)
    o1, s1 = model_ssm.rwkv6_chunked(r, k, v, logw, u)
    o2, s2 = model_ssm.rwkv6_naive(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- mamba
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,di,st,chunk", [(2, 64, 64, 8, 64), (1, 128, 256, 16, 64)])
def test_mamba_kernel_vs_ref(b, s, di, st, chunk, dtype):
    rng = np.random.default_rng(3)
    u = rand(rng, (b, s, di), dtype)
    dt = jnp.abs(rand(rng, (b, s, di), dtype)) * 0.1
    A = -jnp.abs(jnp.asarray(rng.normal(0, 1, (di, st)), jnp.float32))
    B_ = rand(rng, (b, s, st), dtype)
    C_ = rand(rng, (b, s, st), dtype)
    h0 = jnp.asarray(rng.normal(0, 0.3, (b, di, st)), jnp.float32)
    got_y, got_h = ops.mamba_scan(u, dt, A, B_, C_, h0, True)
    want_y, want_h = ref.mamba_ref(u, dt, A, B_, C_, h0)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-3, atol=1e-3)


def test_mamba_model_chunked_vs_naive():
    rng = np.random.default_rng(5)
    b, s, di, st = 1, 512, 32, 4
    u = jnp.asarray(rng.normal(0, 1, (b, s, di)), jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.normal(0, 0.1, (b, s, di)), jnp.float32))
    A = -jnp.abs(jnp.asarray(rng.normal(0, 1, (di, st)), jnp.float32))
    B_ = jnp.asarray(rng.normal(0, 1, (b, s, st)), jnp.float32)
    C_ = jnp.asarray(rng.normal(0, 1, (b, s, st)), jnp.float32)
    y1, h1 = model_ssm.mamba_scan_chunked(u, dt, A, B_, C_, chunk=256)
    y2, h2 = model_ssm.mamba_scan_naive(u, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- ssd
def _ssd_inputs(rng, b, s, h, p, g, n, dtype, init=True):
    x = rand(rng, (b, s, h, p), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(-1, 1, (b, s, h)), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.normal(0, 0.5, (h,)), jnp.float32))
    B_ = rand(rng, (b, s, g, n), dtype)
    C_ = rand(rng, (b, s, g, n), dtype)
    h0 = (jnp.asarray(rng.normal(0, 0.3, (b, h, p, n)), jnp.float32) if init
          else jnp.zeros((b, h, p, n), jnp.float32))
    return x, dt, A, B_, C_, h0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk,init", [
    (2, 64, 4, 16, 8, 32, False),  # a multiple of the chunk
    (1, 100, 4, 16, 24, 32, True),  # padded to the chunk, from a state
    (1, 256, 16, 16, 32, 128, True),  # blocks of 8 heads, 128 lanes of x
])
def test_ssd_kernel_vs_ref(b, s, h, p, n, chunk, init, dtype):
    rng = np.random.default_rng(11)
    args = _ssd_inputs(rng, b, s, h, p, 1, n, dtype, init)
    with jax.default_matmul_precision("highest"):
        got_y, got_h = ops.ssd(*args, chunk=chunk, interpret=True)
        want_y, want_h = ref.ssd_chunked(*args, chunk=chunk)
    assert got_y.shape == (b, s, h, p) and got_y.dtype == dtype
    np.testing.assert_allclose(np.asarray(got_y, np.float32), np.asarray(want_y, np.float32),
                               **TOL[dtype])
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), rtol=1e-4, atol=1e-4)


def _ssd_by_steps(x, dt, A, B_, C_, h0):
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        y, h = model_ssm.ssd_step(x_t, dt_t, A, b_t, c_t, h)
        return h, y

    seq = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
           jnp.moveaxis(B_, 1, 0), jnp.moveaxis(C_, 1, 0))
    h, ys = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(ys, 0, 1), h


@pytest.mark.parametrize("s,g,chunk", [(96, 1, 32), (77, 2, 16)])
def test_ssd_chunked_vs_step_recurrence(s, g, chunk):
    """The chunked form (prefill, training) and the decode update taken one
    position at a time give the same outputs and final state."""
    rng = np.random.default_rng(12)
    args = _ssd_inputs(rng, 2, s, 4, 8, g, 12, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y1, h1 = ref.ssd_chunked(*args, chunk=chunk)
        y2, h2 = _ssd_by_steps(*args)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)


def test_ssd_fast_decay_stays_finite():
    """A state that decays to nothing within a chunk: every decay is a
    difference of cumulative sums at or below the diagonal, never positive."""
    rng = np.random.default_rng(13)
    x, dt, A, B_, C_, h0 = _ssd_inputs(rng, 1, 128, 4, 16, 1, 8, jnp.float32)
    A = A * 50.0
    with jax.default_matmul_precision("highest"):
        got, _ = ops.ssd(x, dt, A, B_, C_, h0, chunk=64, interpret=True)
        want, _ = _ssd_by_steps(x, dt, A, B_, C_, h0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ssd_kernel_counts_its_chunks():
    from repro import obs

    rng = np.random.default_rng(14)
    args = _ssd_inputs(rng, 2, 100, 4, 16, 1, 8, jnp.float32)
    before = obs.counters()
    ops.ssd(*args, chunk=32, interpret=True)
    after = obs.counters()
    assert after["ssd.calls"] - before.get("ssd.calls", 0) == 1
    assert after["ssd.chunks"] - before.get("ssd.chunks", 0) == 2 * 4  # 100 -> 4 chunks


def test_ssd_grad_goes_through_the_chunked_form():
    rng = np.random.default_rng(15)
    args = _ssd_inputs(rng, 1, 64, 4, 16, 1, 8, jnp.float32)

    def total(f):
        return lambda *a: jnp.sum(f(*a)[0] ** 2) + jnp.sum(f(*a)[1])

    with jax.default_matmul_precision("highest"):
        got = jax.grad(total(lambda *a: ops.ssd(*a, chunk=32, interpret=True)),
                       argnums=(0, 1, 2, 3, 4, 5))(*args)
        want = jax.grad(total(_ssd_by_steps), argnums=(0, 1, 2, 3, 4, 5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3, atol=1e-3)


# --------------------------------------------------- property-based sweeps
if HAVE_HYP:

    @given(
        b=st.integers(1, 2),
        nq=st.integers(1, 3),
        heads=st.sampled_from([(2, 1), (2, 2), (4, 2)]),
        dh=st.sampled_from([32, 64]),
        causal=st.booleans(),
    )
    @settings(max_examples=12, deadline=None)
    def test_property_flash_attention_random_shapes(b, nq, heads, dh, causal):
        h, kv = heads
        s = 64 * nq
        rng = np.random.default_rng(b * 1000 + s + h + dh)
        q = rand(rng, (b, s, h, dh), jnp.float32)
        k = rand(rng, (b, s, kv, dh), jnp.float32)
        v = rand(rng, (b, s, kv, dh), jnp.float32)
        got = ops.flash_attention(q, k, v, causal, None, True)
        want = ref.attention_ref(q, k, v, causal, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @given(
        s=st.sampled_from([16, 32, 64]),
        dh=st.sampled_from([16, 32]),
        strong_decay=st.booleans(),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_rwkv6_decay_regimes(s, dh, strong_decay):
        """Weak and strong decays must both stay finite and match the oracle
        (the fp32-range clamp argument in models/ssm.py)."""
        rng = np.random.default_rng(s + dh)
        b, h = 1, 2
        scale = 3.5 if strong_decay else 0.05
        r, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, h, dh)), jnp.float32) for _ in range(3))
        logw = -jnp.abs(jnp.asarray(rng.normal(0, scale, (b, s, h, dh)), jnp.float32)) - 1e-3
        logw = jnp.maximum(logw, -model_ssm.MAX_DECAY)
        u = jnp.asarray(rng.normal(0, 1, (h, dh)), jnp.float32)
        s0 = jnp.zeros((b, h, dh, dh), jnp.float32)
        got, _ = ops.rwkv6(r, k, v, logw, u, s0, True)
        want, _ = ref.rwkv6_ref(r, k, v, logw, u, s0)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode is the CPU's path; any other non-TPU backend is an
    error, never a silent fallback."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no path"):
            ops._interpret(None)
    else:
        assert ops._interpret(None) is interpret
    assert ops._interpret(True) is True  # an explicit choice is kept
