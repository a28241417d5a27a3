"""Jitted public wrappers around the Pallas kernels.

Layout adaptation ([B,S,H,Dh] model convention <-> [B,H,S,Dh] kernel
convention), backend dispatch (``interpret=True`` automatically on the CPU
backend so the kernels execute there; any backend other than TPU or CPU is
an error, never a silent fallback), and custom_vjp wiring: forward runs the
kernel, backward rematerializes through the pure-jnp reference — exact same
math, so gradients are correct while the hot forward path uses the
hand-tiled kernel.

Mosaic kernels cannot be partitioned by the compiler. Given sharding
``rules``, each wrapper runs its kernel under ``shard_map``: batch over the
data-parallel axes and heads (attention, RWKV6, SSD) or channels (Mamba)
over the tensor-parallel axis, each where the size divides. Every shard is then
an independent instance of the same recurrence, so the math is unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ref
from .flash_attention import flash_attention_bhsd
from .mamba import mamba_scan_bsd
from .rwkv6 import rwkv6_bhsd
from .ssd import ssd_bshp


def _interpret(flag: bool | None) -> bool:
    if flag is not None:
        return flag
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels have no path on the {backend!r} backend"
    )


def _axis(rules, name, size: int):
    """``name`` if it is a mesh axis (or tuple of axes) whose size divides
    ``size``; otherwise None (that dimension stays whole on every shard)."""
    if name is None:
        return None
    names = name if isinstance(name, tuple) else (name,)
    n = math.prod(rules.mesh.shape[a] for a in names)
    return name if size % n == 0 else None


def _shard(fn, rules, in_specs, out_specs):
    return jax.shard_map(fn, mesh=rules.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------ flash attention
def flash_attention(q, k, v, causal=True, window=None, interpret=None, rules=None,
                    scale=None):
    """q [B,Sq,H,Dh]; k/v [B,Sk,KV,Dh] -> [B,Sq,H,Dh]; scores scaled by
    ``scale`` (Dh ** -0.5 where None)."""
    fn = functools.partial(_flash_attention, causal=causal, window=window,
                           interpret=interpret,
                           scale=q.shape[3] ** -0.5 if scale is None else scale)
    if rules is None:
        return fn(q, k, v)
    dp = _axis(rules, rules.batch[0], q.shape[0])
    tp = _axis(rules, rules.tp, q.shape[2])
    if tp is not None and _axis(rules, tp, k.shape[2]) is None:
        # kv heads do not split like q heads: give every q head its kv head
        # so that each shard's GQA grouping stays local
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    spec = P(dp, None, tp, None)
    return _shard(fn, rules, (spec, spec, spec), spec)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, interpret, scale):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq, bk = flash_tiles(q.shape[1], k.shape[1], q.shape[3], q.dtype)
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(interpret),
    )
    return jnp.swapaxes(out, 1, 2)


def _tile(s: int, align: int, cap: int) -> int:
    """The largest multiple of ``align`` that divides ``s`` and is at most
    ``cap``; ``s`` itself where there is none."""
    for t in range(min(cap, s) // align * align, 0, -align):
        if s % t == 0:
            return t
    return s


def flash_tiles(sq: int, sk: int, d: int, dtype) -> tuple[int, int]:
    """(bq, bk) of the flash kernel: the largest divisors of the lengths
    that are multiples of the dtype's sublane tile (8 rows of 32 bits), up
    to a 1024 x 1024 score tile, with fewer query rows past Dh 128 so that
    the kernel stays within the default scoped VMEM. On a TPU v5e the
    largest such tile was the fastest at every served and trained length
    (PERF.md); a lane width that is not a multiple of 128 (992 at 3968) is
    padded, and still beats the 128-wide tiles that divide such lengths."""
    sublane = 32 // jnp.dtype(dtype).itemsize
    return (_tile(sq, sublane, 1024 * 128 // max(d, 128)),
            _tile(sk, sublane, 1024))


def _fa_fwd(q, k, v, causal, window, interpret, scale):
    return _flash_attention(q, k, v, causal, window, interpret, scale), (q, k, v)


def _fa_bwd(causal, window, interpret, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal, window,
                                                         scale), q, k, v)
    return vjp(g)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ------------------------------------------------------------------- rwkv6
def rwkv6(r, k, v, logw, u, state0, interpret=None, rules=None):
    """All inputs [B,S,H,Dh] (u: [H,Dh]; state0: [B,H,Dh,Dh] fp32).
    Returns (out [B,S,H,Dh], state [B,H,Dh,Dh])."""
    fn = functools.partial(_rwkv6, interpret=interpret)
    if rules is None:
        return fn(r, k, v, logw, u, state0)
    dp = _axis(rules, rules.batch[0], r.shape[0])
    tp = _axis(rules, rules.tp, r.shape[2])
    seq, st = P(dp, None, tp, None), P(dp, tp, None, None)
    return _shard(fn, rules, (seq, seq, seq, seq, P(tp, None), st),
                  (seq, st))(r, k, v, logw, u, state0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _rwkv6(r, k, v, logw, u, state0, interpret):
    args = [jnp.swapaxes(t, 1, 2) for t in (r, k, v, logw)]
    out, state = rwkv6_bhsd(*args, u, state0.astype(jnp.float32),
                            interpret=_interpret(interpret))
    return jnp.swapaxes(out, 1, 2), state


def _rwkv_fwd(r, k, v, logw, u, state0, interpret):
    return _rwkv6(r, k, v, logw, u, state0, interpret), (r, k, v, logw, u, state0)


def _rwkv_bwd(interpret, res, g):
    r, k, v, logw, u, state0 = res
    _, vjp = jax.vjp(
        lambda *a: ref.rwkv6_ref(*a), r, k, v, logw, u, state0
    )
    return vjp(g)


_rwkv6.defvjp(_rwkv_fwd, _rwkv_bwd)


# ------------------------------------------------------------------- mamba
def mamba_scan(u, dt, A, B_, C_, h0, interpret=None, rules=None):
    """u/dt [B,S,Di]; A [Di,St]; B_/C_ [B,S,St]; h0 [B,Di,St] fp32.
    Returns (y [B,S,Di], h [B,Di,St])."""
    fn = functools.partial(_mamba_scan, interpret=interpret)
    if rules is None:
        return fn(u, dt, A, B_, C_, h0)
    dp = _axis(rules, rules.batch[0], u.shape[0])
    tp = _axis(rules, rules.tp, u.shape[2])
    seq, st = P(dp, None, tp), P(dp, tp, None)
    bc = P(dp, None, None)
    return _shard(fn, rules, (seq, seq, P(tp, None), bc, bc, st),
                  (seq, st))(u, dt, A, B_, C_, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mamba_scan(u, dt, A, B_, C_, h0, interpret):
    return mamba_scan_bsd(u, dt, A, B_, C_, h0.astype(jnp.float32),
                          interpret=_interpret(interpret))


def _mamba_fwd(u, dt, A, B_, C_, h0, interpret):
    return _mamba_scan(u, dt, A, B_, C_, h0, interpret), (u, dt, A, B_, C_, h0)


def _mamba_bwd(interpret, res, g):
    u, dt, A, B_, C_, h0 = res
    _, vjp = jax.vjp(lambda *a: ref.mamba_ref(*a), u, dt, A, B_, C_, h0)
    return vjp(g)


_mamba_scan.defvjp(_mamba_fwd, _mamba_bwd)


# --------------------------------------------------------------------- ssd
def ssd(x, dt, A, B_, C_, h0, chunk=256, interpret=None, rules=None):
    """Mamba-2's SSD scan. x [B,S,H,P]; dt [B,S,H] fp32; A [H]; B_/C_
    [B,S,G,N]; h0 [B,H,P,N] fp32. Returns (y [B,S,H,P], h [B,H,P,N])."""
    fn = functools.partial(_ssd, chunk=chunk, interpret=interpret)
    if rules is None:
        return fn(x, dt, A, B_, C_, h0)
    dp = _axis(rules, rules.batch[0], x.shape[0])
    # heads split over tp only where every shard keeps whole groups
    tp = _axis(rules, rules.tp, B_.shape[2])
    seq, st, bc = P(dp, None, tp, None), P(dp, tp, None, None), P(dp, None, tp, None)
    return _shard(fn, rules, (seq, P(dp, None, tp), P(tp), bc, bc, st),
                  (seq, st))(x, dt, A, B_, C_, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, B_, C_, h0, chunk, interpret):
    return ssd_bshp(x, dt, A, B_, C_, h0, chunk=chunk,
                    interpret=_interpret(interpret))


def _ssd_fwd(x, dt, A, B_, C_, h0, chunk, interpret):
    return _ssd(x, dt, A, B_, C_, h0, chunk, interpret), (x, dt, A, B_, C_, h0)


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(lambda *a: ref.ssd_chunked(*a, chunk=chunk), *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)
