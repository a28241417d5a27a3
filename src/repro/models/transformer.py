"""One configurable stack for every architecture in ``configs/``.

Layer heterogeneity (attention / RWKV6 / Mamba-1 / Mamba-2 mixers, dense /
MoE / dense+MoE FFNs, encoder-decoder, M-RoPE, sliding windows) is expressed as a
repeating block *pattern* (configs/base.py). Weights for each pattern
position are stacked along a leading ``n_repeats`` axis and the stack runs
under ``lax.scan`` — compiled HLO size is O(pattern length), not O(depth),
which keeps 72-layer Jamba and 56-layer Mixtral dry-runs fast. Granite's
multipliers scale the input embedding, the attention scores, each residual
branch and the logits where the config sets them.

Three entry points per model: ``forward_train`` (full causal sequence),
``prefill`` (returns decode state + last-position logits), ``decode_step``
(one token against the state). Decode state per pattern position:
  attn  : k/v ring caches  [B, S_cache, KV, Dh]
  rwkv6 : wkv state [B, H, Dh, Dh] (fp32) + token-shift carries [B, D]
  mamba : ssm state [B, Di, St] (fp32) + conv tail [B, K-1, Di]
  mamba2: ssd state [B, H, P, N] (fp32) + conv tail [B, K-1, Di + 2GN]
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import obs
from ..configs.base import LayerKind, ModelConfig
from ..distributed.sharding import ShardingRules
from ..kernels import ref
from . import ssm
from .attention import attention, cache_insert, decode_attention
from .layers import apply_mrope, apply_rope, gated_rmsnorm, rmsnorm, swiglu
from .moe import moe_ffn
from .params import ParamDef


# ===================================================================== specs
def _null_spec(*_args) -> P:
    return P()


class _NullRules:
    """Spec provider for unsharded runs (single-device smoke tests)."""

    def __getattr__(self, name):
        return P()

    kv_cache = staticmethod(_null_spec)
    ssm_state = staticmethod(_null_spec)
    w_expert_in = staticmethod(_null_spec)
    w_expert_out = staticmethod(_null_spec)


def _c(x, rules: ShardingRules | None, spec) -> jax.Array:
    """Optional sharding constraint."""
    if rules is None:
        return x
    return rules.constrain(x, spec)


def _use_pallas(cfg: ModelConfig) -> bool:
    """'auto' -> only on real TPU backends; 'on' forces the kernels (they run
    in interpret mode on the CPU backend); 'off' keeps the pure-jnp
    blockwise paths (the dry-run default — TPU Pallas calls don't lower on
    the CPU AOT backend)."""
    if cfg.use_pallas == "on":
        return True
    if cfg.use_pallas == "off":
        return False
    return jax.default_backend() == "tpu"


# ================================================================ param defs
def _attn_defs(cfg: ModelConfig, r) -> dict:
    H, KV, Dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    d = {
        "wq": ParamDef((D, H * Dh), r.w_in),
        "wk": ParamDef((D, KV * Dh), r.w_in),
        "wv": ParamDef((D, KV * Dh), r.w_in),
        "wo": ParamDef((H * Dh, D), r.w_out),
    }
    if cfg.qk_norm:
        d["q_norm"] = ParamDef((Dh,), P(), "ones")
        d["k_norm"] = ParamDef((Dh,), P(), "ones")
    return d


def _ffn_defs(cfg: ModelConfig, r) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    return {
        "w1": ParamDef((D, F), r.w_in),
        "w3": ParamDef((D, F), r.w_in),
        "w2": ParamDef((F, D), r.w_out),
    }


def _moe_defs(cfg: ModelConfig, r) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    d = {
        "router": ParamDef((D, E), P()),
        "e_w1": ParamDef((E, D, F), r.w_expert_in(E)),
        "e_w3": ParamDef((E, D, F), r.w_expert_in(E)),
        "e_w2": ParamDef((E, F, D), r.w_expert_out(E)),
    }
    if cfg.moe.dense_residual:
        d["dense"] = _ffn_defs(cfg, r)
    return d


def _rwkv_defs(cfg: ModelConfig, r) -> dict:
    H, Dh, D, F = cfg.n_heads, cfg.head_dim, cfg.d_model, cfg.d_ff
    lora = 64
    return {
        "tm_mu": ParamDef((5, D), P(), "zeros"),
        "tm_wr": ParamDef((D, H * Dh), r.w_in),
        "tm_wk": ParamDef((D, H * Dh), r.w_in),
        "tm_wv": ParamDef((D, H * Dh), r.w_in),
        "tm_wg": ParamDef((D, H * Dh), r.w_in),
        "tm_wo": ParamDef((H * Dh, D), r.w_out),
        "tm_w0": ParamDef((D,), P(), "normal", 1.0),
        "tm_w1": ParamDef((D, lora), P(), "zeros"),
        "tm_w2": ParamDef((lora, D), P(), "zeros"),
        "tm_u": ParamDef((H, Dh), P(), "normal", 0.5),
        "tm_ln": ParamDef((H * Dh,), P(), "ones"),
        "cm_mu": ParamDef((2, D), P(), "zeros"),
        "cm_k": ParamDef((D, F), r.w_in),
        "cm_v": ParamDef((F, D), r.w_out),
        "cm_r": ParamDef((D, D), P()),
    }


def _mamba_defs(cfg: ModelConfig, r) -> dict:
    D = cfg.d_model
    Di, St, K = cfg.mamba_d_inner, cfg.mamba.d_state, cfg.mamba.d_conv
    Rdt = max(1, Di // 16)
    tp_name = None if isinstance(r, _NullRules) else r.tp
    tp, tp0 = P(tp_name), P(tp_name, None)  # Di-leading shardings
    return {
        "in_proj": ParamDef((D, 2 * Di), r.w_in),
        "conv_w": ParamDef((Di, K), tp0, "normal", 0.5),
        "conv_b": ParamDef((Di,), tp, "zeros"),
        "x_proj": ParamDef((Di, Rdt + 2 * St), tp0),
        "dt_proj": ParamDef((Rdt, Di), P(None, tp_name)),
        "dt_bias": ParamDef((Di,), tp, "zeros"),
        "a_log": ParamDef((Di, St), tp0, "mamba_a"),
        "d_skip": ParamDef((Di,), tp, "ones"),
        "out_proj": ParamDef((Di, D), r.w_out),
    }


def _mamba2_defs(cfg: ModelConfig, r) -> dict:
    m, D, Di = cfg.mamba, cfg.d_model, cfg.mamba_d_inner
    C = Di + 2 * m.n_groups * m.d_state  # conv channels: x | B | C
    return {
        "in_proj": ParamDef((D, Di + C + m.n_heads), r.w_in),  # z | x B C | dt
        "conv_w": ParamDef((C, m.d_conv), P(), "normal", 0.5),
        "conv_b": ParamDef((C,), P(), "normal", 0.1),
        "dt_bias": ParamDef((m.n_heads,), P(), "normal", 0.5),
        "a_log": ParamDef((m.n_heads,), P(), "normal", 0.5),
        "d_skip": ParamDef((m.n_heads,), P(), "ones"),
        "norm": ParamDef((Di,), P(), "ones"),
        "out_proj": ParamDef((Di, D), r.w_out),
    }


def _block_defs(cfg: ModelConfig, r, kind: LayerKind, cross_attn: bool = False) -> dict:
    D = cfg.d_model
    d: dict[str, Any] = {"ln1": ParamDef((D,), P(), "ones")}
    if kind.mixer == "attn":
        d["attn"] = _attn_defs(cfg, r)
    elif kind.mixer == "rwkv6":
        d["rwkv"] = _rwkv_defs(cfg, r)
        d["ln2"] = ParamDef((D,), P(), "ones")
        return d  # rwkv block = time-mix + channel-mix, no swiglu/moe
    elif kind.mixer == "mamba":
        d["mamba"] = _mamba_defs(cfg, r)
    elif kind.mixer == "mamba2":
        d["mamba2"] = _mamba2_defs(cfg, r)
    if cross_attn:
        d["ln_x"] = ParamDef((D,), P(), "ones")
        d["xattn"] = _attn_defs(cfg, r)
    d["ln2"] = ParamDef((D,), P(), "ones")
    d["moe" if kind.moe else "ffn"] = (
        _moe_defs(cfg, r) if kind.moe else _ffn_defs(cfg, r)
    )
    return d


def _stack(defs: dict, n: int) -> dict:
    def one(d: ParamDef) -> ParamDef:
        return ParamDef((n,) + d.shape, P(None, *tuple(d.spec)), d.init, d.scale)

    return {
        k: one(v) if isinstance(v, ParamDef) else _stack(v, n)
        for k, v in defs.items()
    }


def param_defs(cfg: ModelConfig, rules: ShardingRules | None = None) -> dict:
    r = rules if rules is not None else _NullRules()
    D, Vp = cfg.d_model, cfg.padded_vocab
    defs: dict[str, Any] = {
        # the embedding as the layers see it, times the multiplier, has std
        # 0.02 (a random model with a larger one predicts its input token)
        "embed": ParamDef((Vp, D), r.embed, "normal", 0.02 / cfg.embedding_multiplier),
        "final_norm": ParamDef((D,), P(), "ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((D, Vp), r.lm_head, "normal", 0.02)
    blocks = {
        f"p{i}": _block_defs(cfg, r, kind, cross_attn=cfg.enc_dec)
        for i, kind in enumerate(cfg.pattern)
    }
    defs["blocks"] = _stack(blocks, cfg.n_repeats)
    if cfg.enc_dec:
        enc_block = _block_defs(cfg, r, LayerKind("attn"), cross_attn=False)
        defs["enc_blocks"] = _stack({"p0": enc_block}, cfg.n_enc_layers)
        defs["enc_final_norm"] = ParamDef((D,), P(), "ones")
    return defs


def cache_defs(
    cfg: ModelConfig, rules, batch: int, cache_len: int, enc_len: int = 0
) -> dict:
    """ParamDef tree matching the decode-state structure that ``prefill``
    produces — used to build ShapeDtypeStructs for the decode dry-run without
    running prefill. Dtypes: KV/conv/shift bf16 (via the dtype argument of
    :func:`abstract_cache`), SSM states fp32 (marked via ``init='fp32'``).
    A hybrid model keeps K/V at its attention positions and state and conv
    tail at its Mamba positions, side by side in one tree."""
    r = rules if rules is not None else _NullRules()
    shardable = batch >= 8
    kv = r.kv_cache(shardable) if rules is not None else P()
    st_spec = r.ssm_state(shardable) if rules is not None else P()
    dp = r._dp() if (rules is not None and batch >= 8) else None
    H, KV, Dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    Di, St, K = cfg.mamba_d_inner, cfg.mamba.d_state, cfg.mamba.d_conv
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    out = {}
    for i, kind in enumerate(cfg.pattern):
        d: dict[str, Any] = {}
        if kind.mixer == "attn":
            d["k"] = ParamDef((batch, eff_len, KV, Dh), kv)
            d["v"] = ParamDef((batch, eff_len, KV, Dh), kv)
            if cfg.enc_dec:
                d["xk"] = ParamDef((batch, enc_len, KV, Dh), kv)
                d["xv"] = ParamDef((batch, enc_len, KV, Dh), kv)
        elif kind.mixer == "rwkv6":
            d["wkv"] = ParamDef(
                (batch, H, Dh, Dh),
                P(*tuple(st_spec), None, None) if rules is not None else P(),
                "fp32",
            )
            d["shift_t"] = ParamDef((batch, D), P(dp, None) if rules else P())
            d["shift_c"] = ParamDef((batch, D), P(dp, None) if rules else P())
        elif kind.mixer == "mamba2":
            m = cfg.mamba
            d["h"] = ParamDef(
                (batch, m.n_heads, m.d_head, m.d_state),
                P(*tuple(st_spec), None, None) if rules is not None else P(),
                "fp32",
            )
            d["conv"] = ParamDef(
                (batch, m.d_conv - 1, Di + 2 * m.n_groups * m.d_state),
                P(dp, None, None) if rules is not None else P(),
            )
        else:  # mamba
            d["h"] = ParamDef(
                (batch, Di, St),
                P(*tuple(st_spec), None) if rules is not None else P(),
                "fp32",
            )
            d["conv"] = ParamDef(
                (batch, K - 1, Di),
                P(dp, None, r.tp) if rules is not None else P(),
            )
        out[f"p{i}"] = d
    return _stack(out, cfg.n_repeats)


def abstract_cache(cfg: ModelConfig, rules, batch: int, cache_len: int,
                   enc_len: int = 0, mesh=None, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct tree for the decode state (dry-run input). Adds its
    bytes to the counters ``cache.kv_bytes`` (keys and values) and
    ``cache.state_bytes`` (recurrent states, conv tails, token shifts)."""
    from jax.sharding import NamedSharding

    defs = cache_defs(cfg, rules, batch, cache_len, enc_len)

    def walk(node):
        out = {}
        for k, v in node.items():
            if isinstance(v, ParamDef):
                dt = jnp.float32 if v.init == "fp32" else dtype
                kind = "kv" if k in ("k", "v", "xk", "xv") else "state"
                obs.count(f"cache.{kind}_bytes",
                          math.prod(v.shape) * jnp.dtype(dt).itemsize)
                if mesh is not None:
                    out[k] = jax.ShapeDtypeStruct(
                        v.shape, dt, sharding=NamedSharding(mesh, v.spec)
                    )
                else:
                    out[k] = jax.ShapeDtypeStruct(v.shape, dt)
            else:
                out[k] = walk(v)
        return out

    return walk(defs)


# ================================================================== context
@dataclass
class Ctx:
    mode: str  # 'train' | 'prefill' | 'decode'
    positions: jax.Array | None = None  # [B, S]
    positions3: jax.Array | None = None  # [3, B, S] (M-RoPE)
    pos: jax.Array | None = None  # scalar, decode
    enc_memory: jax.Array | None = None  # [B, S_enc, D]
    cache_len: int = 0
    causal: bool = True
    batch_shardable: bool = True
    aux: list = field(default_factory=list)


# ================================================================ sub-layers
def _project_qkv(cfg, p_attn, h):
    B, S, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p_attn["wq"]).reshape(B, S, H, Dh)
    k = (h @ p_attn["wk"]).reshape(B, S, KV, Dh)
    v = (h @ p_attn["wv"]).reshape(B, S, KV, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p_attn["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p_attn["k_norm"], cfg.norm_eps)
    return q, k, v


def _rope(cfg, ctx: Ctx, q, k):
    if not cfg.rope:
        return q, k
    if cfg.mrope_sections:
        pos3 = ctx.positions3
        if pos3 is None:  # decode: same position on all three streams
            pos3 = jnp.broadcast_to(ctx.pos, (3, q.shape[0], q.shape[1])).astype(jnp.int32)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        return q, k
    pos = ctx.positions
    if pos is None:
        pos = jnp.full((q.shape[0], q.shape[1]), ctx.pos, jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    return q, k


def _self_attention(cfg, rules, p, x, ctx: Ctx, cache):
    """Returns (mixer_out, new_cache_entries)."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, p["attn"], h)
    q, k = _rope(cfg, ctx, q, k)
    new_cache = {}
    if ctx.mode == "decode":
        # the layer's cache is read only; _decode_blocks writes the new rows
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        out = decode_attention(q, cache["k"], cache["v"], ctx.pos, k, v,
                               scale=cfg.attn_scale)
        new_cache = {"k": k, "v": v}
    else:
        if _use_pallas(cfg) and q.shape[1] % 64 == 0:
            from ..kernels.ops import flash_attention
            out = flash_attention(q, k, v, ctx.causal, cfg.sliding_window,
                                  rules=rules, scale=cfg.attn_scale)
        else:
            out = attention(
                q, k, v, causal=ctx.causal, window=cfg.sliding_window,
                q_chunk=cfg.attn_q_chunk, unroll_chunks=cfg.attn_unroll_chunks,
                scale=cfg.attn_scale,
            )
        if ctx.mode == "prefill":
            new_cache = _prefill_kv_cache(cfg, rules, ctx, k, v)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim) @ p["attn"]["wo"]
    return out, new_cache


def _prefill_kv_cache(cfg, rules, ctx: Ctx, k, v):
    B, S, KV, Dh = k.shape
    L = ctx.cache_len
    spec = rules.kv_cache(ctx.batch_shardable) if rules is not None else None

    def build(t):
        buf = jnp.zeros((B, L, KV, Dh), t.dtype)
        if cfg.sliding_window is not None and S > L:
            # ring discipline: token s lives at slot s % L
            tail = t[:, S - L :]
            slots = jnp.mod(jnp.arange(S - L, S), L)
            buf = buf.at[:, slots].set(tail)
        else:
            buf = jax.lax.dynamic_update_slice(buf, t[:, :L], (0, 0, 0, 0))
        return buf if spec is None else rules.constrain(buf, spec)

    return {"k": build(k), "v": build(v)}


def _cross_attention(cfg, rules, p, x, ctx: Ctx, cache):
    h = rmsnorm(x, p["ln_x"], cfg.norm_eps)
    B, S, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p["xattn"]["wq"]).reshape(B, S, H, Dh)
    new_cache = {}
    if ctx.mode == "decode":  # the encoder's K/V, read only
        xk, xv = cache["xk"], cache["xv"]
    else:
        mem = ctx.enc_memory
        xk = (mem @ p["xattn"]["wk"]).reshape(B, -1, KV, Dh)
        xv = (mem @ p["xattn"]["wv"]).reshape(B, -1, KV, Dh)
        if ctx.mode == "prefill":
            new_cache = {"xk": xk, "xv": xv}
    out = attention(q, xk, xv, causal=False, q_chunk=cfg.attn_q_chunk)
    out = out.reshape(B, S, H * Dh) @ p["xattn"]["wo"]
    return out, new_cache


def _shift(x, prev):
    """Token shift: x_{t-1} with ``prev`` as the t=0 predecessor."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev.astype(x.dtype), x[:, :-1]], axis=1)


def _rwkv_block(cfg, rules, p, x, ctx: Ctx, cache):
    """RWKV6 layer: time-mix + channel-mix (its own FFN form)."""
    pr = p["rwkv"]
    H, Dh, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    B, S, _ = x.shape
    decode = ctx.mode == "decode"
    # ---- time mix
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    prev_t = cache["shift_t"][:, None, :] if cache else None
    hh = _shift(h, prev_t)
    mu = pr["tm_mu"]
    def lerp(i):
        return h + (hh - h) * mu[i][None, None, :]
    r = (lerp(0) @ pr["tm_wr"]).reshape(B, S, H, Dh)
    k = (lerp(1) @ pr["tm_wk"]).reshape(B, S, H, Dh)
    v = (lerp(2) @ pr["tm_wv"]).reshape(B, S, H, Dh)
    w_raw = pr["tm_w0"][None, None, :] + jnp.tanh(lerp(3) @ pr["tm_w1"]) @ pr["tm_w2"]
    logw = ssm.rwkv6_decay(w_raw).reshape(B, S, H, Dh)
    g = jax.nn.silu(lerp(4) @ pr["tm_wg"])
    state0 = cache["wkv"] if cache else None
    if decode:
        out1, wkv = ssm.rwkv6_step(
            r[:, 0], k[:, 0], v[:, 0], logw[:, 0], pr["tm_u"], state0
        )
        out = out1[:, None].astype(x.dtype)
    elif _use_pallas(cfg) and S % ssm.RWKV_CHUNK == 0:
        from ..kernels.ops import rwkv6 as rwkv6_kernel
        s0 = state0 if state0 is not None else jnp.zeros(
            (B, H, Dh, Dh), jnp.float32
        )
        out, wkv = rwkv6_kernel(r, k, v, logw.astype(r.dtype), pr["tm_u"], s0,
                                rules=rules)
    else:
        out, wkv = ssm.rwkv6_chunked(r, k, v, logw, pr["tm_u"], state0)
    out = rmsnorm(out.reshape(B, S, H * Dh), pr["tm_ln"], cfg.norm_eps) * g
    x = x + out @ pr["tm_wo"]
    # ---- channel mix
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    prev_c = cache["shift_c"][:, None, :] if cache else None
    hh2 = _shift(h2, prev_c)
    cmu = pr["cm_mu"]
    xk_ = h2 + (hh2 - h2) * cmu[0][None, None, :]
    xr_ = h2 + (hh2 - h2) * cmu[1][None, None, :]
    kk = jnp.square(jax.nn.relu(xk_ @ pr["cm_k"]))
    out2 = jax.nn.sigmoid(xr_ @ pr["cm_r"]) * (kk @ pr["cm_v"])
    x = x + out2
    new_cache = {}
    if ctx.mode in ("prefill", "decode"):
        new_cache = {
            "wkv": wkv,
            "shift_t": h[:, -1, :],
            "shift_c": h2[:, -1, :],
        }
    return x, new_cache


def _mamba_mixer(cfg, rules, p, x, ctx: Ctx, cache):
    pm = p["mamba"]
    Di, St, K = cfg.mamba_d_inner, cfg.mamba.d_state, cfg.mamba.d_conv
    Rdt = max(1, Di // 16)
    B, S, _ = x.shape
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    xz = h @ pm["in_proj"]  # [B, S, 2Di]
    xr, z = jnp.split(xz, 2, axis=-1)
    conv_state = cache["conv"] if cache else None
    xr_conv = ssm.mamba_conv(xr, pm["conv_w"], pm["conv_b"], conv_state)
    u = jax.nn.silu(xr_conv)
    dbl = u @ pm["x_proj"]  # [B, S, Rdt + 2 St]
    dt_r = dbl[..., :Rdt]
    B_ = dbl[..., Rdt : Rdt + St].astype(jnp.float32)
    C_ = dbl[..., Rdt + St :].astype(jnp.float32)
    dt = jax.nn.softplus(dt_r @ pm["dt_proj"] + pm["dt_bias"][None, None, :])
    A = -jnp.exp(pm["a_log"].astype(jnp.float32))
    h0 = cache["h"] if cache else None
    if ctx.mode == "decode":
        y1, hs = ssm.mamba_step(u[:, 0], dt[:, 0], A, B_[:, 0], C_[:, 0], h0)
        y = y1[:, None].astype(x.dtype)
    elif _use_pallas(cfg) and S % 64 == 0 and Di % 64 == 0:
        from ..kernels.ops import mamba_scan
        h00 = h0 if h0 is not None else jnp.zeros((B, Di, St), jnp.float32)
        y, hs = mamba_scan(u, dt, A, B_.astype(u.dtype), C_.astype(u.dtype), h00,
                           rules=rules)
    else:
        y, hs = ssm.mamba_scan_chunked(u, dt, A, B_, C_, h0)
    y = y + pm["d_skip"][None, None, :] * u
    y = y * jax.nn.silu(z)
    out = y @ pm["out_proj"]
    new_cache = {}
    if ctx.mode in ("prefill", "decode"):
        new_cache = {"h": hs, "conv": _conv_tail(ctx, cache, xr, K)}
    return out, new_cache


def _conv_tail(ctx: Ctx, cache, xr, K: int):
    """The last K-1 inputs of the causal conv, its state for the next token."""
    if ctx.mode == "decode":
        return jnp.concatenate(
            [cache["conv"][:, 1:], xr[:, -1:, :].astype(cache["conv"].dtype)], axis=1)
    B, S, C = xr.shape
    pad = jnp.zeros((B, max(0, K - 1 - S), C), xr.dtype)
    return jnp.concatenate([pad, xr[:, -(K - 1):, :]], axis=1)


def _mamba2_mixer(cfg, rules, p, x, ctx: Ctx, cache):
    """Mamba-2 (arXiv:2405.21060; Hugging Face ``GraniteMoeHybridMambaLayer``):
    in_proj to z, x|B|C and dt; causal depthwise conv with bias and silu over
    x|B|C; dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD scan (the
    Pallas kernel on TPU, its chunked jnp form elsewhere, one step in decode)
    plus the D skip; RMSNorm of y * silu(z); out_proj."""
    pm, m = p["mamba2"], cfg.mamba
    H, G, N, Ph, K = m.n_heads, m.n_groups, m.d_state, m.d_head, m.d_conv
    Di = cfg.mamba_d_inner
    B, S, _ = x.shape
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    z, xbc, dt = jnp.split(h @ pm["in_proj"], [Di, 2 * Di + 2 * G * N], axis=-1)
    conv = jax.nn.silu(ssm.mamba_conv(xbc, pm["conv_w"], pm["conv_b"],
                                      cache["conv"] if cache else None))
    xs, b_, c_ = jnp.split(conv, [Di, Di + G * N], axis=-1)
    xs = xs.reshape(B, S, H, Ph)
    b_, c_ = b_.reshape(B, S, G, N), c_.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + pm["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(pm["a_log"].astype(jnp.float32))
    if ctx.mode == "decode":
        y, hs = ssm.ssd_step(xs[:, 0], dt[:, 0], A, b_[:, 0], c_[:, 0], cache["h"])
        y = y[:, None]
    else:
        h0 = jnp.zeros((B, H, Ph, N), jnp.float32)
        if _use_pallas(cfg):
            from ..kernels.ops import ssd
            y, hs = ssd(xs, dt, A, b_, c_, h0, chunk=m.chunk, rules=rules)
        else:
            y, hs = ref.ssd_chunked(xs, dt, A, b_, c_, h0, chunk=m.chunk)
    y = y + pm["d_skip"][:, None].astype(y.dtype) * xs
    y = gated_rmsnorm(y.reshape(B, S, Di), z, pm["norm"], cfg.norm_eps)
    out = y @ pm["out_proj"]
    new_cache = {}
    if ctx.mode in ("prefill", "decode"):
        new_cache = {"h": hs, "conv": _conv_tail(ctx, cache, xbc, K)}
    return out, new_cache


def _residual(cfg, branch):
    m = cfg.residual_multiplier
    return branch if m == 1 else branch * m


def _ffn_or_moe(cfg, rules, kind: LayerKind, p, x, ctx: Ctx):
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if kind.moe:
        pm = p["moe"]
        out, aux = moe_ffn(
            h, pm["router"], pm["e_w1"], pm["e_w3"], pm["e_w2"], cfg.moe
        )
        if cfg.moe.dense_residual:
            d = pm["dense"]
            out = out + swiglu(h, d["w1"], d["w3"], d["w2"])
        return out, aux
    f = p["ffn"]
    return swiglu(h, f["w1"], f["w3"], f["w2"]), jnp.zeros((), jnp.float32)


def apply_block(cfg, rules, kind: LayerKind, p, x, ctx: Ctx, cache):
    """One pattern-position layer. Returns (x, new_cache, aux_loss)."""
    if kind.mixer == "rwkv6":
        x, new_cache = _rwkv_block(cfg, rules, p, x, ctx, cache)
        return _c(x, rules, rules.residual if rules else None), new_cache, jnp.zeros((), jnp.float32)
    if kind.mixer == "attn":
        with jax.named_scope("attention"):
            mix, new_cache = _self_attention(cfg, rules, p, x, ctx, cache)
    elif kind.mixer == "mamba2":
        with jax.named_scope("mamba2"):
            mix, new_cache = _mamba2_mixer(cfg, rules, p, x, ctx, cache)
    else:
        mix, new_cache = _mamba_mixer(cfg, rules, p, x, ctx, cache)
    x = x + _residual(cfg, mix)
    if cfg.enc_dec and "xattn" in p:
        xmix, xcache = _cross_attention(cfg, rules, p, x, ctx, cache)
        x = x + xmix
        new_cache = {**new_cache, **xcache}
    with jax.named_scope("ffn"):
        ffn_out, aux = _ffn_or_moe(cfg, rules, kind, p, x, ctx)
    x = x + _residual(cfg, ffn_out)
    x = _c(x, rules, rules.residual if rules else None)
    return x, new_cache, aux


# ================================================================ stacks
def _run_blocks(cfg, rules, blocks, x, ctx: Ctx, pattern=None):
    """Scan the stacked pattern blocks over a whole sequence (train and
    prefill). Returns (x, new_caches, aux_total); new_caches is the decode
    state that prefill builds, None in training."""
    pattern = pattern if pattern is not None else cfg.pattern

    def body(carry, layer_params):
        x, aux = carry
        new_cache = {}
        for i, kind in enumerate(pattern):
            key = f"p{i}"
            x, nc, a = apply_block(cfg, rules, kind, layer_params[key], x, ctx, None)
            aux = aux + a
            new_cache[key] = nc
        return (x, aux), new_cache if new_cache and any(new_cache.values()) else None

    fn = jax.checkpoint(body) if (cfg.remat and ctx.mode == "train") else body
    if cfg.scan_layers:
        (x, aux), new_caches = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), blocks)
        return x, new_caches, aux
    # unrolled path (debugging + dry-run cost modules)
    n = jax.tree.leaves(blocks)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    outs = []
    for rep in range(n):
        lp = jax.tree.map(lambda t: t[rep], blocks)
        (x, aux), nc = fn((x, aux), lp)
        outs.append(nc)
    new_caches = (
        jax.tree.map(lambda *ts: jnp.stack(ts), *outs) if outs and outs[0] else None
    )
    return x, new_caches, aux


def _decode_blocks(cfg, rules, blocks, x, ctx: Ctx, caches):
    """Decode's layer loop. The stacked decode state rides in the loop's
    carry: layer ``i`` reads its slice, and writes back only what it changed,
    an attention layer its new token's K/V row at slot ``pos`` mod S_cache,
    a recurrent layer its state, conv tail or token shifts whole; the
    encoder's K/V pass through. A donated state is so updated in place, with
    no pass over a whole stack. Tracing adds the bytes one step writes to
    the counter ``decode.cache_write_bytes``. Returns (x, new_caches)."""
    n = jax.tree.leaves(blocks)[0].shape[0]
    written = {}  # pattern key -> bytes one layer writes

    def body(carry, xs):
        x, state = carry
        layer_params, i = xs
        state = dict(state)
        for j, kind in enumerate(cfg.pattern):
            key = f"p{j}"
            stacks = state[key]
            layer = {name: jax.lax.dynamic_index_in_dim(t, i, keepdims=False)
                     for name, t in stacks.items()}
            x, new, _ = apply_block(cfg, rules, kind, layer_params[key], x, ctx, layer)
            written[key] = sum(v.size * stacks[name].dtype.itemsize for name, v in new.items())
            state[key] = {
                name: t if name not in new
                else cache_insert(t, new[name], i, ctx.pos) if name in ("k", "v")
                else jax.lax.dynamic_update_index_in_dim(t, new[name].astype(t.dtype), i, 0)
                for name, t in stacks.items()
            }
        return (x, state), None

    if cfg.scan_layers:
        (x, caches), _ = jax.lax.scan(body, (x, caches), (blocks, jnp.arange(n)))
    else:  # unrolled path (debugging + dry-run cost modules)
        for rep in range(n):
            (x, caches), _ = body((x, caches), (jax.tree.map(lambda t: t[rep], blocks), rep))
    obs.count("decode.cache_write_bytes", n * sum(written.values()))
    return x, caches


def _embed(cfg, params, tokens) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    m = cfg.embedding_multiplier
    return x if m == 1 else x * m


@jax.named_scope("embed")
def _embed_inputs(cfg, params, batch) -> jax.Array:
    x = _embed(cfg, params, batch["tokens"])
    if cfg.vision_len_ratio and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)  # [B, Sv, D]
        x = jnp.concatenate([ve, x[:, ve.shape[1]:, :]], axis=1)
    return x


def _encode(cfg, rules, params, batch, ctx_mode: str):
    """Run the encoder stack over precomputed frame embeddings."""
    enc_x = batch["encoder_embeds"].astype(params["enc_final_norm"].dtype)
    ectx = Ctx(mode="train", causal=False)
    enc_x, _, _ = _run_blocks(
        cfg, rules, params["enc_blocks"], enc_x, ectx, pattern=(LayerKind("attn"),),
    )
    return rmsnorm(enc_x, params["enc_final_norm"], cfg.norm_eps)


@jax.named_scope("head")
def _logits(cfg, params, x) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return logits if cfg.logits_scaling == 1 else logits / cfg.logits_scaling


# ================================================================ entry points
def forward_train(cfg: ModelConfig, rules, params, batch) -> tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward. Returns (logits [B,S,Vp], aux_loss)."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ctx = Ctx(mode="train", positions=positions,
              positions3=batch.get("positions3"))
    if cfg.enc_dec:
        ctx.enc_memory = _encode(cfg, rules, params, batch, "train")
    x = _c(x, rules, rules.residual if rules else None)
    x, _, aux = _run_blocks(cfg, rules, params["blocks"], x, ctx)
    return _logits(cfg, params, x), aux


def prefill(cfg: ModelConfig, rules, params, batch, cache_len: int):
    """Process a full prompt; returns (state, last-token logits [B,Vp])."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    eff_cache = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    ctx = Ctx(mode="prefill", positions=positions,
              positions3=batch.get("positions3"), cache_len=eff_cache,
              batch_shardable=B >= 8)
    if cfg.enc_dec:
        ctx.enc_memory = _encode(cfg, rules, params, batch, "prefill")
    x = _c(x, rules, rules.residual if rules else None)
    x, caches, _ = _run_blocks(cfg, rules, params["blocks"], x, ctx)
    logits = _logits(cfg, params, x[:, -1:, :])[:, 0]
    return caches, logits


def decode_step(cfg: ModelConfig, rules, params, caches, token, pos):
    """One decode step. token [B,1] int32; pos scalar int32 (position of the
    new token). Returns (logits [B,Vp], new_caches)."""
    x = _embed(cfg, params, token)
    ctx = Ctx(mode="decode", pos=pos,
              batch_shardable=token.shape[0] >= 8)
    x, new_caches = _decode_blocks(cfg, rules, params["blocks"], x, ctx, caches)
    return _logits(cfg, params, x)[:, 0], new_caches
