"""Plain float32 reference of a GraniteMoeHybrid decoder without experts
(Granite 4.0-H): Mamba-2 layers and attention layers with no position
embedding, each followed by a SwiGLU feed-forward.

Written from the published description (the Mamba-2 paper, arXiv:2405.21060,
and Hugging Face's ``GraniteMoeHybrid`` modelling) and from the sizes in the
configuration file, and independent of the program under test: it imports
nothing of it. What it shares with the program is only what a run must
share to be compared: the recipe by which the benchmark makes the weights
from the seed, and the seeded token stream.

Mathematics: the token embedding times ``embedding_multiplier``; per layer
RMSNorm, then the mixer, added to the residual times
``residual_multiplier``; RMSNorm, a SwiGLU feed-forward, added the same
way; a final RMSNorm, the tied embedding as output head, the logits divided
by ``logits_scaling``. ``layer_types`` says which layers attend.

- Attention: q, k and v projections, no rotary embedding, causal
  grouped-query attention with scores scaled by ``attention_multiplier``,
  the output projection.
- Mamba-2: one projection to z, x|B|C and dt; a causal depthwise
  convolution of ``mamba_d_conv`` taps with bias over x|B|C, then silu;
  dt = softplus(dt + dt_bias), A = -exp(A_log) per head; the recurrence
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ, y_t = h_t C_t + D x_t, taken one
  position at a time; RMSNorm of y * silu(z) with its own weight; the output
  projection. Head ``h`` reads the B and C of group ``h // (heads /
  groups)``.

Every matmul runs under ``precision="highest"``; ``prec="fp8"`` rounds
every matmul operand to float8 e4m3 with one scale per tensor, the control
that a lower precision must fail. Attention runs a block of queries at a
time; the layers run under ``lax.scan`` with each period's weights widened
to float32 only inside its step.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.dense_gqa import (  # noqa: F401  (the cell's interface)
    _scale, einsum, flatten, mm, nest, rmsnorm, synthetic_batch, widest_gap)


@dataclass(frozen=True)
class Model:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    period: tuple[str, ...]  # the layer types of one period
    m_heads: int
    m_head_dim: int
    m_state: int
    m_groups: int
    m_conv: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        if c.get("position_embedding_type", "nope") != "nope":
            raise ValueError("this reference has no position embedding")
        if c.get("num_local_experts", 0):
            raise ValueError("this reference has no experts")
        types = tuple(c["layer_types"])
        period = next(p for p in range(1, len(types) + 1)
                      if len(types) % p == 0 and types == types[:p] * (len(types) // p))
        return cls(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                   heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
                   ffn=c.get("shared_intermediate_size") or c["intermediate_size"],
                   vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
                   period=types[:period], m_heads=c["mamba_n_heads"],
                   m_head_dim=c["mamba_d_head"], m_state=c["mamba_d_state"],
                   m_groups=c["mamba_n_groups"], m_conv=c["mamba_d_conv"],
                   embedding_multiplier=float(c["embedding_multiplier"]),
                   attention_multiplier=float(c["attention_multiplier"]),
                   residual_multiplier=float(c["residual_multiplier"]),
                   logits_scaling=float(c["logits_scaling"]))

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.m_groups * self.m_state


# ------------------------------------------------------------------ weights
def leaf_specs(m: Model) -> dict:
    """{path: (shape, init, scale)} of every weight, each position of the
    period stacked over the period's repeats; ``scale`` None means
    fan_in ** -0.5."""
    R, D, F = m.layers // len(m.period), m.hidden, m.ffn
    H, KV, Dh = m.heads, m.kv_heads, m.head_dim
    Di, C, Hm = m.inner, m.conv_channels, m.m_heads
    # the embedding times its multiplier has std 0.02: drawn at 0.02, the
    # x12 token embedding would dominate a random model's last hidden state
    # through the tied head, and nearly every position would predict its own
    # input token with a margin no rounding can flip
    out = {"embed": ((m.padded_vocab, D), "normal", 0.02 / m.embedding_multiplier),
           "final_norm": ((D,), "ones", None)}
    for i, kind in enumerate(m.period):
        p = f"blocks/p{i}"
        out.update({f"{p}/ln1": ((R, D), "ones", None), f"{p}/ln2": ((R, D), "ones", None),
                    f"{p}/ffn/w1": ((R, D, F), "normal", None),
                    f"{p}/ffn/w3": ((R, D, F), "normal", None),
                    f"{p}/ffn/w2": ((R, F, D), "normal", None)})
        if kind == "attention":
            out.update({f"{p}/attn/wq": ((R, D, H * Dh), "normal", None),
                        f"{p}/attn/wk": ((R, D, KV * Dh), "normal", None),
                        f"{p}/attn/wv": ((R, D, KV * Dh), "normal", None),
                        f"{p}/attn/wo": ((R, H * Dh, D), "normal", None)})
        else:
            q = f"{p}/mamba2"
            out.update({f"{q}/in_proj": ((R, D, Di + C + Hm), "normal", None),
                        f"{q}/conv_w": ((R, C, m.m_conv), "normal", 0.5),
                        f"{q}/conv_b": ((R, C), "normal", 0.1),
                        f"{q}/dt_bias": ((R, Hm), "normal", 0.5),
                        f"{q}/a_log": ((R, Hm), "normal", 0.5),
                        f"{q}/d_skip": ((R, Hm), "ones", None),
                        f"{q}/norm": ((R, Di), "ones", None),
                        f"{q}/out_proj": ((R, Di, D), "normal", None)})
    return out


def weights(m: Model, seed: int) -> dict:
    """The weights a run starts from: made on the device in one jitted call
    from the seed, in bfloat16, one key per leaf split from the key of the
    seed's sha256; each leaf normal times its scale, norms and D ones (the
    program's ``init_params`` draws at the same scales)."""
    specs = leaf_specs(m)
    seed32 = int.from_bytes(hashlib.sha256(str(seed).encode()).digest()[:4], "big")

    def make(key):
        keys = jax.random.split(key, len(specs))
        flat = {}
        for k, (path, (shape, init, sc)) in zip(keys, sorted(specs.items())):
            if init == "ones":
                flat[path] = jnp.ones(shape, jnp.bfloat16)
            else:
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * _scale(shape, sc)).astype(jnp.bfloat16)
        return nest(flat)

    return jax.jit(make)(jax.random.PRNGKey(seed32))


# ------------------------------------------------------------------- layers
def attention(m: Model, q, k, v, prec: str, q_block: int = 512):
    """Causal GQA, scores times ``attention_multiplier``: q [B, S, H, Dh],
    k/v [B, S, KV, Dh] -> [B, S, H, Dh], a block of queries at a time."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, Dh)
    kpos = jnp.arange(S)

    def block(qb, k, v, q0):
        s = einsum("bqkgd,bskd->bkgqs", qb, k, prec) * m.attention_multiplier
        qpos = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v, prec)

    block = jax.checkpoint(block, static_argnums=(3,))
    outs = [block(qg[:, q0:q0 + q_block], k, v, q0) for q0 in range(0, S, q_block)]
    return jnp.concatenate(outs, axis=1).reshape(B, S, H, Dh)


def _attention_mixer(m: Model, prec: str, h, a):
    B, S, _ = h.shape
    q = mm(h, a["wq"], prec).reshape(B, S, m.heads, m.head_dim)
    k = mm(h, a["wk"], prec).reshape(B, S, m.kv_heads, m.head_dim)
    v = mm(h, a["wv"], prec).reshape(B, S, m.kv_heads, m.head_dim)
    o = attention(m, q, k, v, prec).reshape(B, S, m.heads * m.head_dim)
    return mm(o, a["wo"], prec)


def _mamba2_mixer(m: Model, prec: str, h, p):
    B, S, _ = h.shape
    Di, H, P, N, G = m.inner, m.m_heads, m.m_head_dim, m.m_state, m.m_groups
    zxd = mm(h, p["in_proj"], prec)
    z, xbc, dt = zxd[..., :Di], zxd[..., Di:Di + m.conv_channels], zxd[..., Di + m.conv_channels:]
    K = m.m_conv
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    # tap k multiplies the input K - 1 - k positions back
    xbc = jax.nn.silu(sum(xp[:, k:k + S] * p["conv_w"][:, k] for k in range(K)) + p["conv_b"])
    x = xbc[..., :Di].reshape(B, S, H, P)
    b = jnp.repeat(xbc[..., Di:Di + G * N].reshape(B, S, G, N), H // G, axis=2)
    c = jnp.repeat(xbc[..., Di + G * N:].reshape(B, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [B, S, H]
    A = -jnp.exp(p["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["d_skip"][:, None] * x
    g = y.reshape(B, S, Di) * jax.nn.silu(z)
    return mm(rmsnorm(g, p["norm"], m.eps), p["out_proj"], prec)


def _period(m: Model, prec: str, x, lp):
    lp = jax.tree.map(lambda t: t.astype(jnp.float32), lp)
    for i, kind in enumerate(m.period):
        p = lp[f"p{i}"]
        h = rmsnorm(x, p["ln1"], m.eps)
        mix = (_attention_mixer(m, prec, h, p["attn"]) if kind == "attention"
               else _mamba2_mixer(m, prec, h, p["mamba2"]))
        x = x + m.residual_multiplier * mix
        f = p["ffn"]
        h = rmsnorm(x, p["ln2"], m.eps)
        ffn = mm(jax.nn.silu(mm(h, f["w1"], prec)) * mm(h, f["w3"], prec), f["w2"], prec)
        x = x + m.residual_multiplier * ffn
    return x


def final_hidden(m: Model, params, tokens, prec: str = "f32"):
    """The final-normed hidden states [B, S, D] of ``tokens`` [B, S]."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x = x * m.embedding_multiplier
    body = jax.checkpoint(lambda x, lp: (_period(m, prec, x, lp), None))
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return rmsnorm(x, params["final_norm"].astype(jnp.float32), m.eps)


def head_logits(m: Model, params, h, prec: str = "f32"):
    """Logits over the real vocabulary of hidden rows ``h`` [..., D]."""
    emb = params["embed"][: m.vocab].astype(jnp.float32)
    return mm(h, emb.T, prec) / m.logits_scaling


def loss(m: Model, params, tokens, prec: str = "f32", row_block: int = 1024):
    """Mean next-token cross-entropy over the real vocabulary, the head taken
    a block of rows at a time."""
    h = final_hidden(m, params, tokens, prec)[:, :-1].reshape(-1, m.hidden)
    gold = tokens[:, 1:].reshape(-1)
    n = h.shape[0]
    pad = -n % row_block
    h = jnp.pad(h, ((0, pad), (0, 0)))
    gold = jnp.pad(gold, (0, pad))
    valid = jnp.arange(n + pad) < n

    @jax.checkpoint
    def block(args):
        hb, gb, vb = args
        lg = head_logits(m, params, hb, prec)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, gb[:, None], 1)[:, 0]
        return jnp.sum(jnp.where(vb, nll, 0.0))

    parts = jax.lax.map(block, (h.reshape(-1, row_block, m.hidden),
                                gold.reshape(-1, row_block), valid.reshape(-1, row_block)))
    return jnp.sum(parts) / n


# ------------------------------------------------------------------ serving
@partial(jax.jit, static_argnums=(0, 1))
def _served_logits(m: Model, prec: str, params, tokens, rows):
    """Logits at positions ``rows`` [B, G] of ``tokens`` [B, S]."""
    with jax.default_matmul_precision("highest"):
        h = final_hidden(m, params, tokens, prec)
        h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
        return head_logits(m, params, h, prec)


def served_gaps(m: Model, params, prompts, served, length: int, prec: str = "f32"):
    """For each request (prompt [P], served tokens [G]): the reference's
    logits at the positions that chose each served token, every sequence
    padded to ``length`` (causal: the padding changes nothing before it).
    Returns the logits [N, G, V] as a host array."""
    n = len(prompts)
    toks = np.zeros((n, length), np.int32)
    rows = np.zeros((n, len(served[0])), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, s[:-1]])
        toks[i, :len(seq)] = seq
        rows[i] = np.arange(len(p) - 1, len(p) - 1 + len(s))
    out = [np.asarray(_served_logits(m, prec, params, jnp.asarray(toks[i:i + 1]),
                                     jnp.asarray(rows[i:i + 1])))[0]
           for i in range(n)]
    return np.stack(out)
