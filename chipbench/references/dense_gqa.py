"""Plain float32 reference of a dense GQA decoder, with its loss, gradients
and AdamW steps.

Written from the published description (Qwen3, Granite 3.0) and from the
sizes in the configuration file, and independent of the program under test:
it imports nothing of it and takes none of its arrays. What it shares with
the program is only what a run must share to be compared: the recipe by
which the benchmark makes the weights from the seed (the program
trains and serves the benchmark's weights), the seeded token stream, and the optimizer's hyper-parameters, all re-implemented here.

Mathematics: token embedding; per layer RMSNorm, q/k/v projections,
per-head RMSNorm of q and k where the model has it (Qwen3), rotary position
embedding on the two halves of each head with the configured theta, causal
grouped-query attention with scores scaled by head_dim ** -0.5, the output
projection, RMSNorm and a SwiGLU feed-forward, both residual; a final
RMSNorm and the tied embedding as output head. Vocabulary rows past the real
vocabulary are padding: masked out of the loss and never chosen.

Every matmul runs under ``precision="highest"``. ``prec="fp8"`` rounds
every matmul operand, forward and backward, to float8 e4m3 with one scale
per tensor: the control that a lower precision must fail.

Blocks: attention runs a block of queries at a time, the layers run under
``lax.scan`` with each layer's weights widened to float32 only inside its
step, and the loss takes the head a block of rows at a time.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_LOGIT = -1e9
F8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclass(frozen=True)
class Model:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    qk_norm: bool

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        return cls(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim")
                   or c["hidden_size"] // c["num_attention_heads"],
                   ffn=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                   qk_norm=bool(c.get("qk_norm", False)))

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256


# ------------------------------------------------------------------ weights
def leaf_specs(m: Model) -> dict:
    """{path: (shape, init, scale)} of every weight, layers stacked on a
    leading axis; ``scale`` None means fan_in ** -0.5."""
    L, D, H, KV, Dh, F = m.layers, m.hidden, m.heads, m.kv_heads, m.head_dim, m.ffn
    out = {
        "embed": ((m.padded_vocab, D), "normal", 0.02),
        "final_norm": ((D,), "ones", None),
        "blocks/p0/ln1": ((L, D), "ones", None),
        "blocks/p0/ln2": ((L, D), "ones", None),
        "blocks/p0/attn/wq": ((L, D, H * Dh), "normal", None),
        "blocks/p0/attn/wk": ((L, D, KV * Dh), "normal", None),
        "blocks/p0/attn/wv": ((L, D, KV * Dh), "normal", None),
        "blocks/p0/attn/wo": ((L, H * Dh, D), "normal", None),
        "blocks/p0/ffn/w1": ((L, D, F), "normal", None),
        "blocks/p0/ffn/w3": ((L, D, F), "normal", None),
        "blocks/p0/ffn/w2": ((L, F, D), "normal", None),
    }
    if m.qk_norm:
        out["blocks/p0/attn/q_norm"] = ((L, Dh), "ones", None)
        out["blocks/p0/attn/k_norm"] = ((L, Dh), "ones", None)
    return out


def nest(flat: dict) -> dict:
    root: dict = {}
    for path, v in flat.items():
        node = root
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return root


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], path))
        else:
            out[path] = tree[k]
    return out


def _scale(shape, init_scale):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return init_scale if init_scale is not None else fan_in ** -0.5


def weights(m: Model, seed: int, shardings=None) -> dict:
    """The weights a run starts from, training and serving alike: made on
    the device in one jitted call from the seed, in bfloat16, one key per
    leaf split from the key of the seed's sha256; each leaf normal times its
    scale, norms ones. ``shardings``, a tree of the weights' shape, places
    each leaf as it is made; the values are the same."""
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

    make = jax.jit(make) if shardings is None else jax.jit(make, out_shardings=shardings)
    return make(jax.random.PRNGKey(seed32))


def shardings_over(m: Model, mesh, axis: str = "model") -> dict:
    """NamedShardings of the weights over ``axis`` of ``mesh``, tensor
    parallel in the Megatron way: the q, k, v and feed-forward input
    projections by output column, the attention and feed-forward output
    projections by input row, the embedding by hidden column, the norms
    whole. The layout only places the reference's arithmetic; the compiler
    inserts every exchange it needs, so the values are those of one
    device."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    by_leaf = {"wq": P(None, None, axis), "wk": P(None, None, axis),
               "wv": P(None, None, axis), "w1": P(None, None, axis),
               "w3": P(None, None, axis), "wo": P(None, axis, None),
               "w2": P(None, axis, None), "embed": P(None, axis)}
    return nest({path: NamedSharding(mesh, by_leaf.get(path.rsplit("/", 1)[-1], P()))
                 for path in leaf_specs(m)})


def synthetic_batch(seed: int, step: int, vocab: int, batch: int, seq: int) -> np.ndarray:
    """The training tokens of ``step``: Philox keyed by the seed, counter
    [0, 0, 0, step], uniform over the real vocabulary."""
    bit = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, step]))
    return bit.integers(0, vocab, size=(batch, seq), dtype=np.int32)


# ---------------------------------------------------------------- precision
@jax.custom_vjp
def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_q8(g),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def _operand(x, prec: str):
    return _q8(x) if prec == "fp8" else x


def mm(a, b, prec: str):
    return jnp.matmul(_operand(a, prec), _operand(b, prec), precision=HIGHEST)


def einsum(spec: str, a, b, prec: str):
    return jnp.einsum(spec, _operand(a, prec), _operand(b, prec), precision=HIGHEST)


# ------------------------------------------------------------------- layers
def rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta: float):
    """x [B, S, H, Dh]; the first and second halves of each head rotate as
    pairs (the Hugging Face ``rotate_half`` form)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(0, x.shape[-1], 2, dtype=jnp.float32) / x.shape[-1])
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, prec: str, q_block: int = 512):
    """Causal GQA: q [B, S, H, Dh], k/v [B, S, KV, Dh] -> [B, S, H, Dh].
    Each block of queries is rematerialised for the backward pass, so that
    one block's scores, not the whole layer's, are held at a time."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, Dh)
    kpos = jnp.arange(S)

    def block(qb, k, v, q0):
        s = einsum("bqkgd,bskd->bkgqs", qb, k, prec) * Dh ** -0.5
        qpos = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return einsum("bkgqs,bskd->bqkgd", p, v, prec)

    block = jax.checkpoint(block, static_argnums=(3,))
    outs = [block(qg[:, q0:q0 + q_block], k, v, q0) for q0 in range(0, S, q_block)]
    return jnp.concatenate(outs, axis=1).reshape(B, S, H, Dh)


def _layer(m: Model, prec: str, x, lp):
    lp = jax.tree.map(lambda t: t.astype(jnp.float32), lp)
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    a = lp["attn"]
    h = rmsnorm(x, lp["ln1"], m.eps)
    q = mm(h, a["wq"], prec).reshape(B, S, m.heads, m.head_dim)
    k = mm(h, a["wk"], prec).reshape(B, S, m.kv_heads, m.head_dim)
    v = mm(h, a["wv"], prec).reshape(B, S, m.kv_heads, m.head_dim)
    if m.qk_norm:
        q = rmsnorm(q, a["q_norm"], m.eps)
        k = rmsnorm(k, a["k_norm"], m.eps)
    q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
    o = attention(q, k, v, prec).reshape(B, S, m.heads * m.head_dim)
    x = x + mm(o, a["wo"], prec)
    f = lp["ffn"]
    h = rmsnorm(x, lp["ln2"], m.eps)
    return x + mm(jax.nn.silu(mm(h, f["w1"], prec)) * mm(h, f["w3"], prec), f["w2"], prec)


def final_hidden(m: Model, params, tokens, prec: str = "f32"):
    """The final-normed hidden states [B, S, D] of ``tokens`` [B, S]."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    body = jax.checkpoint(lambda x, lp: (_layer(m, prec, x, lp), None))
    x, _ = jax.lax.scan(body, x, params["blocks"]["p0"])
    return rmsnorm(x, params["final_norm"].astype(jnp.float32), m.eps)


def head_logits(m: Model, params, h, prec: str = "f32"):
    """Logits over the real vocabulary of hidden rows ``h`` [..., D]."""
    emb = params["embed"][: m.vocab].astype(jnp.float32)
    return mm(h, emb.T, prec)


def loss(m: Model, params, tokens, prec: str = "f32", row_block: int = 1024,
         over: int | None = None):
    """Mean next-token cross-entropy over the real vocabulary (the padding
    rows are masked out, which is the same as leaving them out). ``over``:
    the summed loss is divided by that many predicted tokens in place of
    this batch's, where the batch is a block of a larger one."""
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
    return jnp.sum(parts) / (over or n)


def loss_and_grads(m: Model, params, tokens, prec: str = "f32", seqs: int | None = None):
    """The batch's mean loss and its gradients, taken ``seqs`` sequences at
    a time (all at once by default) and summed, so that the memory the
    float32 backward pass holds is that of one block of sequences."""
    B, S = tokens.shape
    if seqs is None or seqs >= B:
        return jax.value_and_grad(lambda p: loss(m, p, tokens, prec))(params)
    n = B * (S - 1)

    def block(acc, tok):
        value, grads = jax.value_and_grad(lambda p: loss(m, p, tok, prec, over=n))(params)
        return jax.tree.map(jnp.add, acc, (value, grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    out, _ = jax.lax.scan(block, zero, tokens.reshape(B // seqs, seqs, S))
    return out


# ----------------------------------------------------------------- training
@dataclass(frozen=True)
class AdamW:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1  # on leaves of two or more dimensions
    clip: float = 1.0  # global gradient norm


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _train_step(m: Model, opt: AdamW, prec: str, seqs, params, mom, vel, t, tokens):
    """One step: loss and gradients in float32 from the bfloat16 weights
    (``seqs`` sequences at a time), clip, AdamW in float32, weights stored
    back in bfloat16."""
    p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        value, grads = loss_and_grads(m, p32, tokens, prec, seqs)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, opt.clip / (gnorm + 1e-9)), grads)
    t = t + 1.0

    def upd(g, mo, ve, p):
        mo = opt.b1 * mo + (1 - opt.b1) * g
        ve = opt.b2 * ve + (1 - opt.b2) * g * g
        step = (mo / (1 - opt.b1 ** t)) / (jnp.sqrt(ve / (1 - opt.b2 ** t)) + opt.eps)
        wd = opt.weight_decay if p.ndim >= 2 else 0.0
        return (p - opt.lr * (step + wd * p)).astype(jnp.bfloat16), mo, ve

    out = jax.tree.map(upd, grads, mom, vel, p32)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))  # noqa: E731
    return value, grads, pick(0), pick(1), pick(2)


def train_steps(m: Model, seed: int, batches, opt: AdamW = AdamW(), prec: str = "f32",
                mesh=None, seqs: int | None = None):
    """Follow the first ``len(batches)`` steps of a training run from its
    seeded start. Returns {losses, p0, params after the last step, m1 (the
    first moment after the first step), g1_norms (each leaf's norm
    of the first clipped gradient)}, the trees flat by path and on the
    host. With ``mesh``, the weights and AdamW's moments are laid out over
    its ``model`` axis (``shardings_over``), for a model whose float32
    state does not fit one device; ``seqs`` takes each step's gradient that
    many sequences at a time (``loss_and_grads``)."""
    params = weights(m, seed, None if mesh is None else shardings_over(m, mesh))
    p0 = flatten(jax.device_get(params))

    def zeros(p):
        return jnp.zeros(p.shape, jnp.float32, device=None if mesh is None else p.sharding)

    mom = jax.tree.map(zeros, params)
    vel = jax.tree.map(zeros, params)
    losses, g1 = [], None
    for i, tokens in enumerate(batches):
        value, grads, params, mom, vel = _train_step(
            m, opt, prec, seqs, params, mom, vel, jnp.float32(i), jnp.asarray(tokens))
        losses.append(float(value))
        if g1 is None:
            g1 = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
                  for k, v in flatten(jax.device_get(grads)).items()}
            m1 = flatten(jax.device_get(mom))
        del grads
    return {"losses": losses, "p0": p0, "params": flatten(jax.device_get(params)),
            "m1": m1, "g1_norms": g1}


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
    logits at the positions that chose each served token, with every
    sequence padded to ``length`` (causal: the padding changes nothing
    before it). Returns (ref logits [N, G, V]) as a host array."""
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


def widest_gap(ref_logits: np.ndarray, chosen: np.ndarray) -> float:
    """The widest gap by which a chosen token's reference logit lies below
    the reference's best at that position. ref_logits [N, G, V], chosen
    [N, G]."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return float(np.max(best - got))
