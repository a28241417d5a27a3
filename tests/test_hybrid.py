"""The Mamba-2 / attention hybrid (granite_4_0_h_micro) and Granite's
multipliers: published sizes, the hybrid cache, decode state that the
comparison with the full forward pass depends on, and granite-3-2b's program
unchanged by the multiplier fields."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.models import transformer as T
from repro.models.params import abstract_params, init_params, param_count
from repro.train.steps import make_decode_step, make_prefill_step

HERE = os.path.dirname(os.path.abspath(__file__))


def test_published_sizes():
    cfg = configs.get("granite_4_0_h_micro")
    kinds = [k.mixer for k in cfg.pattern] * cfg.n_repeats
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert kinds.count("mamba2") == 36 and not cfg.rope
    assert (cfg.mamba_d_inner, cfg.mamba.n_heads, cfg.mamba.d_state) == (4096, 64, 128)
    assert (cfg.embedding_multiplier, cfg.attn_scale, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.015625, 0.22, 8.0)
    # every leaf the program holds; param_counts leaves out the final norm
    assert param_count(T.param_defs(cfg)) == 3_191_396_096
    assert cfg.param_counts()["total"] + cfg.d_model == 3_191_396_096


def test_hybrid_cache_holds_both_kinds_of_state():
    cfg = configs.get("granite_4_0_h_micro")
    before = obs.counters()
    cache = T.abstract_cache(cfg, None, 32, 2560)
    after = obs.counters()
    attn, mamba = cache["p5"], cache["p0"]
    assert set(attn) == {"k", "v"} and attn["k"].shape == (4, 32, 2560, 8, 64)
    assert set(mamba) == {"h", "conv"}
    assert mamba["h"].shape == (4, 32, 64, 64, 128) and mamba["h"].dtype == jnp.float32
    assert mamba["conv"].shape == (4, 32, 3, 4352) and mamba["conv"].dtype == jnp.bfloat16
    grew = {k: after[k] - before.get(k, 0) for k in ("cache.kv_bytes", "cache.state_bytes")}
    assert grew["cache.kv_bytes"] == 4 * 2 * 32 * 2560 * 8 * 64 * 2
    assert grew["cache.state_bytes"] == 36 * 32 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)


@pytest.mark.parametrize("arch", ["granite_3_2b", "granite_4_0_h_micro"])
def test_decode_step_counts_the_bytes_it_writes(arch):
    """Tracing one decode step adds to ``decode.cache_write_bytes`` what the
    step writes into its decode state: each attention layer's new K/V row in
    bf16, and each Mamba-2 layer's fp32 state and bf16 conv tail whole."""
    cfg = configs.get_smoke(arch)
    B = 3
    params = abstract_params(T.param_defs(cfg), jnp.bfloat16)
    caches = T.abstract_cache(cfg, None, B, 40)
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    before = obs.counters().get("decode.cache_write_bytes", 0)
    jax.eval_shape(make_decode_step(cfg, None), params, caches, token,
                   jax.ShapeDtypeStruct((), jnp.int32))
    grew = obs.counters()["decode.cache_write_bytes"] - before
    kinds = [k.mixer for k in cfg.pattern] * cfg.n_repeats
    rows = kinds.count("attn") * 2 * B * cfg.n_kv_heads * cfg.head_dim * 2
    m = cfg.mamba
    states = kinds.count("mamba2") * B * (
        m.n_heads * m.d_head * m.d_state * 4
        + (m.d_conv - 1) * (cfg.mamba_d_inner + 2 * m.n_groups * m.d_state) * 2)
    assert rows and grew == rows + states


def _prefill_decode(cfg, params, tokens, prompt, break_at=None, leaf=None):
    """Logits of the last prompt position and of each decoded position; with
    ``break_at``, the Mamba-2 layers' ``leaf`` is zeroed before that step."""
    caches, logits = jax.jit(make_prefill_step(cfg, None, tokens.shape[1]))(
        params, {"tokens": tokens[:, :prompt]})
    step = jax.jit(make_decode_step(cfg, None))
    out = [logits]
    for i in range(tokens.shape[1] - prompt - 1):
        if i == break_at:
            caches = {k: ({**c, leaf: jnp.zeros_like(c[leaf])} if "h" in c else c)
                      for k, c in caches.items()}
        logits, caches = step(params, caches, tokens[:, prompt + i:prompt + i + 1],
                              jnp.asarray(prompt + i, jnp.int32))
        out.append(logits)
    return np.stack([np.asarray(x, np.float32) for x in out], axis=1)


@pytest.mark.parametrize("leaf", [None, "h", "conv"])
def test_decode_needs_its_state_and_conv_tail(leaf):
    """Prefill then decode gives the full forward pass's logits; zeroing the
    SSM state or the conv tail at one step does not."""
    cfg = configs.get_smoke("granite_4_0_h_micro")
    params = init_params(T.param_defs(cfg), seed=1, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 24)),
                         jnp.int32)
    prompt = 18
    with jax.default_matmul_precision("highest"):
        full, _ = jax.jit(lambda p: T.forward_train(cfg, None, p, {"tokens": tokens}))(params)
        got = _prefill_decode(cfg, params, tokens, prompt, 2 if leaf else None, leaf)
    want = np.asarray(full, np.float32)[:, prompt - 1:-1]
    gap, spread = np.abs(got - want).max(), want.std()
    if leaf is None:
        assert gap < 1e-3 * spread, (gap, spread)
    else:  # some logit moves by more than the logits' own spread
        assert gap > spread, (gap, spread)


def test_granite_3_2b_holds_the_neutral_multipliers():
    cfg = configs.get("granite_3_2b")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (1, 1, 1)
    assert cfg.attention_multiplier is None and cfg.attn_scale == cfg.head_dim ** -0.5


@pytest.mark.parametrize("use_pallas", ["off", "on"])
def test_granite_3_2b_logits_bit_for_bit_as_before_the_multipliers(use_pallas):
    """Prefill and three decode steps of granite-3-2b's smoke model give the
    logits that the program gave before the multiplier fields existed (scores
    scaled by head_dim ** -0.5 and no other factor), recorded in
    ``data/granite_3_2b_smoke_logits.npz``; ``on`` takes flash in interpret
    mode."""
    cfg = configs.get_smoke("granite_3_2b").replace(use_pallas=use_pallas)
    params = init_params(T.param_defs(cfg), seed=5)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    caches, logits = jax.jit(make_prefill_step(cfg, None, 68))(params, {"tokens": jnp.asarray(prompt)})
    step = jax.jit(make_decode_step(cfg, None))
    got = [np.asarray(logits, np.float32)]
    for i in range(3):
        tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
        logits, caches = step(params, caches, tok, jnp.asarray(64 + i, jnp.int32))
        got.append(np.asarray(logits, np.float32))
    want = np.load(os.path.join(HERE, "data", "granite_3_2b_smoke_logits.npz"))[use_pallas]
    np.testing.assert_array_equal(np.stack(got), want)
