"""End-to-end parity: the model with Pallas kernels forced on (interpret mode
on CPU) must match the pure-jnp paths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T
from repro.models.params import init_params


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "mixtral_8x22b", "rwkv6_1_6b",
                                  "jamba_1_5_large_398b", "granite_4_0_h_micro"])
def test_pallas_on_vs_off(arch):
    cfg_off = configs.get_smoke(arch).replace(use_pallas="off")
    cfg_on = cfg_off.replace(use_pallas="on")
    params = init_params(T.param_defs(cfg_off), seed=0, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    B, S = 2, 64  # multiple of every kernel chunk
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg_off.vocab_size, (B, S)), jnp.int32)}
    l_off, _ = jax.jit(lambda p, b: T.forward_train(cfg_off, None, p, b))(params, batch)
    l_on, _ = jax.jit(lambda p, b: T.forward_train(cfg_on, None, p, b))(params, batch)
    np.testing.assert_allclose(
        np.asarray(l_on, np.float32), np.asarray(l_off, np.float32),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "mixtral_8x22b", "jamba_1_5_large_398b",
                                  "granite_4_0_h_micro"])
def test_pallas_prefill_cache_feeds_decode(arch):
    """The flash-attention prefill (S % 64 == 0) builds the KV cache like the
    jnp path does, so the next decode step gives the same logits."""
    cfg_off = configs.get_smoke(arch).replace(use_pallas="off")
    cfg_on = cfg_off.replace(use_pallas="on")
    params = init_params(T.param_defs(cfg_off), seed=0, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    B, S, cache_len = 2, 64, 72
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg_off.vocab_size, (B, S)), jnp.int32)}
    ring = min(cache_len, cfg_off.sliding_window or cache_len)
    tok = pos = None
    logits = {}
    for cfg in (cfg_off, cfg_on):
        caches, last = jax.jit(
            lambda p, b, cfg=cfg: T.prefill(cfg, None, p, b, cache_len))(params, batch)
        for i, kind in enumerate(cfg.pattern):
            if kind.mixer == "attn":
                for name in ("k", "v"):
                    assert caches[f"p{i}"][name].shape == (
                        cfg.n_repeats, B, ring, cfg.n_kv_heads, cfg.head_dim)
        if tok is None:  # both decode the same next token
            tok = jnp.argmax(last[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]
            pos = jnp.asarray(S, jnp.int32)
        logits[cfg.use_pallas], _ = jax.jit(
            lambda p, c, t, i, cfg=cfg: T.decode_step(cfg, None, p, c, t, i))(
                params, caches, tok, pos)
    np.testing.assert_allclose(np.asarray(logits["on"]), np.asarray(logits["off"]),
                               rtol=2e-3, atol=2e-3)
