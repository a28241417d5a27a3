"""Faults planted in the program under test, to show that ``correct``
catches them. Each is a context manager that patches the program's step
builders for its duration; none is used by a benchmark run.

- ``state_unchanged``: the train step returns the state it was given;
- ``half_batch``: the train step leaves out half of the batch, its loss the
  mean over the rest;
- ``exchange_left_out``: on a mesh, each feed-forward block returns its own
  chip's partial sum over the hidden columns that chip holds, without the
  reduction across chips that the sharded weights need;
- ``token_altered``: the decode step's logits are shifted by one vocabulary
  row, so the token it produces is another;
- ``cache_unchanged``: the decode step returns the KV cache it was given.
"""
from __future__ import annotations

from chipbench.harness import patched


def state_unchanged():
    from repro.train import loop

    def make(orig):
        def make_train_step(*a, **k):
            step = orig(*a, **k)

            def broken(params, opt_state, batch):
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
            return broken
        return make_train_step
    return patched(loop, "make_train_step", make)


def half_batch():
    from repro.train import loop

    def make(orig):
        def make_train_step(*a, **k):
            step = orig(*a, **k)

            def broken(params, opt_state, batch):
                tokens = batch["tokens"]
                return step(params, opt_state, {"tokens": tokens[: tokens.shape[0] // 2]})
            return broken
        return make_train_step
    return patched(loop, "make_train_step", make)


def exchange_left_out(keep: bool = False):
    """``keep`` puts the reduction back, to show that the patch alone
    changes no result. On one chip nothing is exchanged, and the block is
    the program's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.models import transformer

    def make(orig):
        def ffn_or_moe(cfg, rules, kind, p, x, ctx):
            if rules is None or rules.tp is None or kind.moe:
                return orig(cfg, rules, kind, p, x, ctx)
            tp, rows = rules.tp, P(*rules.batch)

            def local(h, w1, w3, w2):
                out = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
                return jax.lax.psum(out, tp) if keep else out

            f = p["ffn"]
            h = transformer.rmsnorm(x, p["ln2"], cfg.norm_eps)
            out = jax.shard_map(local, mesh=rules.mesh,
                                in_specs=(rows, P(None, tp), P(None, tp), P(tp, None)),
                                out_specs=rows, check_vma=False)(h, f["w1"], f["w3"], f["w2"])
            return out, jnp.zeros((), jnp.float32)
        return ffn_or_moe
    return patched(transformer, "_ffn_or_moe", make)


def token_altered():
    import jax.numpy as jnp

    from repro.train import steps

    def make(orig):
        def make_decode_step(*a, **k):
            step = orig(*a, **k)

            def broken(params, caches, token, pos):
                logits, caches = step(params, caches, token, pos)
                return jnp.roll(logits, 1, axis=-1), caches
            return broken
        return make_decode_step
    return patched(steps, "make_decode_step", make)


def cache_unchanged():
    from repro.train import steps

    def make(orig):
        def make_decode_step(*a, **k):
            step = orig(*a, **k)

            def broken(params, caches, token, pos):
                logits, _ = step(params, caches, token, pos)
                return logits, caches
            return broken
        return make_decode_step
    return patched(steps, "make_decode_step", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out, "token_altered": token_altered,
          "cache_unchanged": cache_unchanged}
