"""Faults planted in the program under test, to show that ``correct``
catches them. Each is a context manager that patches the program's step
builders for its duration; none is used by a benchmark run.

- ``state_unchanged``: the train step returns the state it was given;
- ``half_batch``: the train step leaves out half of the batch, its loss the
  mean over the rest;
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
          "token_altered": token_altered, "cache_unchanged": cache_unchanged}
