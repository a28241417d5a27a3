"""Serving launcher: batched prefill+decode for any architecture, optionally
restoring weights from a version-store checkpoint commit.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_1_6b \\
        --batch 8 --prompt-len 64 --gen 32 [--full] [--repo PATH [--commit OID]]

One TPU v5e chip serves ``qwen3_0_6b --full``. A prompt length that is a
multiple of 64 takes the flash-attention kernel in prefill on the chip.
``main(argv)`` is also the in-process entry point (a chip belongs to one
process) and returns what was served.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from .. import configs, obs
from ..core.repo import Repository
from ..models import transformer as T
from ..models.params import init_params
from ..train.checkpoint import CheckpointManager
from ..train.steps import make_decode_step, make_prefill_step
from .compile_cache import enable_compile_cache


@dataclass
class ServeResult:
    prompt: np.ndarray  # [B, prompt_len] int32
    tokens: np.ndarray  # [B, gen] greedy tokens; tokens[:, 0] from prefill
    first_decode_logits: np.ndarray  # [B, Vp] fp32, the step fed tokens[:, 0]
    prefill: Any  # the compiled prefill program
    commit: str | None  # checkpoint commit the weights came from


def main(argv: list[str] | None = None) -> ServeResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--repo", default="", help="restore weights from this repo")
    ap.add_argument("--commit", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch) if args.full else configs.get_smoke(args.arch)
    t0 = time.perf_counter()
    commit = None
    if args.repo:
        ckpt = CheckpointManager(Repository(args.repo))
        commit, _ = ckpt.manifest(args.commit)
        state, manifest = ckpt.restore(commit, subtree="params")
        params = state["params"]
        print(f"restored checkpoint step {manifest['step']} from {args.repo} "
              f"in {time.perf_counter() - t0:.3f} s")
    else:
        params = init_params(T.param_defs(cfg), seed=0)

    cache_len = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len),
                          dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompt)}
    t0 = time.perf_counter()
    prefill = jax.jit(make_prefill_step(cfg, None, cache_len=cache_len)) \
        .lower(params, batch).compile()
    print(f"prefill compile: {time.perf_counter() - t0:.3f} s")

    with obs.span("repro.serve.prefill", prompt_len=args.prompt_len):
        caches, logits = prefill(params, batch)
    with obs.span("repro.serve.sample", token=0):
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]
    step = jax.jit(make_decode_step(cfg, None), donate_argnums=(1,))
    out, first_logits = [tok], None
    for i in range(args.gen - 1):
        pos = args.prompt_len + i
        with obs.span("repro.serve.decode_step", pos=pos):
            logits, caches = step(params, caches, tok, jnp.asarray(pos, jnp.int32))
        if first_logits is None:
            first_logits = np.asarray(logits, np.float32)
        with obs.span("repro.serve.sample", token=i + 1):
            tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]
        out.append(tok)
    return ServeResult(prompt, np.asarray(jnp.concatenate(out, axis=1)),
                       first_logits, prefill, commit)


if __name__ == "__main__":
    main()
