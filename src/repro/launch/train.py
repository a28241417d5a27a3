"""Training launcher: run an architecture as a reproducible training job
inside a version-store repository.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b \\
        --steps 40 --repo /tmp/myrun [--full]

The default smoke config runs on CPU in minutes. ``--full`` selects the
published widths; one TPU v5e chip runs ``qwen3_0_6b --full`` (see
``chip_smoke.py`` for a batch and length that fit). Either way the run is
checkpointed into the repository with machine-actionable records and is
resumable by re-invoking the same command (kill-anywhere semantics).
``main(argv)`` is also the in-process entry point: a chip belongs to one
process, so a program that holds it calls ``main`` rather than a subprocess.
"""
from __future__ import annotations

import argparse
import os

from .. import configs, obs
from ..core.repo import Repository
from ..data.tokens import SyntheticTokens
from ..optim.adamw import AdamW, cosine_schedule
from ..train.loop import SegmentResult, train_segment
from .compile_cache import enable_compile_cache


def make_optimizer(cfg, lr: float, steps: int) -> AdamW:
    """The launcher's optimizer: AdamW on a 10-step warmup + cosine."""
    return AdamW(lr=cosine_schedule(lr, warmup=10, total=steps),
                 moment_dtype=cfg.opt_moment_dtype)


def main(argv: list[str] | None = None) -> SegmentResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--repo", default="")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="published widths (accelerators required)")
    ap.add_argument("--async-ckpt", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch) if args.full else configs.get_smoke(args.arch)
    root = args.repo or os.path.abspath(f"train_{args.arch}")
    if os.path.exists(os.path.join(root, ".repro")):
        repo = Repository(root)
        print(f"resuming in existing repository {root}")
    else:
        repo = Repository.init(root)
        print(f"new repository {root}")

    ds = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.batch, seed=0)
    opt = make_optimizer(cfg, args.lr, args.steps)
    compiles = obs.counters().get("compile", 0)
    res = train_segment(repo, cfg, ds, n_steps=args.steps,
                        ckpt_every=args.ckpt_every, optimizer=opt,
                        async_ckpt=args.async_ckpt)
    print(f"steps {res.start_step} -> {res.end_step}  loss {res.final_loss:.4f}")
    print(f"checkpoint commit: {res.checkpoint_commit}")
    print(f"checkpoint restore {res.restore_s:.3f} s, saves {res.save_s:.3f} s, "
          f"compiles {obs.counters().get('compile', 0) - compiles:g}")
    return res


if __name__ == "__main__":
    main()
