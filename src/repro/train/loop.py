"""Resumable training loop: segments of steps as reproducible jobs.

``train_segment`` is the unit the scheduler submits: initialize-or-resume
from the version store, run N steps, checkpoint every K, commit. Killing the
process anywhere and calling ``train_segment`` again continues from the last
checkpoint and — because data, init, and optimizer are deterministic —
reaches bitwise-identical state (tested in tests/test_train.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import obs
from ..configs.base import ModelConfig
from ..core.repo import Repository
from ..models import transformer as T
from ..models.params import init_params, param_shardings
from ..optim.adamw import AdamW
from .checkpoint import CheckpointManager
from .steps import make_train_step


@dataclass
class SegmentResult:
    start_step: int
    end_step: int
    final_loss: float
    checkpoint_commit: str | None
    losses: list[float] = field(default_factory=list)  # one per step run
    restore_s: float = 0.0  # wall seconds restoring the newest checkpoint
    save_s: float = 0.0  # wall seconds the loop waited on checkpoint saves


def state_shardings(defs: dict, mesh) -> dict:
    """NamedShardings of the train state ``{"params", "opt_state"}``: the
    AdamW moments are laid out exactly like their parameters."""
    ps = param_shardings(defs, mesh)
    return {"params": ps,
            "opt_state": {"m": ps, "v": ps,
                          "step": NamedSharding(mesh, P())}}


def jit_train_step(cfg: ModelConfig, rules, optimizer: AdamW):
    """(step, optimizer init, state shardings, batch sharding): the jitted
    train step and moment init. With ``rules`` both place the state under
    ``rules.mesh`` with the parameter shardings; without, the shardings are
    None and everything stays on the default device."""
    step_fn = make_train_step(cfg, rules, optimizer)
    if rules is None:
        return jax.jit(step_fn, donate_argnums=(0, 1)), optimizer.init, None, None
    shardings = state_shardings(T.param_defs(cfg, rules), rules.mesh)
    state_in = (shardings["params"], shardings["opt_state"])
    batch_sharding = rules.sharding(rules.batch)
    step_fn = jax.jit(
        step_fn, donate_argnums=(0, 1),
        in_shardings=state_in + ({"tokens": batch_sharding},),
        out_shardings=state_in + (rules.sharding(P()),),
    )
    init_opt = jax.jit(optimizer.init, out_shardings=shardings["opt_state"])
    return step_fn, init_opt, shardings, batch_sharding


def train_segment(
    repo: Repository,
    cfg: ModelConfig,
    dataset,
    n_steps: int,
    ckpt_every: int = 50,
    optimizer: AdamW | None = None,
    rules=None,
    seed: int = 0,
    async_ckpt: bool = False,
) -> SegmentResult:
    """With ``rules``, the state is created, stepped and restored under
    ``rules.mesh`` with the parameter shardings; without, on the default
    device."""
    optimizer = optimizer or AdamW(lr=1e-3, moment_dtype=cfg.opt_moment_dtype)
    with obs.span("repro.train.segment", end_step=n_steps) as segment:
        ckpt = CheckpointManager(repo)
        step_fn, init_opt, shardings, batch_sharding = jit_train_step(
            cfg, rules, optimizer)
        t0 = time.perf_counter()
        state, manifest = ckpt.restore(shardings=shardings)
        restore_s = time.perf_counter() - t0
        if state is not None:
            params, opt_state = state["params"], state["opt_state"]
            start = int(manifest["step"])
        else:
            params = init_params(T.param_defs(cfg, rules), seed=seed,
                                 mesh=rules.mesh if rules is not None else None)
            opt_state = init_opt(params)
            start = 0
        segment.set_metadata(start_step=start)

        losses: list[float] = []
        commit = None
        save_s = 0.0
        for step in range(start, n_steps):
            with obs.span("repro.train.feed", step=step):
                tokens = dataset.shard_batch_at(step, 0, 1)
                batch = {"tokens": jax.device_put(tokens, batch_sharding)}
            # the job's first call traces, lowers and compiles (or loads) the
            # step; every later one only dispatches it
            name = "repro.train.first_step" if step == start else "repro.train.step"
            with obs.span(name, step=step):
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            with obs.span("repro.train.loss", step=step):
                losses.append(float(metrics["loss"]))
            if (step + 1) % ckpt_every == 0 or step + 1 == n_steps:
                saver = ckpt.save_async if async_ckpt else ckpt.save
                t0 = time.perf_counter()
                out = saver(
                    step + 1, params, opt_state, data_step=step + 1,
                    extra={"loss": losses[-1], "config": cfg.name},
                )
                save_s += time.perf_counter() - t0
                commit = out if isinstance(out, str) else commit
        t0 = time.perf_counter()
        ckpt.wait()
        save_s += time.perf_counter() - t0
    if commit is None:
        latest = ckpt.latest()
        commit = latest[0] if latest else None
    loss = losses[-1] if losses else float("nan")
    return SegmentResult(start, n_steps, loss, commit, losses, restore_s, save_s)
