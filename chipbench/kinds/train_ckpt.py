"""Training as chained jobs on one repository: each job resumes from the
newest checkpoint commit, trains, and commits its state.

Traffic parameters (``chipbench/traffic/<name>.json``, kind ``train_ckpt``):
``batch``, ``seq_len``; ``setup_steps``, the steps of the set-up job that
leaves the first commit (and that the reference follows); ``ckpt_every``:
each job in the window trains to the second multiple of it after its start
and commits at both, so the first commit can overlap the steps after it;
``async_ckpt``; ``lr``; optionally ``reference_seqs``, the sequences at a
time the float32 reference takes each set-up step's gradient in (the whole
batch where it is absent), so that it fits the chips.

Set-up: the job starts from the benchmark's weights, made on the device in
one jitted call from the seed (``reference.weights``), in the place of the
program's ``init_params``, which compiles one program per leaf with the seed
in it. Its first step's optimizer state is copied to the host for the check.

A cell on more than one chip trains on the program's host mesh
``(data=1, model=chips)`` under its sharding rules: the weights are made
under the program's parameter shardings, every job steps and restores its
state under them, the last commit is read back under them, and the
reference follows the set-up steps with its float32 state laid out over the
same mesh (``reference.shardings_over``). On one chip every call is the
program's single-device path.

Window: back-to-back ``repro.train.loop.train_segment`` calls, each resuming
from the last commit; it ends with the job in progress once ``--seconds``
have passed. ``train_tokens_per_s`` is every token of every step completed
in the window over the window's wall time, restores and saves included.

Checks (``correct``):

- ``loss_gap``: the largest relative gap between the program's loss and the
  reference's at each set-up step;
- ``grad_gap``: the first gradient as the optimizer got it, read from
  AdamW's first moment after the first step, by the worst leaf: the gap
  between the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf;
- ``update_gap``: the same for the change of the weights over the set-up
  steps (as the set-up commit holds them);
- ``ckpt_leaf_mismatch``: leaves of the last commit whose restored bytes do
  not hash to the key the manifest records, or (on a mesh) that were not
  restored under the sharding asked for (exact, limit 0);
- ``resume_breaks``: jobs that did not start where the previous one ended
  (exact, limit 0).

Leaves whose first gradient is under a thousandth of the median leaf's in
the reference are left out of the two leaf gaps: they move by round-off
alone.
"""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np

from chipbench.harness import patched


def _gap_by_leaf(got: dict, want: dict, keep) -> float:
    norms = {k: float(np.linalg.norm(np.asarray(want[k], np.float64))) for k in keep}
    floor = float(np.median(list(norms.values())))
    worst = 0.0
    for k in keep:
        g = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        worst = max(worst, abs(g - norms[k]) / max(norms[k], floor, 1e-30))
    return worst


def train_gaps(reference, model, seed: int, traffic: dict, prog: dict, prec: str = "f32",
               mesh=None) -> dict:
    """The three training numbers for program readings ``prog`` = {losses,
    params, m1} (the trees flat by path): the reference, in ``prec``, follows
    the same steps from the same seeded start (over ``mesh``, if given)."""
    n = len(prog["losses"])
    batches = [reference.synthetic_batch(seed, s, model.vocab, traffic["batch"],
                                         traffic["seq_len"]) for s in range(n)]
    ref = reference.train_steps(model, seed, batches, reference.AdamW(lr=traffic["lr"]),
                                prec=prec, mesh=mesh, seqs=traffic.get("reference_seqs"))
    g1 = ref["g1_norms"]
    med = float(np.median(list(g1.values())))
    keep = [k for k in ref["m1"] if g1[k] >= 1e-3 * med]
    f32 = lambda t: {k: np.asarray(v, np.float32) for k, v in t.items()}  # noqa: E731
    p0, rp, pp = f32(ref["p0"]), f32(ref["params"]), f32(prog["params"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": _gap_by_leaf(prog["m1"], ref["m1"], keep),
        "update_gap": _gap_by_leaf({k: pp[k] - p0[k] for k in keep},
                                   {k: rp[k] - p0[k] for k in keep}, keep),
        "ref_losses": ref["losses"],
        "leaves_compared": (len(keep), len(ref["m1"])),
    }


class Driver:
    def __init__(self, run):
        self.run = run
        t = run.traffic
        self.batch, self.seq = int(t["batch"]), int(t["seq_len"])

    def _segment(self, n_steps: int, ckpt_every: int):
        from repro.train.loop import train_segment

        return train_segment(self.repo, self.cfg, self.data, n_steps=n_steps,
                             ckpt_every=ckpt_every, optimizer=self.opt, rules=self.rules,
                             seed=self.run.seed, async_ckpt=bool(self.run.traffic["async_ckpt"]))

    def setup(self):
        from repro.core.repo import Repository
        from repro.data.tokens import SyntheticTokens
        from repro.optim.adamw import AdamW

        from chipbench.harness import log, program_config

        run, t = self.run, self.run.traffic
        self.cfg = program_config(run.config)
        self.rules = self.shardings = self.mesh = None
        if run.cell.chips > 1:
            from repro.distributed.sharding import make_rules
            from repro.launch.mesh import make_host_mesh
            from repro.models import transformer as T
            from repro.train.loop import state_shardings

            self.mesh = make_host_mesh(run.cell.chips)
            self.rules = make_rules(self.mesh)
            self.shardings = state_shardings(T.param_defs(self.cfg, self.rules), self.mesh)
        self.repo = Repository.init(os.path.join(run.work, "repo"))
        self.data = SyntheticTokens(self.cfg.vocab_size, self.seq, self.batch, seed=run.seed)
        self.opt = AdamW(lr=float(t["lr"]), moment_dtype=self.cfg.opt_moment_dtype)
        n = int(t["setup_steps"])
        with self._own_weights(), self._first_moment():
            self.first = self._segment(n, n)
        log(f"set-up job: steps 0 -> {n}, losses {self.first.losses}, "
            f"restore {self.first.restore_s!r} s, save {self.first.save_s!r} s")

    def _own_weights(self):
        """The program's ``init_params`` replaced by the benchmark's weights,
        made in one jitted call from the seed."""
        from repro.train import loop

        ref = self.run.cell.reference
        model = ref.Model.from_config(self.run.config)
        place = self.shardings and self.shardings["params"]
        return patched(loop, "init_params",
                       lambda orig: lambda *a, **k: ref.weights(model, self.run.seed, place))

    def _first_moment(self):
        """The train step, with AdamW's first moment after its first call
        copied to the host (``self.m1``, flat by path)."""
        import jax

        from repro.train import loop

        flatten = self.run.cell.reference.flatten
        self.m1 = None

        def make(jit_step):
            def jit_train_step(*a, **k):
                step, *rest = jit_step(*a, **k)

                def first(params, opt_state, batch):
                    out = step(params, opt_state, batch)
                    if self.m1 is None:
                        self.m1 = flatten(jax.device_get(out[1]["m"]))
                    return out
                return (first, *rest)
            return jit_train_step
        return patched(loop, "jit_train_step", make)

    @contextlib.contextmanager
    def _spans(self):
        """Benchmark spans around the calls a job makes into the checkpoint
        layer, the step and the feed, for the window of a traced run."""
        from repro.train import checkpoint, loop

        run = self.run
        if not run.trace:
            yield
            return

        def wrap(fn, name):
            def inner(*a, **k):
                with run.span(name):
                    return fn(*a, **k)
            return inner

        def jit_train_step(jit_step):
            def inner(*a, **k):
                step, *rest = jit_step(*a, **k)
                return (wrap(step, "bench.step.dispatch"), *rest)
            return inner

        cm = checkpoint.CheckpointManager
        data, self.data = self.data, _AnnotatedData(self.data, run)
        try:
            with contextlib.ExitStack() as stack:
                for meth in ("restore", "save_async", "save", "wait"):
                    stack.enter_context(patched(
                        cm, meth, lambda fn, meth=meth: wrap(fn, f"bench.ckpt.{meth}")))
                stack.enter_context(patched(loop, "jit_train_step", jit_train_step))
                yield
        finally:
            self.data = data

    def window(self) -> dict:
        run, t = self.run, self.run.traffic
        every = int(t["ckpt_every"])
        self.segments = []
        end = self.first.end_step
        with self._spans():
            t0 = time.perf_counter()
            while True:
                with run.span("bench.segment"):
                    r = self._segment((end // every + 2) * every, every)
                self.segments.append(r)
                end = r.end_step
                if time.perf_counter() - t0 >= run.seconds:
                    break
            wall = time.perf_counter() - t0
        steps = sum(r.end_step - r.start_step for r in self.segments)
        losses = [x for r in self.segments for x in r.losses]
        # a job commits at every multiple of ckpt_every it reaches
        saves = sum(r.end_step // every - r.start_step // every for r in self.segments)
        run.data.update(segments=self.segments, steps=steps, batch=self.batch,
                        seq=self.seq, wall_s=wall, saves=saves)
        from chipbench.harness import log

        log(f"window: {len(self.segments)} jobs, {steps} steps in {wall!r} s; "
            f"restore {[r.restore_s for r in self.segments]} s, "
            f"save {[r.save_s for r in self.segments]} s; last loss {losses[-1]!r}")
        return {"metrics": {"train_tokens_per_s": steps * self.batch * self.seq / wall},
                "attempted": steps,
                "failed": int(sum(not np.isfinite(x) for x in losses))}

    def release(self):
        import gc

        gc.collect()

    def first_job(self) -> dict:
        """The set-up job's readings: its losses, the first moment after its
        first step, and the weights its commit holds (flat by path, on the
        host)."""
        import jax

        from repro.train.checkpoint import CheckpointManager

        state, _ = CheckpointManager(self.repo).restore(
            self.first.checkpoint_commit, shardings=self.shardings, subtree="params")
        params = self.run.cell.reference.flatten(jax.device_get(state["params"]))
        del state
        return {"losses": self.first.losses, "params": params, "m1": self.m1}

    def readings(self, prog: dict | None = None, prec: str = "f32") -> dict:
        """The three training numbers of ``prog`` (the set-up job's, by
        default) against the reference."""
        run = self.run
        ref = run.cell.reference
        return train_gaps(ref, ref.Model.from_config(run.config), run.seed, run.traffic,
                          prog if prog is not None else self.first_job(), prec, self.mesh)

    def control(self) -> dict:
        """The same numbers for the reference itself computed in float8 in
        the program's place, on the set-up job's batches."""
        run = self.run
        ref = run.cell.reference
        model = ref.Model.from_config(run.config)
        batches = [ref.synthetic_batch(run.seed, s, model.vocab, self.batch, self.seq)
                   for s in range(int(run.traffic["setup_steps"]))]
        low = ref.train_steps(model, run.seed, batches, ref.AdamW(lr=run.traffic["lr"]),
                              prec="fp8", mesh=self.mesh,
                              seqs=run.traffic.get("reference_seqs"))
        return self.readings(low)

    def check(self) -> dict:
        from repro.train.checkpoint import CheckpointManager, leaf_keys

        from chipbench.harness import log

        lim = self.run.cell.limits
        # the last commit reads back byte for byte, under the shardings asked
        state, manifest = CheckpointManager(self.repo).restore(
            self.segments[-1].checkpoint_commit, shardings=self.shardings)
        keys = leaf_keys(state)
        mismatch = sum(keys[p] != m["key"] for p, m in manifest["leaves"].items())
        if self.shardings is not None:
            import jax

            mismatch += sum(a.sharding != want for a, want in
                            zip(jax.tree.leaves(state), jax.tree.leaves(self.shardings)))
        del state
        breaks, prev = 0, self.first
        for r in self.segments:
            breaks += int(r.start_step != prev.end_step)
            prev = r
        g = self.readings()
        log(f"losses: program {self.first.losses}, reference {g['ref_losses']}; "
            f"leaves compared {g['leaves_compared'][0]} of {g['leaves_compared'][1]}")
        return {
            "loss_gap": (g["loss_gap"], lim["loss_gap"]),
            "grad_gap": (g["grad_gap"], lim["grad_gap"]),
            "update_gap": (g["update_gap"], lim["update_gap"]),
            "ckpt_leaf_mismatch": (float(mismatch), 0.0),
            "resume_breaks": (float(breaks), 0.0),
        }


class _AnnotatedData:
    """The token feed, with a benchmark span around each batch it makes."""

    def __init__(self, inner, run):
        self.inner, self.run = inner, run

    def shard_batch_at(self, *a):
        with self.run.span("bench.feed"):
            return self.inner.shard_batch_at(*a)
