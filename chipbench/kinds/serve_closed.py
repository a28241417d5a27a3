"""Serving from a checkpoint commit: a closed loop of one client sending
batches of greedy requests to prefill and decode.

Traffic parameters (``chipbench/traffic/<name>.json``, kind
``serve_closed``): ``batch`` requests per batch; ``cycle``, {prompt length:
batches} making up one cycle, whose order the seed shuffles anew in every
cycle; ``gen_tokens`` greedy tokens per request; ``check_requests``, how
many finished requests the reference reads.

Set-up: the seeded weights, made on the device in one jitted call, are
saved as a checkpoint commit and restored from it with
``CheckpointManager.restore(subtree="params")``; the restored weights are
what is served. Prefill and decode (``repro.train.steps``) are compiled
ahead of time once per prompt length, the cache donated; each decode
program runs once on an empty cache before the window.

Window: whole cycles, until ``--seconds`` have passed. A batch is due when
the one before it has delivered its last token. Each token is copied to the
host as a streaming user would see it, and the argmax of each step is fed
back to the next. ``ttft_p95_ms`` is the 95th percentile over all requests
of the time from the batch being due to its first token on the host;
``itl_p95_ms`` the 95th percentile over every gap between consecutive tokens
of a request.

Checks (``correct``):

- ``token_gap``: over a sample of finished requests drawn from the seed, one
  of them of the longest prompt, the widest gap by which a served token's
  logit in the float32 reference lies below the reference's best logit at
  that position (logits);
- ``restore_mismatch``: leaves of the restored weights that differ from the
  weights saved (exact, limit 0).
"""
from __future__ import annotations

import os
import time

import numpy as np


def named(fn, name: str):
    """``fn`` under another name, so each compiled program has a name of its
    own in the trace (``jit_<name>``)."""
    def inner(*a):
        return fn(*a)
    inner.__name__ = inner.__qualname__ = name
    return inner


def schedule(traffic: dict, seed: int, cycles: int) -> list[int]:
    """The prompt length of each batch: every cycle holds the same lengths,
    in an order the seed shuffles anew for each cycle."""
    rng = np.random.default_rng([seed, 1])
    one = [int(p) for p, n in sorted(traffic["cycle"].items(), key=lambda kv: int(kv[0]))
           for _ in range(int(n))]
    return [p for _ in range(cycles) for p in rng.permutation(one).tolist()]


def prompts(traffic: dict, seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The prompts of batch ``index``: uniform over the real vocabulary."""
    rng = np.random.default_rng([seed, 2, index])
    return rng.integers(0, vocab, (int(traffic["batch"]), length), dtype=np.int32)


def sample(traffic: dict, seed: int, lengths: list[int]) -> list[tuple[int, int]]:
    """(batch, row) of the requests the reference reads: one from a batch of
    the longest prompt, the rest drawn from all batches."""
    rng = np.random.default_rng([seed, 3])
    b = int(traffic["batch"])
    longest = [i for i, p in enumerate(lengths) if p == max(lengths)]
    out = [(int(rng.choice(longest)), int(rng.integers(b)))]
    while len(out) < int(traffic["check_requests"]):
        pick = (int(rng.integers(len(lengths))), int(rng.integers(b)))
        if pick not in out:
            out.append(pick)
    return out


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


class Driver:
    def __init__(self, run):
        self.run = run
        t = run.traffic
        self.batch, self.gen = int(t["batch"]), int(t["gen_tokens"])
        self.lengths = sorted(int(p) for p in t["cycle"])

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.repo import Repository
        from repro.models import transformer as T
        from repro.train.checkpoint import CheckpointManager
        from repro.train.steps import make_decode_step, make_prefill_step

        from chipbench.harness import log, program_config

        run = self.run
        ref = run.cell.reference
        self.cfg = cfg = program_config(run.config)
        weights = ref.weights(ref.Model.from_config(run.config), run.seed)
        want = jax.tree.map(lambda d: tuple(d.shape), T.param_defs(cfg),
                            is_leaf=lambda d: hasattr(d, "spec"))
        got = jax.tree.map(lambda a: tuple(a.shape), weights)
        if got != want:
            raise ValueError(f"weights {got} do not have the program's shapes {want}")
        repo = Repository.init(os.path.join(run.work, "repo"))
        ckpt = CheckpointManager(repo)
        t0 = time.perf_counter()
        self.commit = ckpt.save(0, weights, {}, extra={"config": run.config["name"]})
        t1 = time.perf_counter()
        state, _ = ckpt.restore(self.commit, subtree="params")
        self.params = jax.block_until_ready(state["params"])
        t2 = time.perf_counter()
        same = jax.jit(lambda a, b: jax.tree.map(lambda x, y: jnp.all(x == y), a, b))(
            self.params, weights)
        self.restore_mismatch = sum(not bool(v) for v in jax.tree.leaves(same))
        del weights, state, same
        log(f"commit {self.commit[:12]}: save {t1 - t0!r} s, restore {t2 - t1!r} s")

        vocab = cfg.vocab_size
        self.argmax = jax.jit(named(
            lambda logits: jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)[:, None],
            "argmax")).lower(
                jax.ShapeDtypeStruct((self.batch, cfg.padded_vocab), jnp.bfloat16)).compile()
        self.prefill, self.decode = {}, {}
        tok = jnp.zeros((self.batch, 1), jnp.int32)
        pos = jnp.asarray(0, jnp.int32)
        for p in self.lengths:
            # compiled ahead of time: the window calls these and nothing else
            caches = T.abstract_cache(cfg, None, self.batch, p + self.gen)
            self.prefill[p] = jax.jit(named(make_prefill_step(cfg, None, p + self.gen),
                                            f"prefill_{p}")).lower(
                self.params, {"tokens": jax.ShapeDtypeStruct((self.batch, p), jnp.int32)}
            ).compile()
            self.decode[p] = jax.jit(named(make_decode_step(cfg, None), f"decode_{p}"),
                                     donate_argnums=(1,)).lower(
                self.params, caches, tok, pos).compile()
            # one decode step on an empty cache loads each program on the chip
            caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), caches)
            logits, caches = self.decode[p](self.params, caches, tok, pos)
            np.asarray(self.argmax(logits))
            del caches, logits
        log(f"compiled prefill and decode for prompt lengths {self.lengths}")

    def _serve(self, index: int, length: int):
        """One batch; returns (prompts, tokens [B, G], host arrival times)."""
        import jax.numpy as jnp

        run = self.run
        prompt = prompts(run.traffic, run.seed, index, length, self.cfg.vocab_size)
        prefill, decode, argmax = self.prefill[length], self.decode[length], self.argmax
        times = []
        toks = []
        with run.span("bench.prefill"):
            caches, logits = prefill(self.params, {"tokens": jnp.asarray(prompt)})
        with run.span("bench.argmax"):
            tok = argmax(logits)
        with run.span("bench.token_to_host"):
            toks.append(np.asarray(tok))
        times.append(time.perf_counter())
        for i in range(self.gen - 1):
            with run.span("bench.decode_step"):
                logits, caches = decode(self.params, caches, tok,
                                        jnp.asarray(length + i, jnp.int32))
            with run.span("bench.argmax"):
                tok = argmax(logits)
            with run.span("bench.token_to_host"):
                toks.append(np.asarray(tok))
            times.append(time.perf_counter())
        del caches
        return prompt, np.concatenate(toks, axis=1), times

    def window(self) -> dict:
        run = self.run
        per_cycle = sum(int(n) for n in run.traffic["cycle"].values())
        self.served = []  # (length, prompts, tokens)
        ttft, itl = [], []
        order = schedule(run.traffic, run.seed, 1 << 10)
        t0 = due = time.perf_counter()
        i = 0
        while True:
            length = order[i]
            prompt, toks, times = self._serve(i, length)
            ttft += [times[0] - due] * self.batch
            itl += list(np.diff(times)) * self.batch
            self.served.append((length, prompt, toks))
            due = times[-1]
            i += 1
            if i % per_cycle == 0 and due - t0 >= run.seconds:
                break
        wall = due - t0
        requests = i * self.batch
        run.data.update(served=[(p, len(t)) for p, _, t in self.served], wall_s=wall,
                        batch=self.batch, gen=self.gen, requests=requests)
        from chipbench.harness import log

        log(f"window: {i} batches, {requests} requests in {wall!r} s; "
            f"ttft p50 {float(np.median(ttft)) * 1e3!r} ms, "
            f"itl p50 {float(np.median(itl)) * 1e3!r} ms")
        bad = sum(int(np.any((t < 0) | (t >= self.cfg.vocab_size))) for _, _, t in self.served)
        return {"metrics": {"ttft_p95_ms": p95(ttft) * 1e3, "itl_p95_ms": p95(itl) * 1e3},
                "attempted": requests, "failed": bad * self.batch}

    def release(self):
        import gc

        del self.params, self.prefill, self.decode
        gc.collect()

    def readings(self, control: bool = False) -> dict:
        """``token_gap`` over the sampled requests; with ``control``, also
        ``control_gap``: the same gap for the tokens that the reference
        computed in float8 puts first at each of those positions."""
        run = self.run
        ref = run.cell.reference
        model = ref.Model.from_config(run.config)
        picks = sample(run.traffic, run.seed, [s[0] for s in self.served])
        prompts_ = [self.served[b][1][r] for b, r in picks]
        served = [self.served[b][2][r] for b, r in picks]
        weights = ref.weights(model, run.seed)
        length = max(self.lengths) + self.gen
        logits = ref.served_gaps(model, weights, prompts_, served, length)
        out = {"token_gap": ref.widest_gap(logits, np.stack(served))}
        if control:
            low = ref.served_gaps(model, weights, prompts_, served, length, prec="fp8")
            out["control_gap"] = ref.widest_gap(logits, low.argmax(axis=-1))
        return out

    def check(self) -> dict:
        lim = self.run.cell.limits
        return {"token_gap": (self.readings()["token_gap"], lim["token_gap"]),
                "restore_mismatch": (float(self.restore_mismatch), 0.0)}
