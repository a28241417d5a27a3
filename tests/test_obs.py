"""The program's own instrumentation: the spans a training job writes into a
profiler trace, the compile counter, and the names inside the compiled
programs."""
import glob
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.core.repo import Repository
from repro.kernels import ops
from repro.data.tokens import SyntheticTokens
from repro.models import transformer as T
from repro.models.params import init_params
from repro.optim.adamw import AdamW
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import train_segment

CFG = configs.get_smoke("qwen3_0_6b")


def _program_events(trace_dir: str) -> list[dict]:
    """Every ``repro.*`` host event of the trace: name, start and end in
    seconds, stats, and the host line (thread) it was written on."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append({"name": e.name, "start": e.start_ns * 1e-9,
                                "end": (e.start_ns + e.duration_ns) * 1e-9,
                                "attrs": dict(e.stats), "line": (plane.name, i)})
    return sorted(out, key=lambda e: e["start"])


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _within(inner, outer) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def test_train_segment_writes_its_spans_into_the_trace(tmp_path):
    """Two chained jobs under the profiler: the first starts fresh and saves
    asynchronously at steps 2 and 4, the second resumes at 4 and saves at 6.
    Each span is where the work is, nested as the loop runs it, with the
    attributes that join one job's spans."""
    repo = Repository.init(str(tmp_path / "r"), annex_threshold=1024)
    ds = SyntheticTokens(vocab_size=CFG.vocab_size, seq_len=16, global_batch=2, seed=1)
    with jax.profiler.trace(str(tmp_path / "trace")):
        train_segment(repo, CFG, ds, n_steps=4, ckpt_every=2, seed=0, async_ckpt=True)
        train_segment(repo, CFG, ds, n_steps=6, ckpt_every=2, seed=0, async_ckpt=True)
    ev = _program_events(str(tmp_path / "trace"))

    jobs = _named(ev, "repro.train.segment")
    assert [(j["attrs"]["start_step"], j["attrs"]["end_step"]) for j in jobs] == [(0, 4), (4, 6)]
    firsts = _named(ev, "repro.train.first_step")
    assert [f["attrs"]["step"] for f in firsts] == [0, 4]  # once per job
    assert [s["attrs"]["step"] for s in _named(ev, "repro.train.step")] == [1, 2, 3, 5]
    for name in ("repro.train.feed", "repro.train.loss"):
        assert [s["attrs"]["step"] for s in _named(ev, name)] == list(range(6))
    main = jobs[0]["line"]
    for name in ("repro.train.first_step", "repro.train.step", "repro.train.feed",
                 "repro.train.loss", "repro.ckpt.snapshot"):
        for s in _named(ev, name):
            assert s["line"] == main and any(_within(s, j) for j in jobs), (name, s)
    # the fresh job compiles its step inside its first call
    compiles = [c for c in _named(ev, "repro.compile") if _within(c, firsts[0])]
    assert compiles and all(c["attrs"]["seconds"] > 0 and c["attrs"]["fun"] for c in compiles)

    # only the second job restores: fetch inside it, then ready on a thread
    # of its own until the leaves are on the device
    (restore,) = [r for r in _named(ev, "repro.ckpt.restore") if "step" in r["attrs"]]
    assert restore["attrs"]["step"] == 4 and restore["attrs"]["bytes"] > 0
    assert _within(restore, jobs[1]) and restore["end"] <= firsts[1]["start"]
    (fetch,) = _named(ev, "repro.ckpt.restore.fetch")
    assert _within(fetch, restore)
    (ready,) = _named(ev, "repro.ckpt.restore.ready")
    assert ready["attrs"]["step"] == 4 and ready["line"] != main
    assert restore["end"] <= ready["start"] <= ready["end"]

    snaps = _named(ev, "repro.ckpt.snapshot")
    writes = _named(ev, "repro.ckpt.write")
    assert [s["attrs"]["step"] for s in snaps] == [w["attrs"]["step"] for w in writes] == [2, 4, 6]
    assert all(s["attrs"]["bytes"] == w["attrs"]["bytes"] == restore["attrs"]["bytes"]
               for s, w in zip(snaps, writes))
    assert all(w["line"] != main for w in writes)  # async saves write on a worker
    commits = _named(ev, "repro.ckpt.commit")
    assert len(commits) == 3 and all(_within(c, w) for c, w in zip(commits, writes))
    # the loop waits for an in-flight save: before the next, and at the end
    waits = _named(ev, "repro.ckpt.wait")
    assert len(waits) == 3 and all(w["line"] == main for w in waits)


def test_compile_counter_rises_on_a_fresh_jit_only():
    x, y = jnp.arange(8.0), jnp.ones(8)
    f = jax.jit(lambda a: a * 3.0 + 1.0)
    before = obs.counters()
    f(x).block_until_ready()
    after = obs.counters()
    f(y).block_until_ready()  # same shapes: no compile
    again = obs.counters()
    assert after["compile"] - before.get("compile", 0) == 1
    assert after["compile_s"] > before.get("compile_s", 0)
    assert after["compile:jit(<lambda>)"] - before.get("compile:jit(<lambda>)", 0) == 1
    assert again["compile"] == after["compile"]


def test_count_adds_to_the_table():
    before = obs.counters().get("test.count", 0)
    obs.count("test.count", 2)
    obs.count("test.count")
    assert obs.counters()["test.count"] == before + 3


def test_a_restored_state_can_be_donated_at_once(tmp_path):
    """The restore's readiness watcher never raises, even where the caller
    donates the restored leaves before the watcher looks at them, and the
    caller never waits for it."""
    repo = Repository.init(str(tmp_path / "r"), annex_threshold=1024)
    params = init_params(T.param_defs(CFG), seed=0)
    ckpt = CheckpointManager(repo)
    ckpt.save(1, params, AdamW().init(params))
    raised = []
    hook, threading.excepthook = threading.excepthook, raised.append
    try:
        state, _ = ckpt.restore()
        consumed = jax.jit(lambda t: jax.tree.map(lambda a: a + 0, t),
                           donate_argnums=0)(state["params"])
        ckpt.wait()  # joins the watcher
    finally:
        threading.excepthook = hook
    assert not raised and ckpt._ready is None
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(consumed), jax.tree.leaves(params)))


def test_forward_train_names_its_parts():
    params = init_params(T.param_defs(CFG), seed=0)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    text = jax.jit(lambda p, b: T.forward_train(CFG, None, p, b)).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("embed", "attention", "ffn", "head"):
        assert f"/{scope}/" in text, scope


def _kernel(name):
    """(a call of the kernel in interpret mode, its arguments), small."""
    f32 = jnp.float32
    z = lambda *shape: jnp.zeros(shape, f32)  # noqa: E731
    if name == "flash_fwd":
        return (lambda q, k, v: ops.flash_attention(q, k, v, True, None, True),
                (z(1, 128, 2, 64), z(1, 128, 2, 64), z(1, 128, 2, 64)))
    if name == "rwkv6":
        seq = z(1, 64, 2, 32)
        return (lambda *a: ops.rwkv6(*a, True), (seq, seq, seq, seq, z(2, 32), z(1, 2, 32, 32)))
    if name == "ssd_fwd":
        return (lambda *a: ops.ssd(*a, chunk=32, interpret=True),
                (z(1, 64, 4, 16), z(1, 64, 4), z(4), z(1, 64, 1, 8), z(1, 64, 1, 8),
                 z(1, 4, 16, 8)))
    return (lambda *a: ops.mamba_scan(*a, True),
            (z(1, 64, 64), z(1, 64, 64), z(64, 8), z(1, 64, 8), z(1, 64, 8), z(1, 64, 8)))


@pytest.mark.parametrize("name", ["flash_fwd", "rwkv6", "mamba_scan", "ssd_fwd"])
def test_each_kernel_carries_its_name(name):
    call, args = _kernel(name)
    text = jax.jit(call).lower(*args).as_text(debug_info=True)
    assert f"/{name}/pallas_call" in text
