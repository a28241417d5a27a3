"""The program's spans (``repro.obs``) read beside the benchmark's, on the
synthetic trace of ``test_yardstick`` and on a trace the profiler writes."""
import os
from types import SimpleNamespace

import pytest

from chipbench import peaks, program_spans, trace
from chipbench.tests.test_yardstick import FLASH, S, _model, _planes
from chipbench.tests.tiny import BENCH_DIR

# A job resuming from step 3, its first step, two saves, two compiles inside
# the window and one after it, and a restore that found no commit.
PROGRAM_SPANS = [
    ("repro.train.segment", 0, int(4.2 * S), {"start_step": 3, "end_step": 80}),
    ("repro.ckpt.restore", 0, S // 2, {"step": 3, "bytes": 2_000_000_000}),
    ("repro.ckpt.restore.fetch", S // 10, S // 5, {}),
    ("repro.train.first_step", int(0.9 * S), S // 2, {"step": 3}),
    ("repro.compile", S, 0, {"fun": "jit(train_step)", "seconds": 0.4, "cache_hit": 1}),
    ("repro.ckpt.snapshot", int(2.1 * S), int(0.8 * S), {"step": 40, "bytes": 2_000_000_000}),
    ("repro.ckpt.restore", int(4.3 * S), S // 100, {}),
    ("repro.ckpt.snapshot", int(4.6 * S), S // 10, {"step": 80, "bytes": 1_000_000_000}),
    ("repro.compile", int(4.9 * S), 0, {"fun": "jit(add)", "seconds": 0.01}),
    ("repro.compile", 6 * S, 0, {"fun": "jit(add)", "seconds": 0.01}),
]
# on other threads: the restore's readiness watcher and the async writes
PROGRAM_THREADS = [
    ("repro.ckpt.restore.ready", int(0.4 * S), S // 2, {"step": 3}),
    ("repro.ckpt.write", int(2.9 * S), S, {"step": 40, "bytes": 2_000_000_000}),
    ("repro.ckpt.commit", int(3.5 * S), int(0.4 * S), {"step": 40}),
    ("repro.ckpt.write", int(4.7 * S), S // 10, {"step": 80, "bytes": 1_000_000_000}),
]


def _with_program_spans(planes):
    """``planes`` with the program's spans added to its host plane."""
    out = []
    for name, lines in planes:
        if name.startswith("/host"):
            lines = lines + [("python", PROGRAM_SPANS), ("python", PROGRAM_THREADS)]
        out.append((name, lines))
    return out


def _serve_planes():
    """The synthetic trace with a prefill and a decode run added on chip 0."""
    planes = []
    for name, lines in _planes():
        if name == "/device:TPU:0":
            lines = [(ln, ev + ([(FLASH, int(2.0 * S), S // 4)] if ln == "XLA Ops" else
                                [("jit_prefill_64(3)", 2 * S, S // 2),
                                 ("jit_decode_64(4)", int(2.6 * S), S // 5)]
                                if ln == "XLA Modules" else []))
                     for ln, ev in lines]
        planes.append((name, lines))
    return planes


NEW_READERS = {"ckpt_resume_s", "ckpt_snapshot_s", "ckpt_write_gbps", "train_first_step_s",
               "compiles_in_window.train"}
FIRST_READERS = {"ckpt_save_stall_s", "ckpt_restore_s", "train_mfu", "flash_fwd_roofline.train",
                 "idle_share.train", "prefill_mfu", "flash_fwd_roofline.serve",
                 "decode_roofline", "idle_share.serve"}


def _readers():
    from chipbench.harness import _module_at, load_benchmark

    return {m["name"]: _module_at(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
                                  "program_spans_" + m["name"].replace(".", "_")).read
            for m in load_benchmark()["per_layer"]}


def _run(red):
    seg = SimpleNamespace(restore_s=3.5, save_s=2.8)
    return SimpleNamespace(red=red, traffic={"cycle": {"64": 1}}, peak=peaks.peak("TPU v5 lite"),
                           model=_model("qwen3-0.6b"), cell=SimpleNamespace(chips=1),
                           data={"batch": 2, "seq": 2048, "gen": 8, "segments": [seg], "saves": 2})


@pytest.mark.parametrize("planes", [_planes, _serve_planes])
def test_program_spans_leave_every_earlier_reading_as_it_was(planes):
    plain = trace.reduce_planes(planes())
    red = program_spans.reduce_planes(_with_program_spans(planes()))
    assert (red.window, red.busy_s, red.gaps, red.chips) == \
        (plain.window, plain.busy_s, plain.gaps, plain.chips)
    assert red.ops == plain.ops and red.modules == plain.modules and red.spans == plain.spans
    assert trace.breakdown(red) == trace.breakdown(plain)
    readers = _readers()
    earlier = set(readers) - NEW_READERS
    # the first benchmark's nine, and every reader added since as a file
    assert FIRST_READERS <= earlier
    assert earlier == {f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))
                       if f.endswith(".py")} - NEW_READERS
    for name in earlier:
        assert readers[name](_run(red)) == readers[name](_run(plain)), name
    if planes is _serve_planes:  # the serving readers find their programs
        assert all(readers[n](_run(red)) is not None
                   for n in ("prefill_mfu", "decode_roofline", "flash_fwd_roofline.serve"))


def test_idle_gaps_go_to_the_innermost_program_span():
    """The gap at 1.5-3.0 s has its middle inside the program's snapshot,
    around the benchmark's own span; the one at 4.0-5.0 s inside no program
    span, so it falls back to the benchmark's."""
    red = program_spans.reduce_planes(_with_program_spans(_planes()))
    assert dict(program_spans.idle_gaps(red)) == pytest.approx(
        {"repro.ckpt.snapshot": 1.5, "bench.segment": 1.0})
    # without the program's spans it reads as the benchmark's breakdown does
    plain = trace.reduce_planes(_planes())
    assert program_spans.idle_gaps(plain) == trace.breakdown(plain)["idle_gaps"]


@pytest.mark.parametrize("name,want", [
    ("ckpt_resume_s", 0.9),  # restore at 0, ready until 0.9
    ("ckpt_snapshot_s", (0.8 + 0.1) / 2),
    ("ckpt_write_gbps", 3.0 / 1.1),  # 3 GB over 1.0 + 0.1 s
    ("train_first_step_s", 0.5),
    ("compiles_in_window.train", 2),  # the one at 6 s is after the window
])
def test_program_span_readers_by_hand(name, want):
    read = _readers()[name]
    red = program_spans.reduce_planes(_with_program_spans(_planes()))
    assert read(_run(red)) == pytest.approx(want)
    # a program that writes no such spans reads as unmeasured
    assert read(_run(program_spans.reduce_planes(_planes()))) is None
    assert read(_run(trace.reduce_planes(_planes()))) is None
    assert read(_run(None)) is None


def test_program_span_stats_become_attrs():
    red = program_spans.reduce_planes(_with_program_spans(_planes()))
    (first,) = program_spans.spans(red, "repro.train.first_step")
    assert first.attrs == {"step": 3} and first.start == pytest.approx(0.9)
    assert [s.attrs.get("step") for s in program_spans.spans(red, "repro.ckpt.restore")] == [3, None]
    assert all(s.name.startswith("repro.") for s in red.program)


def test_program_spans_are_read_from_a_profiler_trace(tmp_path):
    """The host planes of a trace the profiler wrote carry the program's
    span with its attributes, and nothing of the benchmark's."""
    import jax
    import jax.numpy as jnp

    from repro import obs

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("repro.train.step", step=7):
                jnp.ones(4).block_until_ready()
    found = program_spans.extract(program_spans.host_planes(str(tmp_path)),
                                  (0.0, float("inf")))
    steps = [s for s in found if s.name == "repro.train.step"]
    assert len(steps) == 1 and steps[0].attrs.get("step") == 7 and steps[0].dur > 0
    assert all(s.name.startswith("repro.") for s in found)


def test_reading_a_trace_carries_the_program_spans():
    """Loading the readers wraps ``trace.read`` once, and no more."""
    _readers()
    _readers()
    assert trace.read.with_program_spans
    assert not getattr(trace.read.__closure__[0].cell_contents, "with_program_spans", False)
