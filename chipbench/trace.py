"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone. What it uses of the trace:

- device planes ``/device:TPU:<n>``: the line ``XLA Ops`` (one event per
  operation run, named by its HLO text) and the line ``XLA Modules`` (one
  event per run of a compiled program, named ``jit_<function>(<id>)``);
- host planes: the benchmark's own ``TraceAnnotation`` spans, whose names
  start with ``bench.``; the span ``bench.window`` marks the traced window.

Busy time is the union of a chip's operation intervals inside the window;
the idle share is one minus busy over the window. Each idle gap is charged
to the innermost benchmark span that covers its middle on the host.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"  # the benchmark span that marks the measured window
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SHAPE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


@dataclass
class Op:
    name: str  # the HLO text of the operation
    start: float  # seconds on the trace's clock
    dur: float


@dataclass
class Reduced:
    window: tuple[float, float]
    busy_s: float  # mean over chips
    ops: list[Op]  # chip 0's operations inside the window
    modules: list[Op]  # chip 0's program runs inside the window
    spans: list[Op]  # the benchmark's host spans inside the window
    chips: int
    gaps: list[tuple[float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def module_runs(self, function: str) -> list[Op]:
        """Every run of the program jitted from ``function``."""
        prefix = f"jit_{function}("
        return [m for m in self.modules if m.name.startswith(prefix)]


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def operand_shapes(op_text: str) -> list[tuple[int, ...]]:
    """Shapes of an HLO operation's result and operands, in order of
    appearance: ``%x = bf16[2,16,2048,128]{...} custom-call(bf16[...] ...)``."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _SHAPE.finditer(op_text)]


def reduce_planes(planes) -> Reduced:
    """``planes``: iterable of (name, [(line name, [(event name, start_ns,
    duration_ns)])]) — what ``ProfileData`` holds, in plain values so the
    reduction can be checked on a small synthetic trace."""
    spans: list[Op] = []
    devices: dict[str, dict[str, list[Op]]] = {}
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            dev = devices.setdefault(pname, {"ops": [], "modules": []})
            for lname, events in lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(lname)
                if key:
                    dev[key].extend(Op(n, s * 1e-9, d * 1e-9) for n, s, d in events)
        elif pname.startswith("/host:"):
            for _, events in lines:
                spans.extend(Op(n, s * 1e-9, d * 1e-9) for n, s, d in events
                             if n.startswith("bench."))
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    w = max(windows, key=lambda s: s.dur)
    lo, hi = w.start, w.start + w.dur
    busy, first = [], None
    for pname in sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1])):
        dev = devices[pname]
        merged = _union(_clip([(o.start, o.start + o.dur) for o in dev["ops"]], lo, hi))
        busy.append(sum(b - a for a, b in merged))
        if first is None:
            first = (dev, merged)
    dev, merged = first
    inside = lambda o: o.start < hi and o.start + o.dur > lo  # noqa: E731
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    return Reduced(
        window=(lo, hi), busy_s=sum(busy) / len(busy),
        ops=[o for o in dev["ops"] if inside(o)],
        modules=[o for o in dev["modules"] if inside(o)],
        spans=[s for s in spans if inside(s) and s is not w],
        chips=len(devices), gaps=gaps)


def read(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    planes = []
    for plane in pd.planes:
        name = plane.name
        if not (DEVICE_PLANE.match(name) or name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(name) and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            lines.append((line.name, [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]))
        planes.append((name, lines))
    return reduce_planes(planes)


def _short(op_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = op_text.split(" = ", 1)[0]
    return head.lstrip("%")


def module_of(red: Reduced):
    """A function giving, for an op, the name of the program run it lies in
    (``jit_<function>``), or ``?``."""
    mods = sorted(red.modules, key=lambda m: m.start)
    starts = [m.start for m in mods]

    def find(o: Op) -> str:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < mods[i].start + mods[i].dur:
            return mods[i].name.split("(", 1)[0]
        return "?"
    return find


def self_times(ops: list[Op]):
    """(op, its duration less that of the ops nested in it): a ``while``
    loop or a call holds the operations of its body on the same line."""
    ordered = sorted(ops, key=lambda o: (o.start, -o.dur))
    own = [o.dur for o in ordered]
    stack: list[int] = []
    for i, o in enumerate(ordered):
        while stack and ordered[stack[-1]].start + ordered[stack[-1]].dur <= o.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= o.dur
        stack.append(i)
    return zip(ordered, own)


def innermost(spans: list[Op], times: list[float]) -> list[str]:
    """For each time, the name of the innermost span open at that time
    (spans of one host thread nest), or ``bench.other``. One sweep."""
    events = []
    for i, s in enumerate(spans):
        events.append((s.start, 1, i))
        events.append((s.start + s.dur, 0, i))
    for j, t in enumerate(times):
        events.append((t, 2, j))
    events.sort()
    open_: list[int] = []
    out = ["bench.other"] * len(times)
    for _, kind, i in events:
        if kind == 1:
            open_.append(i)
        elif kind == 0:
            open_.remove(i)
        elif open_:
            out[i] = spans[open_[-1]].name
    return out


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time by what
    the host was doing, as the result line carries them."""
    find = module_of(red)
    per_op: dict[str, float] = defaultdict(float)
    for o, own in self_times(red.ops):
        per_op[f"{find(o)}:{_short(o.name)}"] += own
    per_gap: dict[str, float] = defaultdict(float)
    for (a, b), name in zip(red.gaps, innermost(red.spans, [0.5 * (a + b) for a, b in red.gaps])):
        per_gap[name] += b - a
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(per_gap.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
