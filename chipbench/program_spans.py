"""The program's own spans in a traced run, beside the benchmark's.

``repro.obs`` writes host events whose names start with ``repro.`` into the
profiler's trace, on the device trace's clock, with its attributes as the
events' stats. ``chipbench.trace`` keeps only the benchmark's ``bench.*``
spans, and the harness deletes the trace once it has read it. So importing
this module (the readers of the program-span metrics do, when a cell loads
them) wraps ``trace.read``: the ``Reduced`` it returns is the same object as
before, with one attribute more, ``program``, the program's events in the
window, each with its stats as ``attrs``. The wrapper also logs the idle
gaps charged to the innermost program span at their middle
(``idle_gaps``). A program without the spans yields an empty ``program``,
and the readers then return None.
"""
from __future__ import annotations

import sys
from collections import defaultdict
from dataclasses import dataclass, field

from chipbench import trace

PREFIX = "repro."  # the prefix of the program's span names


@dataclass
class Span(trace.Op):
    attrs: dict = field(default_factory=dict)  # the event's stats


def extract(planes, window: tuple[float, float]) -> list[Span]:
    """The program's events on the host planes of ``planes`` that start
    inside ``window``, in order of their start. ``planes`` is laid out as
    ``trace.reduce_planes`` takes it, except that an event may carry its
    stats, a dict, as a fourth value."""
    lo, hi = window
    out = [Span(n, s * 1e-9, d * 1e-9, dict(st[0]) if st else {})
           for pname, lines in planes if pname.startswith("/host:")
           for _, events in lines
           for n, s, d, *st in events if n.startswith(PREFIX)]
    return sorted((s for s in out if lo <= s.start < hi), key=lambda s: s.start)


def without_program(planes):
    """``planes`` as ``trace.reduce_planes`` takes them: no stats, and none
    of the program's events."""
    return [(pname, [(ln, [(n, s, d) for n, s, d, *_ in events if not n.startswith(PREFIX)])
                     for ln, events in lines])
            for pname, lines in planes]


def reduce_planes(planes) -> trace.Reduced:
    """``trace.reduce_planes`` of ``planes``, carrying the program's events
    as ``program``."""
    red = trace.reduce_planes(without_program(planes))
    red.program = extract(planes, red.window)
    return red


def host_planes(trace_dir: str):
    """The host planes of the newest trace under ``trace_dir``, with the
    program's events' stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace.newest_xplane(trace_dir))
    return [(plane.name, [(line.name, [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                                       for e in line.events if e.name.startswith(PREFIX)])
                          for line in plane.lines])
            for plane in pd.planes if plane.name.startswith("/host:")]


def spans(red, name: str) -> list[Span]:
    """The program's spans named ``name`` in the window of ``red``; none
    where the trace was read without them."""
    return [s for s in getattr(red, "program", ()) if s.name == name]


def idle_gaps(red, top: int = 10) -> list[list]:
    """The idle gaps of ``red`` charged to the innermost program span open
    at each gap's middle, or, where none is, to the innermost benchmark
    span: the benchmark's spans wrap the program's calls from outside, so
    they nest inside the program's. A zero-length event (``repro.compile``)
    marks an instant and is passed over."""
    mids = [0.5 * (a + b) for a, b in red.gaps]
    program = [s for s in getattr(red, "program", ()) if s.dur > 0]
    per_gap: dict[str, float] = defaultdict(float)
    for (a, b), name, fallback in zip(red.gaps, trace.innermost(program, mids),
                                      trace.innermost(red.spans, mids)):
        per_gap[name if name.startswith(PREFIX) else fallback] += b - a
    return [[k, v] for k, v in sorted(per_gap.items(), key=lambda kv: -kv[1])[:top]]


def _read(original):
    def read(trace_dir: str) -> trace.Reduced:
        red = original(trace_dir)
        red.program = extract(host_planes(trace_dir), red.window)
        print(f"program spans in the window: {len(red.program)}; idle gaps by "
              f"program span: {idle_gaps(red)}", file=sys.stderr, flush=True)
        return red
    read.with_program_spans = True
    return read


if not getattr(trace.read, "with_program_spans", False):
    trace.read = _read(trace.read)
