"""The program's own instrumentation: spans on the profiler's clock, and a
table of counters.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``. It is written
into the trace only while a profiler session is running, so it shares the
device trace's clock; with no session it costs about a microsecond. The
attributes become the event's stats; ``set_metadata`` on the open span adds
more once they are known. Span names start with ``repro.``.

A ``jax.monitoring`` listener, registered when this module is imported,
counts every backend compile (``compile`` and ``compile_s``, in total and
per function as ``compile:<fun>`` and ``compile_s:<fun>``) and writes a
zero-length ``repro.compile`` event (attrs ``fun``, ``seconds``, and
``cache_hit`` where the persistent compilation cache was consulted) into the
trace, inside whatever span triggered the compile.
"""
from __future__ import annotations

import threading
from collections import defaultdict

import jax.monitoring
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_counts: dict[str, float] = defaultdict(float)
# what the persistent cache said about the compile in progress, per thread:
# the cache events fire inside the compile they belong to
_cache = threading.local()


def span(name: str, **attrs) -> TraceAnnotation:
    """A host span named ``name`` in the profiler's trace, with ``attrs``."""
    return TraceAnnotation(name, **attrs)


def count(name: str, n: float = 1) -> None:
    with _lock:
        _counts[name] += n


def counters() -> dict[str, float]:
    """A copy of the counter table."""
    with _lock:
        return dict(_counts)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_ASKED:
        _cache.hit = 0
    elif event == _CACHE_HIT:
        _cache.hit = 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    fun = str(kw.get("fun_name", "?"))
    for key in ("compile", f"compile:{fun}"):
        count(key)
    for key in ("compile_s", f"compile_s:{fun}"):
        count(key, seconds)
    attrs = {"fun": fun, "seconds": seconds}
    hit = getattr(_cache, "hit", None)
    if hit is not None:
        attrs["cache_hit"] = hit
        _cache.hit = None
    with TraceAnnotation("repro.compile", **attrs):
        pass


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
