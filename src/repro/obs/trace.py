"""Span tracing with Chrome/Perfetto ``trace_event`` JSON export.

Usage::

    with start_tracing() as collector:
        simulate(trace, method)
    collector.write_chrome_trace("run.json")     # open in ui.perfetto.dev

Hot paths are instrumented with ``with span("engine/sizing_wave",
n=len(wave)): ...``. When no collector is installed (the default),
:func:`span` returns a shared null context manager after a single
module-global ``None`` check — no clock reads, no allocation — so the
disabled cost on the 100k-task replay is ~zero.

Each recorded span carries an id and the id of the span it was opened
in (its parent; ``None`` at the root). The open span is held in a
``contextvars.ContextVar``, so spans of interleaved asyncio tasks nest
under their own task's spans. While a collector is installed, every
span also opens a ``jax.profiler.TraceAnnotation`` of the same name: a
``jax.profiler`` trace then shows the program's spans beside the device
operations, on the profiler's clock. A span held open across an
``await`` is opened with :func:`async_span` and is not mirrored (the
profiler's annotations nest per thread, not per task).

Span *counts* are deterministic: spans sit at step/wave/dispatch
granularity, which is a pure function of (trace, config, seed).
``BENCH_obs.json`` gates them at zero growth. Span *durations* are
wall-clock and excluded from every gate.

Side-effect-free by construction: no rng use, no event reordering, no
feedback into sizing arithmetic — bitwise invariants hold with tracing
on. Stdlib only at import; ``jax`` is imported by :func:`start_tracing`.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import time

__all__ = ["TraceCollector", "async_span", "span", "start_tracing",
           "stop_tracing", "tracing", "tracing_active"]


class TraceCollector:
    """Accumulates completed spans and per-name counts.

    ``spans`` holds ``(name, start_ns, dur_ns, args, id, parent)``
    tuples in completion order: ``start_ns`` on ``perf_counter_ns``,
    ``parent`` the id of the enclosing span or ``None``. ``span_counts``
    is the deterministic per-name tally used by the bench gates."""

    def __init__(self):
        self.spans: list[tuple[str, int, int, dict, int, int | None]] = []
        self.span_counts: collections.Counter = collections.Counter()
        self._ids = itertools.count(1)
        self._t0_ns = time.perf_counter_ns()

    def total_spans(self) -> int:
        return sum(self.span_counts.values())

    def to_chrome_trace(self) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON object (complete events);
        each event's ``args`` carry its span ``id`` and ``parent``."""
        t0 = self._t0_ns
        events = [{
            "name": name,
            "cat": "repro",
            "ph": "X",
            "pid": 1,
            "tid": 1,
            "ts": (start - t0) / 1000.0,
            "dur": dur / 1000.0,
            "args": {**args, "id": sid, "parent": parent},
        } for name, start, dur, args, sid, parent in self.spans]
        return {"displayTimeUnit": "ms", "traceEvents": events}

    def write_chrome_trace(self, path) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(), fh)


_COLLECTOR: TraceCollector | None = None
# jax.profiler.TraceAnnotation, bound by the first start_tracing()
_ANNOTATION = None
# id of the innermost open span of the running thread / asyncio task
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=None)


class _Span:
    __slots__ = ("_col", "name", "args", "id", "parent", "start_ns",
                 "_token", "_ann")

    def __init__(self, col: TraceCollector, name: str, args: dict,
                 annotation):
        self._col = col
        self.name = name
        self.args = args
        self._ann = None if annotation is None else annotation(name)

    def __enter__(self):
        self.id = next(self._col._ids)
        self.parent = _CURRENT.get()
        self._token = _CURRENT.set(self.id)
        if self._ann is not None:
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self.start_ns
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _CURRENT.reset(self._token)
        col = _COLLECTOR
        if col is not None:
            col.spans.append((self.name, self.start_ns, dur, self.args,
                              self.id, self.parent))
            col.span_counts[self.name] += 1
        return False

    def set(self, **args) -> None:
        """Add args known only inside the span (e.g. bytes written)."""
        self.args.update(args)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, **args):
    """Context manager timing one named region, mirrored as a profiler
    annotation. Near-free when tracing is off (one global ``None``
    check, shared null object)."""
    col = _COLLECTOR
    if col is None:
        return _NULL_SPAN
    return _Span(col, name, args, _ANNOTATION)


def async_span(name: str, **args):
    """:func:`span` for a region held open across an ``await``: recorded
    with its parent link, but not mirrored as a profiler annotation."""
    col = _COLLECTOR
    if col is None:
        return _NULL_SPAN
    return _Span(col, name, args, None)


def tracing_active() -> bool:
    return _COLLECTOR is not None


def start_tracing() -> TraceCollector:
    """Install (and return) a fresh collector as the active one."""
    global _COLLECTOR, _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    _COLLECTOR = TraceCollector()
    return _COLLECTOR


def stop_tracing() -> TraceCollector | None:
    """Deactivate tracing; returns the collector that was active."""
    global _COLLECTOR
    col = _COLLECTOR
    _COLLECTOR = None
    return col


@contextlib.contextmanager
def tracing():
    """``with tracing() as collector: ...`` — scoped start/stop. Restores
    the previously active collector on exit, so nesting is safe."""
    global _COLLECTOR
    prev = _COLLECTOR
    col = start_tracing()
    try:
        yield col
    finally:
        _COLLECTOR = prev
