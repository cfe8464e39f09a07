"""In-memory span recorder for the planner's host code.

Spans and counters sit at the host boundaries of the planner's layers
(`fl.sim.run_many`, `_prepare`, `_solve_horizons`, `_dispatch_group`,
`core.monotonic_jax.solve_pairs_fused`), never inside a function JAX
traces, with one exception: the counter `engine.subprogram_traces` sits in
the Python bodies of the engine's jitted sub-programs (the leader step in
`fl.engine_common`, one client's local training in `fl.client`).  Those
bodies run only when JAX misses its trace cache, while the dispatch that
traces them is the innermost open span, and the count adds nothing to the
traced program.  Recording is off by default: `span` then hands back one shared
`contextlib.nullcontext()` and `count` returns at once, with no clock
read and no allocation of its own.

    from repro import tracing
    tracing.enable()
    run_many(cfgs, engine="scan")
    for s in tracing.spans():
        print(s.name, s.seconds, s.counts, s.attrs)

On, each span records its name, id, parent, root (the outermost open span
when it opened: one `sim.run_many` call shares one root), start and end on
`time.perf_counter()`, keyword attributes, counts added by `count` while
it was the innermost open span, and `compile_s`: the seconds of JAX's
`/jax/core/compile/*` events that fired while it was the innermost open
span, by event (`jaxpr_trace`, `jaxpr_to_mlir_module`, `backend_compile`;
the last one holds a persistent-cache load).  An event that fires inside
another (a jit traced while an outer one is traced or lowered) counts
once, in the outer.  Each span also opens a
`jax.profiler.TraceAnnotation` of its name, so a profiled stretch shows
it beside the device ops.
"""
from __future__ import annotations

import contextlib
import itertools
import time

import jax

__all__ = ["enable", "disable", "reset", "spans", "span", "timed", "count",
           "Span"]

COMPILE_EVENT_PREFIX = "/jax/core/compile/"

_NULL = contextlib.nullcontext()
_on = False
_spans: list["Span"] = []
_stack: list["Span"] = []
_ids = itertools.count(1)
_listener = None


class Span:
    """One interval on `time.perf_counter()`, as a context manager; with
    `record` it is also a recorded span."""

    __slots__ = ("name", "id", "parent", "root", "start", "end", "attrs",
                 "counts", "_events", "_record", "_annotation")

    def __init__(self, name: str, attrs: dict, record: bool = True):
        self.name, self.attrs, self.counts = name, attrs, {}
        self._events: list[tuple[float, float, str]] = []  # (start, s, key)
        self.id = self.parent = self.root = self.end = None
        self._record = record

    def __enter__(self) -> "Span":
        if self._record:
            parent = _stack[-1] if _stack else None
            self.id = next(_ids)
            self.parent = parent.id if parent else None
            self.root = parent.root if parent else self.id
            _spans.append(self)
            _stack.append(self)
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        if self._record:
            self._annotation.__exit__(*exc)
            _stack.remove(self)
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def compile_s(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for _, secs, key in self._events:
            out[key] = out.get(key, 0.0) + secs
        return out


def span(name: str, **attrs):
    """A span named `name` around a block; the shared null context when
    recording is off."""
    return Span(name, attrs) if _on else _NULL


def timed(name: str, **attrs) -> Span:
    """Like `span`, for an interval the caller also needs the length of:
    the clock is read whether or not recording is on, and `.seconds`
    holds the same reading the span records."""
    return Span(name, attrs, record=_on)


def count(name: str, n: int = 1) -> None:
    """Add `n` to the counter `name` of the innermost open span (dropped
    when no span is open, or recording is off)."""
    if _on and _stack:
        top = _stack[-1].counts
        top[name] = top.get(name, 0) + n


def _on_duration(event: str, secs: float, **kw) -> None:
    if _stack and event.startswith(COMPILE_EVENT_PREFIX):
        events, start = _stack[-1]._events, time.perf_counter() - secs
        # Events nested in this one ended before it, so they are the last
        # ones kept; their seconds are already inside `secs`.
        while events and events[-1][0] >= start:
            events.pop()
        key = event[len(COMPILE_EVENT_PREFIX):].removesuffix("_duration")
        events.append((start, secs, key))


def enable() -> None:
    """Start recording, and put each JAX compile event's seconds down to
    the innermost open span."""
    global _on, _listener
    if _listener is None:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener = _on_duration
    _on = True


def disable() -> None:
    """Stop recording and stop listening to compile events; what was
    recorded stays until `reset`."""
    global _on, _listener
    _on = False
    if _listener is not None:
        jax.monitoring.unregister_event_duration_listener(_listener)
        _listener = None


def reset() -> None:
    """Forget every recorded span.  Spans still open close as usual but
    are not listed again."""
    _spans.clear()


def spans() -> list[Span]:
    """Recorded spans in the order they opened (`end` is None while a span
    is still open)."""
    return list(_spans)
