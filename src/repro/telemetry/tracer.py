"""Tracer: nested wall-clock spans, ring-buffered, Chrome-trace exportable.

The engine's control loop is host-driven Python around a handful of jitted
calls, so host-side spans capture exactly the boundaries that matter: the
step, admission (the dispatcher's LP, page allocation), the step plan, the
upload, the jitted call, the read-back and migrations, plus per-module
Attention/MLP spans when the engine runs its eager instrumented probe
(``transformer.paged_decode_step_traced``).

Design constraints:

  * **Disabled mode is zero-cost.**  ``span()`` on a disabled tracer
    returns a shared no-op context manager — no per-call allocation, no
    clock reads — and ``sync()`` is a no-op, so the fast path pays one
    attribute check per call site.
  * **Bounded memory.**  Completed spans land in a ``deque(maxlen=...)``
    ring buffer; aggregate per-name duration/count totals survive ring
    overflow (the dispatcher and the profiler fit consume totals and
    recent spans, not unbounded history).
  * **The profiler's clock.**  A tracer built with a ``profiler_prefix``
    also opens a ``jax.profiler.TraceAnnotation`` named
    ``<prefix><name>`` with the span's args as its metadata for each
    context-manager span, so under ``jax.profiler.trace`` it lands on the
    calling thread's host line of the ``.xplane.pb``, on the same clock as
    the device's ops (the engine's ``engine.*``; see
    ``docs/observability.md``).  Without a prefix nothing is annotated.
  * **Two time bases.**  Context-manager spans use the wall clock
    (``time.perf_counter``, optionally device-sync'd via ``sync()``);
    ``add_span`` records spans on explicit timelines — the DES places
    *simulated-clock* module spans on their own track, which the Chrome
    export maps to a separate pid so Perfetto renders both.

Export: ``export_chrome()`` / ``write_chrome()`` produce Chrome
``trace_event`` JSON ("X" complete events) loadable in chrome://tracing or
https://ui.perfetto.dev; see ``repro.telemetry.export`` for the schema
validator CLI.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation


class Span(NamedTuple):
    """One completed span.  ``ts``/``dur`` are seconds in the track's own
    time base (wall clock for ``track="main"``, caller-defined otherwise);
    ``depth`` is the nesting level at record time."""

    name: str
    ts: float
    dur: float
    depth: int
    track: str
    args: Optional[Dict[str, Any]]


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    __slots__ = ("tracer", "name", "args", "t0", "depth", "ann")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self.tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        tr = self.tracer
        self.depth = len(tr._stack)
        tr._stack.append(self)
        self.ann = None
        if tr.profiler_prefix is not None:
            self.ann = TraceAnnotation(tr.profiler_prefix + self.name,
                                       **(self.args or {}))
            self.ann.__enter__()
        self.t0 = tr._time()
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        dur = tr._time() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tr._stack.pop()
        tr._record(Span(self.name, self.t0, dur, self.depth, "main",
                        self.args))
        return False

    def set(self, **args) -> None:
        """Add args known only once the span's work is done (a
        migration's groups and bytes) to the span and its annotation."""
        self.args = {**(self.args or {}), **args}
        if self.ann is not None:
            self.ann.set_metadata(**args)


class Tracer:
    """Nested-span tracer with a ring buffer and per-name totals;
    ``profiler_prefix`` names its spans' profiler annotations (None: no
    annotation)."""

    def __init__(self, enabled: bool = False, capacity: int = 65536,
                 time_fn=time.perf_counter,
                 profiler_prefix: Optional[str] = None):
        self.enabled = enabled
        self.profiler_prefix = profiler_prefix
        self._time = time_fn
        self.events: collections.deque = collections.deque(maxlen=capacity)
        self._stack: List[_SpanCtx] = []
        # aggregate duration / count per span name; survives ring overflow
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    # ------------------------------------------------------------- recording
    def span(self, name: str, args: Optional[Dict[str, Any]] = None):
        """Context manager timing a nested wall-clock span, annotated for
        the profiler where the tracer has a prefix.  On a disabled tracer this returns a shared
        no-op object (no allocation, no clock read)."""
        if not self.enabled:
            return _NULL_SPAN
        return _SpanCtx(self, name, args)

    def sync(self, x) -> None:
        """Block until ``x`` (any jax pytree) is ready — called inside a
        span so the recorded duration is device-sync'd.  No-op disabled."""
        if not self.enabled or x is None:
            return
        import jax
        jax.block_until_ready(x)

    def add_span(self, name: str, ts: float, dur: float, track: str = "main",
                 depth: int = 0, args: Optional[Dict[str, Any]] = None
                 ) -> None:
        """Record a span with an explicit (ts, dur) on an explicit track —
        used for simulated-clock timelines (the DES); never annotated."""
        if not self.enabled:
            return
        self._record(Span(name, ts, dur, depth, track, args))

    def _record(self, sp: Span) -> None:
        self.events.append(sp)
        self.totals[sp.name] = self.totals.get(sp.name, 0.0) + sp.dur
        self.counts[sp.name] = self.counts.get(sp.name, 0) + 1

    # --------------------------------------------------------------- reading
    def spans(self, name: Optional[str] = None,
              track: Optional[str] = None) -> List[Span]:
        out = []
        for sp in self.events:
            if name is not None and sp.name != name:
                continue
            if track is not None and sp.track != track:
                continue
            out.append(sp)
        return out

    def total(self, name: str) -> float:
        """Aggregate recorded duration (seconds) of all spans named
        ``name`` — O(1), survives ring overflow."""
        return self.totals.get(name, 0.0)

    def count(self, name: str) -> int:
        return self.counts.get(name, 0)

    def __len__(self) -> int:
        return len(self.events)

    def reset(self) -> None:
        self.events.clear()
        self._stack.clear()
        self.totals.clear()
        self.counts.clear()

    # ---------------------------------------------------------------- export
    def export_chrome(self) -> Dict[str, Any]:
        """Chrome ``trace_event`` JSON object (see export.spans_to_chrome)."""
        from repro.telemetry.export import spans_to_chrome
        return spans_to_chrome(list(self.events))

    def write_chrome(self, path: str) -> int:
        """Write the Chrome trace to ``path``; returns the event count."""
        import json
        obj = self.export_chrome()
        with open(path, "w") as f:
            json.dump(obj, f)
        return len(obj["traceEvents"])
