"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

Device planes (``/device:...``) give the operations that ran on each chip
(line "XLA Ops", falling back to "XLA Modules"); busy time is the union of
their intervals.  Host planes give the harness's own spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``) on the same clock, which
attribute each idle gap of the device to what the host was doing.
Nothing here knows the program: kernel names are matched by the metric
readers.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]          # seconds, trace clock
OP_LINES = ("XLA Ops", "XLA Modules")
# an op event's name is its HLO text: "%name = shape{layout} kind(..."
HLO = re.compile(r"^%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")
# ops that only wrap others (their time is their body's)
WRAPPERS = ("while", "conditional", "call")


TUPLE = re.compile(r"^%?([\w.\-]+) = \(.*?\) ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``copy.136 bf16[32,12007,16,96] copy`` for an HLO op event (a
    tuple-shaped result reads ``tuple``)."""
    m = HLO.match(name)
    if m:
        return f"{m[1]} {m[2]} {m[3]}"
    m = TUPLE.match(name)
    return f"{m[1]} tuple {m[2]}" if m else name[:120]


def op_kind(name: str) -> str:
    m = HLO.match(name) or TUPLE.match(name)
    return m[m.lastindex] if m else ""


@dataclasses.dataclass
class Op:
    name: str
    start: float
    dur: float


def union(intervals: Sequence[Interval]) -> np.ndarray:
    """Sorted, disjoint (k, 2) array covering ``intervals``."""
    if not intervals:
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def covered(u: np.ndarray, s: float, e: float) -> float:
    """Length of ``[s, e]`` that the disjoint intervals ``u`` cover."""
    if not len(u) or e <= s:
        return 0.0
    lo = np.clip(u[:, 0], s, e)
    hi = np.clip(u[:, 1], s, e)
    return float(np.sum(hi - lo))


@dataclasses.dataclass
class TraceSummary:
    ops: List[List[Op]]                 # per device plane
    host: Dict[str, List[Interval]]     # bench.* spans by name
    host_events: List[Tuple[float, float, str]]   # main-thread events
    window_s: float = 0.0

    @property
    def devices(self) -> int:
        return len(self.ops)

    def busy_union(self, plane: int = 0) -> np.ndarray:
        return union([(o.start, o.start + o.dur) for o in self.ops[plane]])

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.ops:
            return 0.0
        return float(np.mean([covered(self.busy_union(i), -np.inf, np.inf)
                              for i in range(self.devices)]))

    def op_seconds(self, match) -> Tuple[float, int]:
        """Summed device seconds (averaged over chips) and count of the
        operations for which ``match(op)`` holds."""
        if not self.ops:
            return 0.0, 0
        secs = [sum(o.dur for o in plane if match(o)) for plane in self.ops]
        n = sum(1 for o in self.ops[0] if match(o))
        return float(np.mean(secs)), n

    def idle_gaps(self, plane: int = 0) -> List[Interval]:
        """Idle intervals of the device between the first and the last
        ``bench.step`` span."""
        steps = self.host.get("bench.step", [])
        if not steps or plane >= self.devices:
            return []
        lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
        u = self.busy_union(plane)
        gaps, t = [], lo
        for s, e in u:
            if e <= lo or s >= hi:
                continue
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        return gaps

    def host_activity(self, times: Sequence[float]) -> List[str]:
        """Innermost main-thread event running at each of ``times``
        (ascending), in one sweep: the thread's events nest, so the open
        ones form a stack."""
        out, stack, i, ev = [], [], 0, self.host_events
        for t in times:
            while i < len(ev) and ev[i][0] <= t:
                s, e, name = ev[i]
                while stack and stack[-1][0] < s:
                    stack.pop()
                stack.append((e, name))
                i += 1
            while stack and stack[-1][0] < t:
                stack.pop()
            out.append(stack[-1][1] if stack else "no host span")
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        by_op: Dict[str, float] = defaultdict(float)
        for o in (self.ops[0] if self.ops else []):
            if op_kind(o.name) not in WRAPPERS:
                by_op[short_name(o.name)] += o.dur
        by_host: Dict[str, float] = defaultdict(float)
        gaps = self.idle_gaps()
        for (s, e), name in zip(gaps, self.host_activity(
                [0.5 * (s + e) for s, e in gaps])):
            by_host[name] += e - s
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {"device_ops": [[k, v] for k, v in rank(by_op)],
                "idle_gaps": [[k, v] for k, v in rank(by_host)]}


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[List[Op]] = []
    host: Dict[str, List[Interval]] = defaultdict(list)
    main_line: Optional[object] = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            plane_ops = [Op(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events]
            if plane_ops:
                ops.append(plane_ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        host[e.name].append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
                        main_line = ln
    host_events = []
    if main_line is not None:
        host_events = sorted((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, e.name)
                             for e in main_line.events)
    return TraceSummary(ops, dict(host), host_events)


def reduce_dir(trace_dir: str) -> TraceSummary:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(paths, key=os.path.getmtime))
