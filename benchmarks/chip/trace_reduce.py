"""Reduce a profiler trace (``.xplane.pb``) to device time and idle gaps.

The trace holds one plane per TPU (``/device:TPU:<i>``), whose
``XLA Modules`` line has one event per program execution and whose
``XLA Ops`` line has one event per operation, and the host plane
(``/host:CPU``), whose lines are threads; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) sit on the thread that opened them.
Host and device events share one clock.  The measured window is the
benchmark's ``bench.window`` span.

Device busy time is the union of the op intervals on a TPU plane inside
the window (averaged over the TPU planes); an idle gap is a stretch of
the window with no op running, named by the innermost benchmark span
open at its midpoint.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
#: Host spans the cell kinds open, and ``must.segment`` of the recorded
#: test trace (``record_testdata.py``); a gap is named by them.
HOST_SPANS = ("must.segment", "train.data", "train.step", "train.loss")

_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def _union(intervals: Iterable[Interval], lo: float, hi: float
           ) -> List[Interval]:
    """Sorted, merged intervals clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def module_name(event_name: str) -> str:
    """``jit__real_ozaki(5038...)`` -> ``jit__real_ozaki``."""
    return _HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
    return []


def reduce_trace(path: str, top: int = 10) -> Optional[Dict]:
    """Busy time, per-module and per-op device time, longest idle gaps.

    Returns None where the trace holds no TPU plane (a CPU run) or no
    ``bench.window`` span.  Times are seconds.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    host = data.find_plane_with_name("/host:CPU")
    if not devices or host is None:
        return None
    window = None
    spans = []
    for line in host.lines:
        for e in line.events:
            if e.name == WINDOW_SPAN:
                window = (e.start_ns, e.start_ns + e.duration_ns)
            elif e.name in HOST_SPANS:
                spans.append((e.start_ns, e.start_ns + e.duration_ns,
                              e.name))
    if window is None:
        return None
    lo, hi = window
    busy_total = 0.0
    per_module: Dict[str, float] = defaultdict(float)
    per_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        modules = _events(plane, "XLA Modules")
        ops = _events(plane, "XLA Ops") or modules
        busy = _union(((s, e) for s, e, _ in ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for s, e, name in modules:
            clipped = min(e, hi) - max(s, lo)
            if clipped > 0:
                per_module[module_name(name)] += clipped
        modules.sort()
        starts = [s for s, _, _ in modules]
        for s, e, name in ops:
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = (module_name(modules[i][2])
                     if i >= 0 and s < modules[i][1] else "?")
            per_op[f"{owner}/{op_name(name)}"] += clipped
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)

    def host_span_at(t: float) -> str:
        open_ = [(s, name) for s, e, name in spans if s <= t < e]
        return max(open_)[1] if open_ else "outside_spans"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ns = 1e-9
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / n * ns,
        "per_module_s": {k: v / n * ns for k, v in sorted(
            per_module.items(), key=lambda kv: -kv[1])},
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_span_at((s + e) / 2), (e - s) * ns]
                      for s, e in longest],
    }
