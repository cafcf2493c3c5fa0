"""Reduce a profiler trace to the device time of an expert layer's parts.

The offload transform runs each grouped site (``ragged_dot_general``)
under ``ozaki_<site>`` or ``native_<site>``, its name ending in a
``ragged<i>`` component (``ozaki_scan0.ragged3``), and the model runs
its routing, dispatch and combine under ``moe_route``, ``moe_dispatch``
and ``moe_combine``.  XLA keeps these scopes in each op's ``op_name``;
``scope_reduce.op_paths`` reads them from the trace.  An op belongs to
the innermost of these scopes (or a dense site's) on its path, so a
product inside ``moe_route`` counts as its site's, not as routing.
Only innermost op events inside the ``bench.window`` span count, and
times are averaged over the TPU planes, as ``scope_reduce`` counts them.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, Optional

import scope_reduce
import trace_reduce

#: A site scope (dense or grouped) or an expert-layer scope, as one
#: component of an op's name path.
PART = re.compile(
    r"(?:^|/)((?:ozaki|native)_(?:[A-Za-z]+\d*\.)*(?:dot|ragged)\d+"
    r"|moe_(?:route|dispatch|combine))(?=[/:]|$)")

_GROUPED = re.compile(r"ozaki_(?:[A-Za-z]+\d*\.)*ragged\d+")


def part_of(op_path: Optional[str]) -> Optional[str]:
    """``grouped`` (an offloaded grouped site), ``moe_route``,
    ``moe_dispatch``, ``moe_combine``, ``site`` (any other site scope)
    for the innermost such scope on an op's path, else None."""
    found = PART.findall(op_path or "")
    if not found:
        return None
    inner = found[-1]
    if inner.startswith("moe_"):
        return inner
    return "grouped" if _GROUPED.fullmatch(inner) else "site"


@functools.lru_cache(maxsize=2)
def reduce_parts(path: str) -> Optional[Dict]:
    """Busy time and innermost op time of each part, in seconds.

    None where the trace holds no TPU plane or no ``bench.window`` span.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    host = data.find_plane_with_name("/host:CPU")
    if not devices or host is None:
        return None
    window = next((e for line in host.lines for e in line.events
                   if e.name == trace_reduce.WINDOW_SPAN), None)
    if window is None:
        return None
    lo, hi = window.start_ns, window.start_ns + window.duration_ns
    paths = scope_reduce.op_paths(str(path))
    busy = 0.0
    parts: Dict[str, float] = defaultdict(float)
    for plane in devices:
        ops = sorted(trace_reduce._events(plane, "XLA Ops"),
                     key=lambda o: (o[0], -o[1]))
        busy += sum(e - s for s, e in trace_reduce._union(
            ((s, e) for s, e, _ in ops), lo, hi))
        plane_paths = paths.get(plane.name, {})
        seen: Dict[str, Optional[str]] = {}
        for i, (s, e, name) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < e:
                continue  # encloses the next op: not innermost
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0:
                continue
            if name not in seen:
                seen[name] = part_of(plane_paths.get(name))
            if seen[name] is not None:
                parts[seen[name]] += clipped
    n, ns = len(devices), 1e-9
    keys = ("grouped", "moe_route", "moe_dispatch", "moe_combine", "site")
    return {"busy_s": busy / n * ns,
            "parts_s": {k: parts.get(k, 0.0) / n * ns for k in keys}}


def parts(ctx) -> Optional[Dict]:
    """:func:`reduce_parts` of the run's trace (``reader_context``)."""
    import reader_context

    path = reader_context.trace_file(ctx)
    return None if path is None else reduce_parts(str(path))
