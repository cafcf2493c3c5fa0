"""Reduce a profiler trace (``.xplane.pb``) to device time per GEMM site.

The offload transform runs each ``dot_general`` site of the program
under one ``jax.named_scope``: ``ozaki_<site>`` where the site is
offloaded, ``native_<site>`` where it is left native, with the site
path's ``/`` written as ``.`` (``ozaki_scan0.dot3``).  XLA keeps the
scope in each op's ``op_name`` metadata.  On a TPU plane the profiler
writes it into the op's event metadata, as the ``tf_op`` stat
(``jit(f)/while/body/closed_call/ozaki_scan0.dot3/jit(_real_ozaki)/mul:``),
which ``jax.profiler.ProfileData`` does not expose: :func:`op_paths`
reads it from the file's protobuf wire format.  A parent program
without scopes gives no scoped time.

Only innermost op events count: the event of a ``while``, ``call`` or
``conditional`` op encloses the events of the ops its body ran, and is
not counted again.  Device busy time is the union of all op intervals
on a TPU plane inside the ``bench.window`` span, as ``trace_reduce``
counts it; every time is averaged over the TPU planes.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple

import trace_reduce

#: A site scope as one component of an op's name path.
SCOPE = re.compile(
    r"(?:^|/)(ozaki|native)_((?:[A-Za-z]+\d*\.)*dot\d+)(?=[/:]|$)")

#: The event-metadata stat that holds an op's ``op_name``.
OP_PATH_STAT = "tf_op"

_TPU = "/device:TPU:"


def scope_of(op_path: Optional[str]) -> Optional[str]:
    """The innermost site scope in an op's name path, or None."""
    found = SCOPE.findall(op_path or "")
    if not found:
        return None
    kind, site = found[-1]
    return f"{kind}_{site}"


# -- the protobuf wire format, as far as XSpace's event metadata needs -----


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a memoryview."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, object]:
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


@functools.lru_cache(maxsize=2)
def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """Per TPU plane, each op event name's ``op_name`` path.

    Reads XSpace (``planes = 1``), XPlane (``name = 2``,
    ``event_metadata = 4``, ``stat_metadata = 5``), XEventMetadata
    (``name = 2``, ``stats = 5``) and XStat (``metadata_id = 1``,
    ``str_value = 5``, ``ref_value = 7``); the lines, most of the file,
    are skipped whole.  A name that two metadata entries share with
    different paths gets none.
    """
    data = memoryview(Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                sid, meta = _map_entry(v)
                stats[sid] = next((bytes(x).decode() for g, x in _fields(meta)
                                   if g == 2), "")
        if not name.startswith(_TPU):
            continue
        table: Dict[str, Optional[str]] = {}
        for entry in events:
            _, meta = _map_entry(entry)
            op, found = "", None
            for f, v in _fields(meta):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stats.get(stat.get(1)) != OP_PATH_STAT:
                        continue
                    if 5 in stat:
                        found = bytes(stat[5]).decode()
                    elif 7 in stat:
                        found = stats.get(stat[7])
            if op in table and table[op] != found:
                found = None
            table[op] = found
        out[name] = {k: v for k, v in table.items() if v is not None}
    return out


# -- the reduction -----------------------------------------------------------


@functools.lru_cache(maxsize=2)
def reduce_scopes(path: str) -> Optional[Dict]:
    """Busy time and innermost op time per site scope, in seconds.

    Returns None where the trace holds no TPU plane (a CPU run) or no
    ``bench.window`` span.  ``scoped_s`` sums the innermost op time of
    all ``ozaki_*`` and all ``native_*`` scopes; ``per_site_s`` gives it
    per scope; ``callback_s`` is the innermost op time of host
    callbacks; ``innermost_s`` the time of all innermost ops.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices = [p for p in data.planes if p.name.startswith(_TPU)]
    host = data.find_plane_with_name("/host:CPU")
    if not devices or host is None:
        return None
    window = None
    for line in host.lines:
        for e in line.events:
            if e.name == trace_reduce.WINDOW_SPAN:
                window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        return None
    lo, hi = window
    paths = op_paths(str(path))
    busy = innermost = callbacks = 0.0
    scoped: Dict[str, float] = defaultdict(float)
    per_site: Dict[str, float] = defaultdict(float)
    for plane in devices:
        ops = sorted(trace_reduce._events(plane, "XLA Ops"),
                     key=lambda o: (o[0], -o[1]))
        busy += sum(e - s for s, e in trace_reduce._union(
            ((s, e) for s, e, _ in ops), lo, hi))
        plane_paths = paths.get(plane.name, {})
        scopes: Dict[str, Optional[str]] = {}
        for i, (s, e, name) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < e:
                continue  # encloses the next op: not innermost
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0:
                continue
            innermost += clipped
            if name not in scopes:
                scopes[name] = scope_of(plane_paths.get(name))
            scope = scopes[name]
            if scope is not None:
                scoped[scope.split("_", 1)[0]] += clipped
                per_site[scope] += clipped
            elif "callback" in trace_reduce.op_name(name):
                callbacks += clipped  # the site-event hook's debug_callback
    n, ns = len(devices), 1e-9
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "innermost_s": innermost / n * ns,
        "callback_s": callbacks / n * ns,
        "scoped_s": {k: scoped.get(k, 0.0) / n * ns
                     for k in ("ozaki", "native")},
        "per_site_s": {k: v / n * ns for k, v in sorted(
            per_site.items(), key=lambda kv: -kv[1])},
    }
