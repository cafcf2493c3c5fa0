"""Span tracer with Chrome-trace (``chrome://tracing`` / Perfetto) export.

``Tracer.span`` is a context manager measuring host wall time (the
caller is responsible for blocking on device work inside the span —
e.g. the train loop converts the loss to float before the span closes,
and the serve engine ``np.asarray``-s the sampled tokens).  Each closed
span becomes one event:

``{"type": "span", "name": ..., "ts": <us since the Unix epoch>,``
``  "dur": <us>, "tid": <thread id>, "args": {...}}``

A span also opens a ``jax.profiler.TraceAnnotation`` of the same name
(near-free when no profiler session is recording), and ``ts`` is read
on the clock the profiler stamps its host events with (the system
clock, ``time.time_ns``), so a span of the JSONL stream and its event
in a device trace's host plane line up.

When the tracer is built over an :class:`~repro.obs.events.EventSink`
the spans stream straight into the JSONL file (bounded memory over long
runs); without a sink they accumulate in ``tracer.events`` for tests
and ad-hoc use.  :func:`to_chrome` converts span events — from either
source — into the Chrome Trace Event JSON the ``python -m repro.obs
export`` CLI writes: complete ("ph": "X") events that chrome://tracing
and https://ui.perfetto.dev open directly.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "to_chrome"]


class Tracer:
    """Nestable wall-time spans, streamed to a sink or kept in memory."""

    def __init__(self, sink=None):
        self._sink = sink
        self._lock = threading.Lock()
        #: retained span events (only when no sink streams them out)
        self.events: List[dict] = []

    @staticmethod
    def _now_us() -> float:
        # The profiler's host clock (CLOCK_REALTIME), so that a span's
        # ts matches its TraceAnnotation in a recorded trace.
        return time.time_ns() / 1e3

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a block; record it as one span event on exit.

        Spans nest naturally (the ``with`` discipline guarantees a
        child closes before — and therefore lies inside — its parent);
        exceptions still close the span, flagged ``error=True``.
        """
        start = self._now_us()
        try:
            with TraceAnnotation(str(name)):
                yield self
        except BaseException:
            args = {**args, "error": True}
            raise
        finally:
            event = {"name": str(name), "ts": start,
                     "dur": self._now_us() - start,
                     "tid": threading.get_ident() % 10_000_000,
                     "args": args}
            if self._sink is not None:
                self._sink.emit("span", **event)
            else:
                with self._lock:
                    self.events.append({"type": "span", **event})


def to_chrome(events, process_name: str = "repro") -> dict:
    """Span events -> Chrome Trace Event Format JSON document.

    ``events`` is any iterable of event dicts; non-span entries are
    ignored, so a whole JSONL run file can be passed verbatim.  The
    output is the stable subset every trace viewer understands:
    ``traceEvents`` of complete ("ph": "X") events with microsecond
    ``ts``/``dur``, one pid, per-thread tids, plus the process-name
    metadata record.
    """
    trace_events = [{
        "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
        "args": {"name": process_name},
    }]
    for ev in events:
        if ev.get("type") != "span":
            continue
        trace_events.append({
            "name": ev.get("name", "?"),
            "cat": "repro.obs",
            "ph": "X",
            "ts": float(ev.get("ts", 0.0)),
            "dur": float(ev.get("dur", 0.0)),
            "pid": 1,
            "tid": int(ev.get("tid", 0)),
            "args": ev.get("args", {}),
        })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def write_chrome_trace(events, path) -> "Optional[str]":
    """Serialize :func:`to_chrome` to ``path``; returns the path."""
    import json
    from pathlib import Path

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(to_chrome(events)) + "\n")
    return str(path)
