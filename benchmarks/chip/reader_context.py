"""What a per-layer reader needs beyond the ``ctx`` it is handed.

``run_cell.py`` hands each reader the reduced trace, the peaks, the
window's work and its length.  The scope readers also need the trace
file itself and the running cell's configuration and traffic:

- :func:`trace_file` takes ``ctx["trace_file"]`` where the harness
  passes it, else the run's own trace, the newest ``bench_trace_*``
  recording under the temporary directory (where ``run_cell.py``
  records it), kept only if its ``bench.window`` has the length of the
  window ``ctx["trace"]`` was reduced from;
- :func:`running_cell` reads the cell from the ``--workload`` (and
  ``--cpu-rehearsal``) that ``run_cell.py`` was started with, and
  finds its files by name as the harness does.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
from pathlib import Path
from typing import Dict, Optional, Tuple

import scope_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def trace_file(ctx) -> Optional[Path]:
    """The traced run's ``.xplane.pb``, or None."""
    if ctx.get("trace_file"):
        return Path(ctx["trace_file"])
    reduced = ctx.get("trace")
    if reduced is None:
        return None
    found = sorted(Path(tempfile.gettempdir()).glob(
        "bench_trace_*/plugins/profile/*/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    scopes = scope_reduce.reduce_scopes(str(found[-1]))
    if scopes is None or not math.isclose(
            scopes["window_s"], reduced["window_s"], rel_tol=1e-12):
        return None
    return found[-1]


def scopes(ctx) -> Optional[Dict]:
    """``scope_reduce.reduce_scopes`` of the run's trace, or None."""
    path = trace_file(ctx)
    return None if path is None else scope_reduce.reduce_scopes(str(path))


def running_cell(argv=None) -> Optional[Tuple[Dict, Dict]]:
    """(configuration, traffic) of the cell this process runs, or None.

    A CPU rehearsal applies the files' ``rehearsal`` overrides, as the
    harness does.
    """
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return None
    cell = cells[args.workload]
    (spec,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    config = json.loads((ROOT / spec["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if args.cpu_rehearsal:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return config, traffic
