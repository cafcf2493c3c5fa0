"""Run one cell of the chip benchmark and print its result line.

  python benchmarks/chip/run_cell.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1> [--cpu-rehearsal]

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Both are found by name, and so is
every per-layer metric:

- ``configs/<config>.json``: the configuration's sizes; beside it
  ``configs/<config>_ref.py``, its plain reference;
- ``traffic/<traffic>.json``: the traffic's parameters, whose ``kind``
  names the general driver ``kinds/<kind>.py`` that reads them;
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``;
- ``metrics/<metric>.py``, or ``metrics/<stem>.py`` for a metric named
  ``<stem>.<suffix>``: the reader of a per-layer metric.

A run sets up (weights or inputs from ``--seed``, every shape the
window uses compiled or loaded from the compile cache in
``<checkout>/.jax_cache``), measures for ``--seconds``, compares what
the timed path produced with the plain reference, and prints one JSON
line last on standard output.  ``--trace 1`` records a profiler trace
of the window and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell
asks for, it exits nonzero and prints no result; ``--cpu-rehearsal``
(for tests) runs the same code at the configuration's rehearsal sizes
on the CPU and says ``cpu`` in ``device``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class CellError(RuntimeError):
    """The cell cannot run here: no chip, a missing file, a bad name."""


def load_module(path: Path):
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def metric_reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<stem>.py`` for ``stem.x``."""
    exact = HERE / "metrics" / f"{name}.py"
    return load_module(exact if exact.is_file()
                       else HERE / "metrics" / f"{name.split('.')[0]}.py")


class Cell:
    """What a cell's driver (``kinds/<kind>.py``) is handed.

    ``config`` and ``traffic`` are the parsed data files, with their
    ``rehearsal`` overrides applied in a CPU rehearsal.  The driver opens
    the measured window with :meth:`window`, records the window's
    work in ``work`` (counts the per-layer readers use) and returns the
    end-to-end values, ``attempted``/``failed`` and its checks.
    """

    def __init__(self, bench: dict, name: str, seed: int, seconds: float,
                 trace: bool, rehearsal: bool):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise CellError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = cells[name]
        self.name = name
        self.bench = bench
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_spec = configs[self.spec["config"]]
        self.config = load_json(ROOT / self.config_spec["file"])
        self.traffic = load_json(
            HERE / "traffic" / f"{self.spec['traffic']}.json")
        self.limits = load_json(HERE / "limits" / f"{name}.json")
        if rehearsal:
            self.config = {**self.config, **self.config.get("rehearsal", {})}
            self.traffic = {**self.traffic,
                            **self.traffic.get("rehearsal", {})}
        self.kind = load_module(HERE / "kinds" / f"{self.traffic['kind']}.py")
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.work: dict = {}
        self.setup_s = math.nan
        self.window_s = math.nan
        self.memory_peak_bytes = None
        self._trace_dir = None

    def reference(self):
        """The configuration's plain reference, ``configs/<config>_ref.py``."""
        return load_module(ROOT / Path(self.config_spec["file"]).with_name(
            f"{self.spec['config']}_ref.py"))

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens.

        Yields a function that says whether ``--seconds`` have passed;
        the driver finishes the unit in flight and leaves.  In a traced
        run the profiler records the window, marked by a
        ``bench.window`` span.
        """
        import jax

        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=options)
        t0 = time.perf_counter()
        self.setup_s = t0 - T_START
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield lambda: time.perf_counter() - t0 >= self.seconds
            self.window_s = time.perf_counter() - t0
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        """Peak bytes in use on the fullest chip; call after the window."""
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peaks = [s["peak_bytes_in_use"] for s in stats
                 if "peak_bytes_in_use" in s]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def trace_file(self):
        if self._trace_dir is None:
            return None
        found = sorted(Path(self._trace_dir).glob(
            "plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


def cell_metrics(bench: dict, cell: str, per_layer: bool):
    """The cell's end-to-end metrics, or the per-layer metrics it reports."""
    def applies(m):
        return cell in m.get("workloads", [cell])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not per_layer:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def _finite(x):
    """``x`` with NaN and infinities as null: the result line is JSON."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tests only: the same code, tiny, on the CPU")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except CellError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2


def prepare(workload: str, seed: int, seconds: float, trace: bool,
            rehearsal: bool) -> Cell:
    """The cell, on a checked device, with the compile cache set.

    Raises :class:`CellError` where the cell cannot run: no TPU (unless
    ``rehearsal``), fewer chips than it asks for, a file missing.
    """
    bench = load_json(ROOT / "BENCHMARK.json")
    if not (ROOT / "src" / "repro").is_dir():
        raise CellError("the system under test (src/repro) is not in this "
                        "checkout")
    for p in (str(HERE), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell = Cell(bench, workload, seed, seconds, trace, rehearsal)
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devices = jax.devices()
    cell.device = {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}
    if cell.device["platform"] != "tpu" and not rehearsal:
        raise CellError(f"no TPU: JAX found {cell.device}")
    if len(devices) < cell.spec["chips"] and not rehearsal:
        raise CellError(f"the cell asks for {cell.spec['chips']} chips, "
                        f"JAX found {cell.device}")
    from counts import device_peaks

    cell.peaks = None
    if not rehearsal:
        cell.peaks = device_peaks(cell.device["kind"])
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell


def run(args) -> int:
    cell = prepare(args.workload, args.seed, args.seconds, bool(args.trace),
                   args.cpu_rehearsal)
    bench, device, peaks = cell.bench, cell.device, cell.peaks
    out = cell.kind.run(cell)
    checks = out["checks"]
    correct = bool(checks) and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    metrics = {}
    if not args.trace:
        values = {"setup_s": cell.setup_s, **out["end_to_end"]}
        for m in cell_metrics(bench, cell.name, per_layer=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = cell.memory_peak_bytes
    breakdown = None
    if args.trace:
        from trace_reduce import reduce_trace

        path = cell.trace_file()
        reduced = reduce_trace(path) if path is not None else None
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        ctx = {"trace": reduced, "peaks": peaks, "work": cell.work,
               "window_s": cell.window_s}
        for m in cell_metrics(bench, cell.name, per_layer=True):
            value = metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if cell._trace_dir is not None:
            import shutil

            shutil.rmtree(cell._trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(f"work {json.dumps(_finite(cell.work))}", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
