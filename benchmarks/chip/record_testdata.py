"""Record the small device trace that the trace-reduction tests read.

  python benchmarks/chip/record_testdata.py     # on one TPU v5e

With the profiler on (no Python tracer, host events of the benchmark's
own spans only, to keep the file small), opens a ``bench.window`` span
and, inside it, runs one contour segment of a small MuST matrix (n=128,
two blocks of 64) in mode ``dgemm`` and then one 64 x 64 Ozaki GEMM at
s=2, each under a ``must.segment`` span, with a 50 ms pause between
them that no span covers.  Prints the planes, lines and most frequent
event names of the whole trace, so that its layout can be read by hand,
and writes to ``benchmarks/chip/testdata/must_v5e.xplane.pb`` the part
of it that ``trace_reduce`` reads (:func:`trim`): the TPU planes' module
and op lines, and the benchmark's own spans on the host.

  python benchmarks/chip/record_testdata.py --trim <full.xplane.pb>

trims a trace recorded earlier, with no chip.
"""

from __future__ import annotations

import collections
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
OUT = ROOT / "benchmarks" / "chip" / "testdata" / "must_v5e.xplane.pb"


def _quote(text: str) -> str:
    return json.dumps(text)  # a valid text-proto string literal


def trim(path) -> bytes:
    """The serialized XSpace of ``path`` cut to what ``trace_reduce``
    reads, timestamps and names unchanged."""
    from jax.profiler import ProfileData

    sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
    import trace_reduce

    keep_host = {trace_reduce.WINDOW_SPAN, *trace_reduce.HOST_SPANS}
    data = ProfileData.from_file(str(path))
    planes = []
    for pid, plane in enumerate(data.planes, start=1):
        if plane.name.startswith("/device:TPU:"):
            lines = [(line.name, list(line.events)) for line in plane.lines
                     if line.name in ("XLA Modules", "XLA Ops")]
        elif plane.name == "/host:CPU":
            lines = [(line.name, [e for e in line.events
                                  if e.name in keep_host])
                     for line in plane.lines]
            lines = [(name, evs) for name, evs in lines if evs]
        else:
            continue
        names = {}
        body = []
        for lid, (lname, events) in enumerate(lines, start=1):
            evs = []
            for e in events:
                mid = names.setdefault(e.name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{round(e.start_ns * 1000)} duration_ps: "
                           f"{round(e.duration_ns * 1000)} }}")
            body.append(f"lines {{ id: {lid} name: {_quote(lname)} "
                        f"timestamp_ns: 0 {' '.join(evs)} }}")
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_quote(n)} }} }}" for n, i in names.items())
        planes.append(f"planes {{ id: {pid} name: {_quote(plane.name)} "
                      f"{' '.join(body)} {meta} }}")
    return ProfileData.text_proto_to_serialized_xspace(" ".join(planes))


def main() -> int:
    if sys.argv[1:2] == ["--trim"]:
        OUT.write_bytes(trim(sys.argv[2]))
        print("bytes", OUT.stat().st_size)
        return 0
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_enable_x64", True)
    if jax.devices()[0].platform != "tpu":
        print("record_testdata: no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData, TraceAnnotation

    import numpy as np

    from repro.apps import must as MU
    from repro.core import get_backend

    cfg = MU.MustConfig(n=128, block=64, n_energies=2, seed=1)
    system = MU.build_system(cfg)
    ozaki = get_backend("fp64_int8_2")
    a = jax.device_put(np.random.default_rng(1).standard_normal((64, 64)))
    MU.run_contour(cfg, "dgemm", system)  # compile outside the trace
    ozaki(a, a).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("must.segment"):
                MU.run_contour(cfg, "dgemm", system)
            time.sleep(0.05)
            with TraceAnnotation("must.segment"):
                ozaki(a, a).block_until_ready()
        jax.profiler.stop_trace()
        src = sorted(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))[-1]
        full = ROOT / "chiprun_out" / OUT.name
        full.parent.mkdir(exist_ok=True)
        shutil.copyfile(src, full)
    OUT.write_bytes(trim(full))
    print("bytes", full.stat().st_size, "trimmed", OUT.stat().st_size)
    data = ProfileData.from_file(str(full))
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            events = list(line.events)
            names = collections.Counter(e.name[:60] for e in events)
            print("  LINE", repr(line.name), len(events),
                  names.most_common(6))
    return 0


if __name__ == "__main__":
    sys.exit(main())
