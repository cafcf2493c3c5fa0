"""Record the small device trace that the scope-reduction tests read.

  python benchmarks/chip/record_scope_testdata.py [<dir>]  # on one TPU v5e

With the profiler on (no Python tracer), opens a ``bench.window`` span
and, inside it, runs twice a small offloaded program under a
``train.step`` span and fetches its result under a ``train.loss``
span of ``repro.obs.Tracer``.  The program is a scan of two steps, each
with an offloaded 256 x 256 x 256 product (``ozaki_scan0.dot0``) and a
product the size gate leaves native (``native_scan0.dot1``), then an
offloaded product at top level (``ozaki_dot0``); a site-event hook
counts the executions, which stages the program's one host callback.

Prints the planes and lines of the trace, the ``op_name`` paths of the
first op events, how far the ``train.loss`` span's JSONL ``ts`` lies from its
event in the host plane, and the site-event counts.  Writes to
``benchmarks/chip/testdata/scopes_v5e.xplane.pb`` the part of the trace
that ``scope_reduce`` reads (:func:`trim`): the TPU planes' module and
op lines with each op's ``op_name`` path, and the benchmark's spans on
the host; the whole trace goes to ``<dir>`` (default ``runs/``).

  python benchmarks/chip/record_scope_testdata.py --trim <full.xplane.pb>

trims a trace recorded earlier, with no chip.
"""

from __future__ import annotations

import collections
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
OUT = CHIP / "testdata" / "scopes_v5e.xplane.pb"
HOST_SPANS = ("bench.window", "train.step", "train.loss")

sys.path.insert(0, str(CHIP))
import scope_reduce  # noqa: E402
from record_testdata import _quote  # noqa: E402


def trim(path) -> bytes:
    """The serialized XSpace of ``path`` cut to what ``scope_reduce``
    reads, timestamps, names and op paths (``tf_op``) unchanged."""
    from jax.profiler import ProfileData

    paths = scope_reduce.op_paths(str(path))
    data = ProfileData.from_file(str(path))
    planes = []
    for pid, plane in enumerate(data.planes, start=1):
        if plane.name.startswith("/device:TPU:"):
            lines = [(line.name, list(line.events)) for line in plane.lines
                     if line.name in ("XLA Modules", "XLA Ops")]
        elif plane.name == "/host:CPU":
            lines = [(line.name, [e for e in line.events
                                  if e.name in HOST_SPANS])
                     for line in plane.lines]
            lines = [(name, evs) for name, evs in lines if evs]
        else:
            continue
        op_paths = paths.get(plane.name, {})
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
        meta = " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} "
            + (f"stats {{ metadata_id: 1 str_value: {_quote(op_paths[n])} }} "
               if n in op_paths else "") + "} }"
            for n, i in names.items())
        stat = (f"stat_metadata {{ key: 1 value {{ id: 1 name: "
                f"{_quote(scope_reduce.OP_PATH_STAT)} }} }}")
        planes.append(f"planes {{ id: {pid} name: {_quote(plane.name)} "
                      f"{' '.join(body)} {meta} {stat} }}")
    return ProfileData.text_proto_to_serialized_xspace(" ".join(planes))


def _program():
    import jax
    import jax.numpy as jnp

    def f(c, xs):
        def body(c, x):
            y = jnp.tanh(c @ x)
            return y + jnp.sum(y[:, :64].T @ y[:, :64]), None

        c, _ = jax.lax.scan(body, c, xs)
        return c @ xs[0]

    return f


def main() -> int:
    if sys.argv[1:2] == ["--trim"]:
        OUT.write_bytes(trim(sys.argv[2]))
        print("bytes", OUT.stat().st_size)
        return 0
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_scope_testdata: no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import PrecisionPolicy, offload
    from repro.obs import Tracer

    counts = collections.Counter()
    policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                             min_dim=128)
    step = jax.jit(offload(_program(), policy,
                           on_site_event=lambda p: counts.update(
                               [p["site"]])))
    rng = np.random.default_rng(1)
    c = jax.device_put(rng.standard_normal((256, 256), np.float32) / 16)
    xs = jax.device_put(rng.standard_normal((2, 256, 256), np.float32) / 16)
    float(jnp.sum(step(c, xs)))  # compile outside the trace
    jax.effects_barrier()
    counts.clear()
    tracer = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        with TraceAnnotation("bench.window"):
            for _ in range(2):
                with TraceAnnotation("train.step"):
                    out = step(c, xs)
                with tracer.span("train.loss"):
                    float(jnp.sum(out))
        jax.profiler.stop_trace()
        jax.effects_barrier()
        src = sorted(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))[-1]
        full = (Path(sys.argv[1]) if len(sys.argv) > 1 else ROOT / "runs"
                ) / "scopes_v5e_full.xplane.pb"
        full.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(src, full)
    print("site events", dict(counts))
    OUT.write_bytes(trim(full))
    print("bytes", full.stat().st_size, "trimmed", OUT.stat().st_size)
    data = ProfileData.from_file(str(full))
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), list(plane.stats)[:8])
        for line in lines:
            events = list(line.events)
            names = collections.Counter(e.name[:60] for e in events)
            print("  LINE", repr(line.name), len(events),
                  names.most_common(6))
            if line.name == "XLA Ops":
                paths = scope_reduce.op_paths(str(full)).get(plane.name, {})
                for e in events[:12]:
                    print("    OP", e.name[:100], e.start_ns, e.duration_ns,
                          paths.get(e.name))
    env = data.find_plane_with_name("Task Environment")
    start = dict(env.stats).get("profile_start_time") if env else None
    host = data.find_plane_with_name("/host:CPU")
    (span,) = [e for line in host.lines for e in line.events
               if e.name == "train.loss"][-1:]
    (ev,) = tracer.events[-1:]
    print("span", "profile_start_time", start, "event start_ns",
          span.start_ns, "jsonl ts_us", ev["ts"],
          "offset_ms", None if start is None
          else (ev["ts"] * 1e3 - (start + span.start_ns)) / 1e6)
    return 0


if __name__ == "__main__":
    sys.exit(main())
