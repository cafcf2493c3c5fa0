"""Telemetry-stack tests: registry, tracer, events, numerics, serve,
the per-site execution hook, the report/export CLI, and the logger."""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LMConfig
from repro.core import PrecisionPolicy, offload, site_report
from repro.models import Model
from repro.obs import (Logger, MetricsRun, NumericsMonitor, Registry,
                       Tracer, load_runs, read_events, to_chrome)
from repro.obs.cli import main as obs_main
from repro.obs.events import EventSink, json_safe
from repro.serve import Engine, Request


class TestRegistry:
    def test_counter_identity_and_inc(self):
        reg = Registry()
        c = reg.counter("site_exec", site="dot0")
        assert reg.counter("site_exec", site="dot0") is c
        assert reg.counter("site_exec", site="dot1") is not c
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_gauge_set_add(self):
        g = Registry().gauge("occupancy")
        g.set(3)
        g.add(-1)
        assert g.value == 2.0

    def test_histogram_stats_and_buckets(self):
        h = Registry().histogram("lat_s")
        for v in (5e-7, 2.0, 5000.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 3
        assert snap["min"] == 5e-7 and snap["max"] == 5000.0
        assert snap["mean"] == pytest.approx(snap["sum"] / 3)
        buckets = dict((str(b), c) for b, c in snap["buckets"])
        assert buckets["1e-06"] == 1     # 5e-7 <= 1e-6
        assert buckets["10.0"] == 1      # 2.0 in (1, 10]
        assert buckets["inf"] == 1       # 5000 beyond the last decade
        assert sum(c for _, c in snap["buckets"]) == 3

    def test_histogram_quantiles(self):
        h = Registry().histogram("lat_s")
        assert h.quantile(0.5) is None  # empty
        for v in (0.01, 0.02, 0.03, 0.04, 9.0):
            h.observe(v)
        p50, p95, p99 = (h.quantile(q) for q in (0.5, 0.95, 0.99))
        # Estimates are clamped to the observed range and monotone.
        assert 0.01 <= p50 <= p95 <= p99 <= 9.0
        assert p50 < 0.1       # 4 of 5 samples in (0.01, 0.1]
        assert p99 > 1.0       # the tail sample dominates p99
        snap = h.snapshot()
        assert snap["p50"] == pytest.approx(p50)
        assert snap["p95"] == pytest.approx(p95)
        assert snap["p99"] == pytest.approx(p99)
        for bad in (0.0, 1.5, -1.0):
            with pytest.raises(ValueError, match="quantile"):
                h.quantile(bad)

    def test_histogram_quantile_single_value(self):
        h = Registry().histogram("lat_s")
        h.observe(2.5)
        # Clamping pins every quantile to the one observation.
        assert h.quantile(0.5) == 2.5
        assert h.quantile(0.99) == 2.5

    def test_kind_conflict_raises(self):
        reg = Registry()
        reg.counter("x", a="1")
        reg.gauge("x", a="2")  # different labels: fine
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x", a="1")

    def test_snapshot_is_json_and_sorted(self):
        reg = Registry()
        reg.counter("b").inc()
        reg.gauge("a").set(1)
        snap = reg.snapshot()
        json.dumps(snap)  # must not raise
        assert [s["name"] for s in snap] == ["a", "b"]

    def test_counter_under_jit_callback(self):
        """The intercept hook's shape: a zero-operand debug callback
        inside a jitted program, counts drained by effects_barrier."""
        reg = Registry()
        c = reg.counter("execs")

        @jax.jit
        def f(x):
            jax.debug.callback(lambda: c.inc())
            return x * 2

        for _ in range(3):
            f(jnp.ones(4))
        jax.effects_barrier()
        assert c.value == 3


class TestTracer:
    def test_span_nesting(self):
        tr = Tracer()
        with tr.span("outer", step=1):
            with tr.span("inner"):
                pass
        inner, outer = tr.events  # children close (and record) first
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["args"] == {"step": 1}

    def test_exception_flags_error_and_reraises(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.events[0]["args"]["error"] is True

    def test_streams_to_sink(self, tmp_path):
        sink = EventSink(tmp_path / "ev.jsonl")
        tr = Tracer(sink=sink)
        with tr.span("s"):
            pass
        sink.close()
        assert tr.events == []  # streamed, not retained
        events = read_events(tmp_path / "ev.jsonl")
        assert [e["type"] for e in events] == ["span"]

    def test_chrome_trace_schema(self):
        tr = Tracer()
        with tr.span("work", k=1):
            pass
        doc = to_chrome(tr.events + [{"type": "step"}])  # non-spans ok
        json.dumps(doc)
        assert doc["displayTimeUnit"] == "ms"
        meta, ev = doc["traceEvents"]
        assert meta["ph"] == "M" and meta["name"] == "process_name"
        assert ev["ph"] == "X" and ev["pid"] == 1
        assert ev["name"] == "work" and ev["args"] == {"k": 1}
        assert isinstance(ev["ts"], float) and ev["dur"] >= 0.0

    def test_chrome_trace_keeps_error_flag(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("load_ckpt", step=3):
                raise ValueError("corrupt")
        doc = to_chrome(tr.events)
        json.dumps(doc)
        (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        # The error marker survives export so the viewer can flag it.
        assert ev["args"] == {"step": 3, "error": True}

    def test_chrome_trace_concurrent_spans(self):
        import threading

        tr = Tracer()
        gate = threading.Barrier(2)

        def work(name):
            with tr.span(name):
                gate.wait()      # both spans provably overlap
                with tr.span(f"{name}/inner"):
                    pass

        threads = [threading.Thread(target=work, args=(n,))
                   for n in ("prefill", "decode")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        doc = to_chrome(tr.events)
        json.dumps(doc)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {s["name"] for s in spans} == {
            "prefill", "decode", "prefill/inner", "decode/inner"}
        # Each thread keeps its own lane: the viewer must not stack
        # overlapping spans from different threads on one tid.
        tids = {s["name"]: s["tid"] for s in spans}
        assert tids["prefill"] != tids["decode"]
        assert tids["prefill"] == tids["prefill/inner"]
        assert tids["decode"] == tids["decode/inner"]
        for s in spans:
            assert isinstance(s["tid"], int)


class TestEvents:
    def test_json_safe_coerces_numpy(self):
        out = json_safe({"a": np.float32(1.5), "b": np.arange(2),
                         "c": (1, 2), "d": jnp.float32})
        json.dumps(out)
        assert out == {"a": 1.5, "b": [0, 1], "c": [1, 2],
                       "d": str(jnp.float32)}

    def test_run_id_allocation(self, tmp_path):
        with MetricsRun(tmp_path) as r0:
            pass
        with MetricsRun(tmp_path) as r1:
            pass
        assert (r0.run_id, r1.run_id) == ("0000", "0001")
        assert sorted(load_runs(tmp_path)) == ["0000", "0001"]

    def test_site_event_handler_counts_and_declares_once(self, tmp_path):
        run = MetricsRun(tmp_path)
        handler = run.site_event_handler()
        for _ in range(3):
            handler({"site": "dot0", "backend": "fp64_int8_4"})
        handler({"site": "scan0/dot1"})
        run.close()
        events = load_runs(tmp_path)[run.run_id]
        execs = [e for e in events if e["type"] == "site_exec"]
        assert [e["site"] for e in execs] == ["dot0", "scan0/dot1"]
        counters = {(e["labels"]["site"]): e["value"]
                    for e in events if e["type"] == "metric"
                    and e["name"] == "site_exec"}
        assert counters == {"dot0": 3, "scan0/dot1": 1}
        types = [e["type"] for e in events]
        assert types[0] == "run_start" and types[-1] == "run_end"

    def test_read_events_skips_torn_line(self, tmp_path):
        path = tmp_path / "events-0000.jsonl"
        path.write_text('{"t": 1, "type": "step", "loss": 2.0}\n'
                        '{"t": 2, "type": "ru')  # killed mid-write
        events = read_events(path)
        assert len(events) == 1 and events[0]["loss"] == 2.0
        assert events.dropped == 1

    def test_read_events_counts_all_torn_lines(self, tmp_path):
        path = tmp_path / "events-0000.jsonl"
        path.write_text('{"type": "step", "loss": 2.0}\n'
                        'not json at all\n'
                        '[1, 2, 3]\n'             # parseable non-dict
                        '{"type": "step", "loss": 1.0}\n')
        events = read_events(path)
        assert [e["loss"] for e in events] == [2.0, 1.0]
        assert events.dropped == 2

    def test_site_decl_carries_tile_choice(self, tmp_path):
        # Pallas-family sites declare the analytic tile model's pick;
        # jnp-family sites declare tiles=None.
        def f(a, b):
            return jnp.sum(a @ b)

        a = jnp.ones((128, 128), jnp.float32)
        for backend, has_tiles in (("pallas_int8", True),
                                   ("fp64_int8", False)):
            pol = PrecisionPolicy(backend=backend, default_splits=4,
                                  min_dim=64)
            sites = site_report(f, pol)(a, a)
            with MetricsRun(tmp_path / backend) as run:
                run.declare_sites(sites)
            events = load_runs(tmp_path / backend)[run.run_id]
            (decl,) = [e for e in events if e["type"] == "site_decl"]
            if has_tiles:
                assert set(decl["tiles"]) == {"block_m", "block_n",
                                              "block_k", "pairs",
                                              "schedule"}
                assert decl["tiles"]["schedule"] == "ordered"
            else:
                assert decl["tiles"] is None


class TestOnSiteEvent:
    """The intercept hook: offload(..., on_site_event=...)."""

    def test_scan_counts_per_iteration(self):
        counts = {}

        def handler(p):
            counts[p["site"]] = counts.get(p["site"], 0) + 1

        def f(c, xs):
            def body(c, x):
                return c @ x, jnp.sum(c)
            return jax.lax.scan(body, c, xs)

        c = jnp.ones((128, 128), jnp.float32)
        xs = jnp.ones((3, 128, 128), jnp.float32)
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=2,
                              min_dim=64)
        wrapped = offload(f, pol, on_site_event=handler)
        wrapped(c, xs)
        jax.effects_barrier()
        # Forward (no AD): one firing per scan iteration, exactly.
        assert counts == {"scan0/dot0": 3}
        payloadless = wrapped.sites(c, xs)
        assert [s.name for s in payloadless] == ["scan0/dot0"]

    def test_payload_carries_static_site_facts(self):
        seen = []

        def f(a, b):
            return jnp.sum(a @ b)

        a = jnp.ones((128, 96), jnp.float32)
        b = jnp.ones((96, 128), jnp.float32)
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=3,
                              min_dim=64)
        offload(f, pol, on_site_event=seen.append)(a, b)
        jax.effects_barrier()
        (p,) = seen
        assert p["site"] == "dot0" and p["splits"] == 3
        assert p["backend"] == "fp64_int8"
        assert list(p["lhs_shape"]) == [128, 96] and p["k"] == 96
        assert p["dtype"] == "float32" and p["flops"] > 0

    def test_non_offloaded_sites_do_not_fire(self):
        seen = []

        def f(a, b):
            return jnp.sum(a @ b)

        a = jnp.ones((32, 32), jnp.float32)
        offload(f, PrecisionPolicy(min_dim=64),
                on_site_event=seen.append)(a, a)
        jax.effects_barrier()
        assert seen == []

    def test_fires_under_external_grad(self):
        """Zero-operand callbacks survive differentiation (operand-
        carrying ones are dropped by partial-eval): >= 1 per site."""
        counts = {}

        def handler(p):
            counts[p["site"]] = counts.get(p["site"], 0) + 1

        def f(a, b):
            return jnp.sum(jnp.tanh(a @ b))

        a = jnp.ones((128, 128), jnp.float32) * 0.01
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=2,
                              min_dim=64)
        g = jax.grad(offload(f, pol, on_site_event=handler))(a, a)
        jax.effects_barrier()
        assert g.shape == (128, 128)
        assert counts.get("dot0", 0) >= 1


class TestSiteEventStaging:
    """One host callback a call reports every statically counted site."""

    POLICY = PrecisionPolicy(backend="fp64_int8", default_splits=2,
                             min_dim=64)

    @staticmethod
    def _scan(c, xs):
        def body(c, x):
            return jnp.tanh(c @ x), None
        c, _ = jax.lax.scan(body, c, xs)
        return c @ xs[0]

    @staticmethod
    def _while(a):
        def body(v):
            i, x = v
            return i + 1, jnp.tanh(x @ x)
        return jax.lax.while_loop(lambda v: v[0] < 3, body, (0, a))[1] @ a

    @staticmethod
    def _callbacks(lowered_text):
        import re
        return len(re.findall(r"custom_call @\w*callback", lowered_text))

    def _counting(self, f):
        counts = {}

        def handler(p):
            counts[p["site"]] = counts.get(p["site"], 0) + 1

        return offload(f, self.POLICY, on_site_event=handler), counts

    def test_scan_program_stages_one_callback(self):
        c = jnp.ones((128, 128), jnp.float32) * 0.01
        xs = jnp.ones((3, 128, 128), jnp.float32) * 0.01
        wrapped, _ = self._counting(self._scan)
        assert self._callbacks(jax.jit(wrapped).lower(c, xs).as_text()) == 1

    def test_while_site_keeps_its_own_callback(self):
        a = jnp.ones((128, 128), jnp.float32) * 0.01
        wrapped, counts = self._counting(self._while)
        # One in the while body, one a call for the top-level site.
        assert self._callbacks(jax.jit(wrapped).lower(a).as_text()) == 2
        for _ in range(2):
            jax.jit(wrapped)(a)
        jax.effects_barrier()
        assert counts == {"while0/dot0": 6, "dot0": 2}

    def test_scan_counts_equal_per_execution_counts(self):
        c = jnp.ones((128, 128), jnp.float32) * 0.01
        xs = jnp.ones((3, 128, 128), jnp.float32) * 0.01
        wrapped, counts = self._counting(self._scan)
        step = jax.jit(wrapped)
        for _ in range(2):
            step(c, xs)
        jax.effects_barrier()
        assert counts == {"scan0/dot0": 6, "dot0": 2}
        mult = {s.name: s.mult for s in wrapped.sites(c, xs)}
        assert counts == {name: 2 * m for name, m in mult.items()}

    @pytest.mark.parametrize("abstract", [False, True])
    def test_shard_map_counts_once_per_shard(self, abstract):
        import contextlib

        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

        def f(a, b):
            def per_shard(a_s, b_s):
                def body(c, _):
                    return jnp.tanh(c @ b_s), None
                c, _ = jax.lax.scan(body, a_s, None, length=2)
                return c @ b_s
            # Under jax.set_mesh the staged shard_map holds an
            # AbstractMesh, which knows no devices.
            return jax.shard_map(per_shard,
                                 **({} if abstract else {"mesh": mesh}),
                                 in_specs=(P("dp"), P()),
                                 out_specs=P("dp"))(a, b)

        a = jnp.ones((512, 128), jnp.float32) * 0.01
        b = jnp.ones((128, 128), jnp.float32) * 0.01
        wrapped, counts = self._counting(f)
        step = jax.jit(wrapped)
        with jax.set_mesh(mesh) if abstract else contextlib.nullcontext():
            assert self._callbacks(step.lower(a, b).as_text()) == 1
            step(a, b)
        jax.effects_barrier()
        # 4 shards; the scan body runs twice on each.
        assert counts == {"shmap0/scan0/dot0": 8, "shmap0/dot0": 4}
        assert all(s.spmd == "dp=4" for s in wrapped.sites(a, b))

    def test_external_grad_reports_mult_executions(self):
        c = jnp.ones((128, 128), jnp.float32) * 0.01
        xs = jnp.ones((3, 128, 128), jnp.float32) * 0.01
        wrapped, counts = self._counting(self._scan)
        grad = jax.jit(jax.grad(lambda c, xs: jnp.sum(wrapped(c, xs))))
        grad(c, xs)
        jax.effects_barrier()
        # The per-call callback sits outside every loop, so external AD
        # no longer hoists a loop site's report down to one a call.
        assert counts == {"scan0/dot0": 3, "dot0": 1}


    def test_int8_dots_count_runs_times_mult(self, tmp_path):
        from repro.core.ozaki import fold_runs

        c = jnp.ones((128, 128), jnp.float32) * 0.01
        xs = jnp.ones((3, 128, 128), jnp.float32) * 0.01
        with MetricsRun(tmp_path) as run:
            wrapped = offload(self._scan, self.POLICY,
                              on_site_event=run.site_event_handler())
            step = jax.jit(wrapped)
            assert self._callbacks(step.lower(c, xs).as_text()) == 1
            for _ in range(2):
                step(c, xs)
            jax.effects_barrier()
            dots = {m["labels"]["site"]: m["value"]
                    for m in run.registry.snapshot()
                    if m["name"] == "int8_dots"}
        sites = wrapped.sites(c, xs)
        # s = 2 at k = 128: two runs, so two INT8 dots an execution.
        assert all(s.int8_dots == len(fold_runs(2, 128)) == 2
                   for s in sites)
        assert dots == {s.name: 2 * s.mult * s.int8_dots for s in sites}
        assert dots == {"scan0/dot0": 12, "dot0": 4}

    def test_remat_train_step_stages_one_callback(self):
        from repro.launch.train import build_train_step
        from repro.train import AdamW

        cfg = LMConfig(name="test_obs_step", vocab_size=256, num_layers=2,
                       d_model=128, num_heads=4, num_kv_heads=2,
                       head_dim=32, d_ff=256, max_seq_len=128, remat=True)
        model, opt = Model(cfg), AdamW()
        params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        state = jax.eval_shape(opt.init, params)
        batch = jax.ShapeDtypeStruct((1, 129), jnp.int32)
        wrapped, _ = self._counting(build_train_step(model, opt))
        sites = wrapped.sites(params, state, batch)
        assert sum(s.mult for s in sites if s.offloaded) > 1
        lowered = jax.jit(wrapped).lower(params, state, batch).as_text()
        assert self._callbacks(lowered) == 1


class TestPhaseScopes:
    def test_offloaded_site_ops_carry_phase_scopes(self):
        # The engine's phases nest inside the site's scope, so each op
        # is still charged to its site and also to its phase.
        import re

        def f(a, b):
            return jnp.tanh(a @ b)

        a = jnp.ones((128, 128), jnp.float32)
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=4,
                              min_dim=64)
        hlo = jax.jit(offload(f, pol)).lower(a, a).compile().as_text()
        names = re.findall(r'op_name="([^"]*)"', hlo)
        for phase in ("phase_slice", "phase_pairs", "phase_fold"):
            assert any(re.search(rf"(^|/)ozaki_dot0/(.*/)?{phase}/", n)
                       for n in names), phase
        # (Names of reducer bodies are relative: only the ops' own.)
        assert not any("phase_" in n and "ozaki_dot0/" not in n
                       for n in names if n.startswith("jit("))


class TestTracerOnProfilerClock:
    def test_span_lines_up_with_its_host_plane_event(self, tmp_path):
        from jax.profiler import ProfileData

        tr = Tracer()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with tr.span("obs.clock_check"):
                jnp.ones(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        data = ProfileData.from_file(str(path))
        env = data.find_plane_with_name("Task Environment")
        start_ns = dict(env.stats)["profile_start_time"]
        host = data.find_plane_with_name("/host:CPU")
        (event,) = [e for line in host.lines for e in line.events
                    if e.name == "obs.clock_check"]
        (span,) = tr.events
        # Host-plane times are relative to the session's start.
        assert abs(span["ts"] * 1e3 - (start_ns + event.start_ns)) < 1e6
        assert abs(span["dur"] * 1e3 - event.duration_ns) < 1e6


class TestNumericsMonitor:
    def _fn(self, a, b):
        return jnp.sum(a @ b)

    @pytest.fixture(scope="class")
    def operands(self):
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
        return a, b

    def test_healthy_no_drift(self, operands):
        a, b = operands
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=6,
                              min_dim=64)
        mon = NumericsMonitor(self._fn, policy=pol, budget=1e-3)
        report = mon.check(0, a, b)
        assert report.site == "dot0" and report.splits == 6
        assert 0 < report.realized_rel < 1e-3
        assert report.drift is False

    def test_stale_plan_drifts_and_records(self, operands, tmp_path):
        a, b = operands
        # Deliberately under-split with an unmeetable budget: the
        # realized error must breach it -> drift.
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=1,
                              min_dim=64)
        run = MetricsRun(tmp_path)
        stream = io.StringIO()
        mon = NumericsMonitor(self._fn, policy=pol, budget=1e-9,
                              registry=run.registry, sink=run.sink,
                              log=Logger("numerics", stream=stream))
        report = mon.check(7, a, b)
        assert report.drift is True
        assert report.realized_rel > 1e-9
        assert "WARNING: numerics drift at step 7" in stream.getvalue()
        assert "re-tune" in stream.getvalue()
        gauge = run.registry.gauge("numerics_realized_rel",
                                   site="dot0")
        assert gauge.value == pytest.approx(report.realized_rel)
        assert run.registry.counter("numerics_drift",
                                    site="dot0").value == 1
        run.close()
        events = load_runs(tmp_path)[run.run_id]
        (num,) = [e for e in events if e["type"] == "numerics"]
        assert num["step"] == 7 and num["drift"] is True

    def test_probe_never_perturbs_output(self, operands):
        a, b = operands
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=1,
                              min_dim=64)
        mon = NumericsMonitor(self._fn, policy=pol, budget=1e-9)
        native = float(self._fn(a, b))
        probed = float(mon._wrapped(a, b))
        assert probed == pytest.approx(native, rel=1e-6)

    def test_maybe_check_period(self, operands):
        a, b = operands
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=4,
                              min_dim=64)
        mon = NumericsMonitor(self._fn, policy=pol, budget=1.0,
                              every=3)
        assert mon.maybe_check(1, a, b) is None
        assert mon.maybe_check(2, a, b) is None
        assert mon.maybe_check(3, a, b) is not None
        mon.every = 0
        assert mon.maybe_check(3, a, b) is None

    def test_requires_plan_or_policy(self):
        with pytest.raises(ValueError, match="plan or a policy"):
            NumericsMonitor(self._fn)

    @pytest.mark.parametrize("kind", ["monitor", "calibrator"])
    def test_reference_dots_run_at_highest_precision(self, operands,
                                                     kind):
        # The error reference and its |A|@|B| normalizer must not be a
        # TPU's one-pass bf16 DEFAULT matmul; only the program's own
        # (native) product keeps the program's precision.
        from repro.core import offload
        from repro.obs.numerics import _ProbeGemm
        from repro.tune.calibrate import _CalibrationGemm, _Recorder

        a, b = operands
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=4,
                              min_dim=64)
        probe = (_ProbeGemm(pol) if kind == "monitor"
                 else _CalibrationGemm(pol, 4, _Recorder()))
        jaxpr = jax.make_jaxpr(offload(self._fn, pol, backend=probe))(
            a, b).jaxpr
        precisions = [e.params["precision"] for e in jaxpr.eqns
                      if e.primitive.name == "dot_general"]
        highest = (jax.lax.Precision.HIGHEST,) * 2
        assert precisions.count(highest) == 2
        assert len(precisions) == 3


SMALL = LMConfig(name="test_obs_serve", vocab_size=128, num_layers=1,
                 d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
                 d_ff=128)


class TestServeMetrics:
    @pytest.fixture(scope="class")
    def model_params(self):
        model = Model(SMALL)
        params = model.init_params(jax.random.PRNGKey(0))
        return model, params

    def test_per_request_metrics(self, model_params, tmp_path):
        model, params = model_params
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 128, n)]
                   for n in (3, 7, 12)]
        run = MetricsRun(tmp_path)
        eng = Engine(model, params, batch_slots=2, max_len=64,
                     metrics=run)
        done = eng.run([Request(prompt=p, max_new_tokens=5)
                        for p in prompts])
        run.close()
        assert all(len(r.out) == 5 for r in done)
        events = load_runs(tmp_path)[run.run_id]
        reqs = [e for e in events if e["type"] == "request"]
        assert len(reqs) == 3
        by_prompt = {e["prompt_len"]: e for e in reqs}
        assert sorted(by_prompt) == [3, 7, 12]
        for ev in reqs:
            # The first token comes from prefill, every further token
            # from one decode tick: ticks == new_tokens - 1 exactly.
            assert ev["new_tokens"] == 5
            assert ev["decode_ticks"] == 4
            assert ev["ttft_s"] is not None and ev["ttft_s"] >= 0
            assert ev["admission_wait_s"] >= 0
            assert ev["prefill_s"] > 0
            assert ev["tokens_per_s"] > 0
        tokens = [e for e in events if e["type"] == "metric"
                  and e["name"] == "serve_tokens"]
        assert tokens[0]["value"] == 15
        occ = [e for e in events if e["type"] == "metric"
               and e["name"] == "serve_slot_occupancy"]
        assert occ[0]["value"] == 0  # drained at run end
        ttft = [e for e in events if e["type"] == "metric"
                and e["name"] == "serve_ttft_s"]
        assert ttft[0]["count"] == 3
        spans = {e["name"] for e in events if e["type"] == "span"}
        assert {"prefill", "decode_tick"} <= spans

    def test_metrics_off_is_untouched(self, model_params):
        model, params = model_params
        eng = Engine(model, params, batch_slots=1, max_len=64)
        (done,) = eng.run([Request(prompt=[1, 2, 3],
                                   max_new_tokens=3)])
        assert len(done.out) == 3


def _seed_run(tmp_path, with_execs=True):
    """A metrics dir with real Site declarations (+ optional execs)."""

    def f(a, b):
        return jnp.sum(jnp.tanh(a @ b) @ b)

    a = jnp.ones((128, 128), jnp.float32)
    pol = PrecisionPolicy(backend="fp64_int8", default_splits=4,
                          min_dim=64)
    sites = site_report(f, pol)(a, a)
    run = MetricsRun(tmp_path)
    run.declare_sites(sites)
    if with_execs:
        handler = run.site_event_handler()
        for s in sites:
            if s.offloaded:
                handler({"site": s.name})
    run.event("step", step=1, loss=3.5, ms=12.0, int8_gemms=20)
    run.event("numerics", step=1, site="dot0", splits=4,
              realized_rel=1.5e-6, budget=3.8e-6, drift=False)
    with run.tracer.span("train_step", step=1):
        pass
    run.close()
    return run.run_id, sites


class TestCli:
    def test_report_tables(self, tmp_path):
        run_id, sites = _seed_run(tmp_path)
        out = io.StringIO()
        rc = obs_main(["report", str(tmp_path)], out=out)
        text = out.getvalue()
        assert rc == 0
        assert f"run {run_id}:" in text
        for s in sites:
            assert s.name in text
        assert "int8_gemms/step" in text
        assert "train_step" in text
        assert "1.500e-06" in text  # realized_rel column

    def test_check_passes_with_execs(self, tmp_path):
        _seed_run(tmp_path)
        out = io.StringIO()
        assert obs_main(["report", str(tmp_path), "--check"],
                        out=out) == 0
        assert "CHECK OK" in out.getvalue()

    def test_check_fails_without_execs(self, tmp_path):
        _seed_run(tmp_path, with_execs=False)
        out = io.StringIO()
        assert obs_main(["report", str(tmp_path), "--check"],
                        out=out) == 1
        assert "recorded no executions" in out.getvalue()

    def test_check_fails_on_run_without_decls(self, tmp_path):
        MetricsRun(tmp_path).close()
        out = io.StringIO()
        assert obs_main(["report", str(tmp_path), "--check"],
                        out=out) == 1
        assert "no site_decl events" in out.getvalue()

    def test_run_selection(self, tmp_path):
        first, _ = _seed_run(tmp_path)
        MetricsRun(tmp_path).close()  # a later, empty run
        out = io.StringIO()
        obs_main(["report", str(tmp_path)], out=out)
        assert "run 0001:" in out.getvalue()  # latest by default
        out = io.StringIO()
        obs_main(["report", str(tmp_path), "--run", first], out=out)
        assert f"run {first}:" in out.getvalue()
        out = io.StringIO()
        obs_main(["report", str(tmp_path), "--all"], out=out)
        assert "run 0000:" in out.getvalue()
        assert "run 0001:" in out.getvalue()
        with pytest.raises(SystemExit):
            obs_main(["report", str(tmp_path), "--run", "9999"],
                     out=io.StringIO())

    def test_report_surfaces_torn_lines(self, tmp_path):
        run_id, _ = _seed_run(tmp_path)
        path = tmp_path / f"events-{run_id}.jsonl"
        with path.open("a") as f:
            f.write('{"type": "ru')  # killed mid-write
        out = io.StringIO()
        assert obs_main(["report", str(tmp_path)], out=out) == 0
        assert "1 torn line(s) dropped" in out.getvalue()

    def test_report_latency_quantile_table(self, tmp_path):
        run = MetricsRun(tmp_path)
        h = run.registry.histogram("serve_ttft_s")
        for v in (0.01, 0.02, 0.03, 4.0):
            h.observe(v)
        run.close()
        out = io.StringIO()
        assert obs_main(["report", str(tmp_path)], out=out) == 0
        text = out.getvalue()
        assert "serve latency quantiles" in text
        assert "p50" in text and "p95" in text and "p99" in text
        assert "serve_ttft_s" in text

    def test_export_writes_chrome_trace(self, tmp_path):
        _seed_run(tmp_path / "metrics")
        target = tmp_path / "trace.json"
        out = io.StringIO()
        rc = obs_main(["export", str(tmp_path / "metrics"),
                       "-o", str(target)], out=out)
        assert rc == 0
        doc = json.loads(target.read_text())
        assert doc["traceEvents"][0]["ph"] == "M"
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [s["name"] for s in spans] == ["train_step"]

    def test_empty_dir_errors(self, tmp_path):
        with pytest.raises(SystemExit, match="no events"):
            obs_main(["report", str(tmp_path)], out=io.StringIO())


class TestLogger:
    def test_level_filtering(self, monkeypatch):
        stream = io.StringIO()
        log = Logger("t", stream=stream)
        monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
        log.info("hidden")
        log.warning("shown")
        monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
        log.debug("now visible")
        lines = stream.getvalue().splitlines()
        assert lines == ["[t] WARNING: shown", "[t] now visible"]

    def test_info_renders_like_legacy_prints(self):
        stream = io.StringIO()
        Logger("serve", stream=stream).info("OK (3 requests)")
        assert stream.getvalue() == "[serve] OK (3 requests)\n"

    def test_attach_sink_tees(self, tmp_path):
        sink = EventSink(tmp_path / "ev.jsonl")
        log = Logger("train", stream=io.StringIO())
        log.attach_sink(sink)
        log.warning("drift!")
        sink.close()
        (ev,) = read_events(tmp_path / "ev.jsonl")
        assert ev == {**ev, "type": "log", "level": "WARNING",
                      "logger": "train", "msg": "drift!"}

    def test_get_logger_caches(self):
        from repro.obs import get_logger, reset_logger
        a = get_logger("test_obs_cache")
        assert get_logger("test_obs_cache") is a
        b = reset_logger("test_obs_cache")
        assert b is not a
