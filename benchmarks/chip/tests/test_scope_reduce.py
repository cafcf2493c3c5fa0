"""Tests of the GEMM-site scope reduction and its readers, on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import gemm_counts  # noqa: E402
import reader_context  # noqa: E402
import run_cell as harness  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from test_chip_bench import run_cell  # noqa: E402

TRACE = HERE / "testdata" / "scopes_v5e.xplane.pb"
CELL = "smollm_360m.train_ozaki_s4"
NEW = ("ozaki_share.train", "gemm_roofline.train")


def _cell_files(rehearsal: bool):
    return reader_context.running_cell(
        ["--workload", CELL] + (["--cpu-rehearsal"] if rehearsal else []))


# -- the reduction, on a trace recorded on one v5e -------------------------


@pytest.fixture(scope="module")
def reduced():
    return scope_reduce.reduce_scopes(str(TRACE))


def test_sites_are_found_by_scope(reduced):
    assert reduced["devices"] == 1
    sites = reduced["per_site_s"]
    assert {"ozaki_scan0.dot0", "ozaki_dot0", "native_scan0.dot1"} <= set(sites)
    assert all(t > 0 for t in sites.values())
    ozaki = sum(t for k, t in sites.items() if k.startswith("ozaki_"))
    native = sum(t for k, t in sites.items() if k.startswith("native_"))
    assert reduced["scoped_s"]["ozaki"] == pytest.approx(ozaki, rel=1e-12)
    assert reduced["scoped_s"]["native"] == pytest.approx(native, rel=1e-12)
    # The offloaded products cost far more than the small native one.
    assert reduced["scoped_s"]["ozaki"] > 10 * reduced["scoped_s"]["native"]


def test_only_innermost_ops_count(reduced):
    # Busy time is trace_reduce's union; innermost ops lie inside it and
    # every scoped or callback op is one of them.
    busy = trace_reduce.reduce_trace(TRACE)["busy_s"]
    assert reduced["busy_s"] == pytest.approx(busy, rel=1e-12)
    scoped = sum(reduced["scoped_s"].values()) + reduced["callback_s"]
    assert 0 < scoped <= reduced["innermost_s"] <= reduced["busy_s"]


def test_the_one_callback_is_seen(reduced):
    assert reduced["callback_s"] > 0


def test_scope_pattern_takes_whole_components():
    path = "jit(f)/while/body/closed_call/ozaki_scan0.dot3/jit(_real_ozaki)/mul:"
    assert scope_reduce.scope_of(path) == "ozaki_scan0.dot3"
    assert scope_reduce.scope_of(
        "jit(f)/native_while2.cond.dot0/dot_general:") == "native_while2.cond.dot0"
    assert scope_reduce.scope_of("jit(f)/ozaki_dot0:") == "ozaki_dot0"
    # A function named ozaki_* is not a site scope.
    assert scope_reduce.scope_of("jit(ozaki_matmul)/mul:") is None
    assert scope_reduce.scope_of(None) is None


def test_op_paths_come_from_the_event_metadata():
    (paths,) = scope_reduce.op_paths(str(TRACE)).values()
    scoped = {scope_reduce.scope_of(p) for p in paths.values()}
    assert {"ozaki_scan0.dot0", "ozaki_dot0", "native_scan0.dot1"} <= scoped
    # The parent's recording carries no paths the reduction reads as scopes.
    bare = scope_reduce.op_paths(str(HERE / "testdata/must_v5e.xplane.pb"))
    assert not any(scope_reduce.scope_of(p)
                   for plane in bare.values() for p in plane.values())


def test_readers_find_the_trace_by_its_window(tmp_path, monkeypatch):
    found = tmp_path / "bench_trace_x" / "plugins" / "profile" / "1"
    found.mkdir(parents=True)
    shutil.copy(TRACE, found / "t.xplane.pb")
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    window = trace_reduce.reduce_trace(TRACE)["window_s"]
    ctx = {"trace": {"window_s": window}}
    assert reader_context.trace_file(ctx) == found / "t.xplane.pb"
    assert reader_context.trace_file({"trace": {"window_s": window * 2}}) is None
    assert reader_context.trace_file({"trace": None}) is None
    assert reader_context.trace_file({"trace_file": str(TRACE)}) == TRACE


def test_readers_on_the_recorded_trace(monkeypatch):
    ozaki_share = harness.metric_reader("ozaki_share.train")
    roofline = harness.metric_reader("gemm_roofline.train")
    peaks = {"int8_ops_per_s": 393e12}
    ctx = {"trace_file": str(TRACE), "peaks": peaks,
           "work": {"steps": 2}, "window_s": 1.0}
    share = ozaki_share.read(ctx)
    assert 0 < share < 100
    monkeypatch.setattr(sys, "argv", ["run_cell.py", "--workload", CELL])
    ozaki = scope_reduce.reduce_scopes(str(TRACE))["scoped_s"]["ozaki"]
    want = (2 * gemm_counts.lm_train_offloaded_ops(*_cell_files(False))
            / 393e12 / ozaki)
    assert roofline.read(ctx) == pytest.approx(100 * want, rel=1e-12)
    # A trace with no site scopes (the parent program's) reads nothing.
    bare = {**ctx, "trace_file": str(HERE / "testdata/must_v5e.xplane.pb")}
    assert ozaki_share.read(bare) is None and roofline.read(bare) is None


# -- the benchmark's own GEMM count ----------------------------------------


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("min_dim", [128, 64])
def test_gemm_count_equals_the_offloaded_sites(remat, min_dim):
    """At the rehearsal sizes the gate keeps some products native; the
    count applies it per product as the program does per site."""
    import jax
    import jax.numpy as jnp

    config, traffic = _cell_files(rehearsal=True)
    traffic = {**traffic, "remat": remat, "min_dim": min_dim}
    kind = harness.load_module(HERE / "kinds" / "lm_train.py")
    from repro.core import PrecisionPolicy, offload
    from repro.launch.train import build_train_step
    from repro.models import Model
    from repro.train import AdamW

    model = Model(kind.lm_config(config, remat))
    opt = AdamW(**traffic["optimizer"])
    policy = PrecisionPolicy(backend=traffic["backend"],
                             default_splits=traffic["splits"],
                             min_dim=min_dim)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq_len"] + 1), jnp.int32)
    sites = offload(build_train_step(model, opt), policy).sites(
        params, state, batch)
    want = sum(s.flops for s in sites if s.offloaded)
    assert 0 < want < sum(s.flops for s in sites)
    assert gemm_counts.lm_train_offloaded_ops(config, traffic) == want
    executions = sum(times for _, m, k, n, times
                     in gemm_counts.lm_train_products(config, traffic)
                     if min(m, k, n) >= min_dim)
    assert executions == sum(s.mult for s in sites if s.offloaded)


def test_gemm_count_at_the_cell_size():
    config, traffic = _cell_files(rehearsal=False)
    per_step = gemm_counts.lm_train_offloaded_ops(config, traffic)
    tokens = traffic["batch"] * traffic["seq_len"]
    assert per_step / tokens == pytest.approx(2.6425e9, rel=1e-4)
    # 27 products a layer (7 forward, 6 recomputed, 14 backward) and the
    # head's 3, all through the gate at min_dim 128.
    executions = sum(t for _, m, k, n, t
                     in gemm_counts.lm_train_products(config, traffic)
                     if min(m, k, n) >= traffic["min_dim"])
    assert executions == 27 * config["num_hidden_layers"] + 3


def test_running_cell_reads_the_harness_arguments():
    config, traffic = _cell_files(rehearsal=False)
    assert config["hidden_size"] == 960 and traffic["seq_len"] == 2048
    small, small_traffic = _cell_files(rehearsal=True)
    assert small["hidden_size"] == config["rehearsal"]["hidden_size"]
    assert small_traffic["seq_len"] == traffic["rehearsal"]["seq_len"]
    assert reader_context.running_cell(["--seed", "1"]) is None


# -- the harness -------------------------------------------------------------


def test_rehearsal_reports_the_scope_metrics_absent():
    p = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds", "1",
                 "--trace", "1", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert not set(NEW) & set(line["metrics"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in NEW:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
