"""Tests of the chip benchmark's harness, yardstick and trace reduction.

All run on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import counts  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = HERE / "testdata" / "must_v5e.xplane.pb"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(*args, root=ROOT, env=None, timeout=240):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run_cell.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)


# -- trace reduction, on a trace recorded on one v5e -----------------------


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(TRACE)), trace_reduce.reduce_trace(TRACE)


def _window(data):
    host = data.find_plane_with_name("/host:CPU")
    (w,) = [e for line in host.lines for e in line.events
            if e.name == "bench.window"]
    return w.start_ns, w.start_ns + w.duration_ns


def test_trace_busy_is_the_union_of_op_intervals(recorded):
    data, reduced = recorded
    lo, hi = _window(data)
    (tpu,) = [p for p in data.planes if p.name == "/device:TPU:0"]
    (ops,) = [line for line in tpu.lines if line.name == "XLA Ops"]
    # Count busy nanoseconds by sweeping sorted intervals by hand.
    busy, reach = 0.0, lo
    for s, e in sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in ops.events):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            busy += e - s
            reach = e
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_trace_time_per_module(recorded):
    data, reduced = recorded
    lo, hi = _window(data)
    (tpu,) = [p for p in data.planes if p.name == "/device:TPU:0"]
    (mods,) = [line for line in tpu.lines if line.name == "XLA Modules"]
    want = {}
    for e in mods.events:
        if lo <= e.start_ns and e.start_ns + e.duration_ns <= hi:
            name = trace_reduce.module_name(e.name)
            want[name] = want.get(name, 0.0) + e.duration_ns * 1e-9
    got = reduced["per_module_s"]
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-9)
    # Both engines of the recording ran: XLA's f64 dot and the Ozaki path.
    assert any("real_ozaki" in m for m in got)
    assert all(0 < t <= reduced["window_s"] for t in got.values())


def test_trace_gaps_are_named_by_the_open_host_span(recorded):
    _, reduced = recorded
    gaps = reduced["idle_gaps"]
    assert gaps and len(gaps) <= 10
    lengths = [g[1] for g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    names = {g[0] for g in gaps}
    assert names <= {"must.segment", "outside_spans"}
    # The 50 ms pause between the two segments is the longest gap, and
    # no span covers it.
    assert gaps[0][0] == "outside_spans" and gaps[0][1] >= 0.05
    assert "must.segment" in names
    ops = reduced["device_ops"]
    assert ops and all("/" in name and t > 0 for name, t in ops)


def test_union_merges_and_clips():
    got = trace_reduce._union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert got == [(1, 3), (5, 10)]


# -- the yardstick's counts ------------------------------------------------


def test_lm_flops_match_the_model_parameter_count():
    from repro.configs import LMConfig

    cfg = json.loads((HERE / "configs/smollm_360m.json").read_text())
    for tied in (True, False):
        c = {**cfg, "tie_word_embeddings": tied}
        lm = LMConfig(vocab_size=c["vocab_size"],
                      num_layers=c["num_hidden_layers"],
                      d_model=c["hidden_size"],
                      num_heads=c["num_attention_heads"],
                      num_kv_heads=c["num_key_value_heads"], head_dim=64,
                      d_ff=c["intermediate_size"], tie_embeddings=tied)
        L, d, V = lm.num_layers, lm.d_model, lm.vocab_size
        # Everything but the norms and the embedding gather is a matmul;
        # a tied head is the embedding again.
        matmul = lm.num_params() - 2 * L * d - d - (0 if tied else V * d)
        T = 512
        got = counts.lm_train_flops_per_token(c, T)
        assert got == 6 * matmul + 12 * L * T * lm.q_dim
    assert counts.lm_train_flops_per_token(cfg, 512) == pytest.approx(
        2.36e9, rel=0.01)


def test_peaks_table_refuses_an_unknown_device():
    assert counts.device_peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        counts.device_peaks("TPU v9 imaginary")


# -- the harness -----------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(cell):
    p = run_cell("--workload", cell, "--seed", "3000000019", "--seconds", "1",
                 "--trace", "0", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
    names = {m["name"] for m in BENCH["end_to_end"]
             if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == names


def test_no_tpu_means_no_result():
    p = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _copy_bench(tmp_path: Path, with_src: bool) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    if with_src:
        (root / "src").symlink_to(ROOT / "src")
    return root


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = _copy_bench(tmp_path, with_src=False)
    p = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--cpu-rehearsal", root=root)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A cell added as data files plus entries, with no harness edit."""
    root = _copy_bench(tmp_path, with_src=True)
    chip = root / "benchmarks/chip"
    cfg = json.loads((chip / "configs/smollm_360m.json").read_text())
    cfg.update(name="lm_small", **cfg.pop("rehearsal"))
    (chip / "configs/lm_small.json").write_text(json.dumps(cfg))
    shutil.copy(chip / "configs/smollm_360m_ref.py",
                chip / "configs/lm_small_ref.py")
    traffic = json.loads((chip / "traffic/train_ozaki_s4.json").read_text())
    traffic.update(backend="fp64_int8_6", splits=6, seq_len=64, remat=False)
    (chip / "traffic/train_ozaki_s6.json").write_text(json.dumps(traffic))
    (chip / "limits/lm_small.train_ozaki_s6.json").write_text(json.dumps(
        {"loss1_rel": 1e-3, "grad_norm_gap": 1e-2, "update_norm_gap": 1.0}))
    (chip / "metrics/steps_done.py").write_text(
        "def read(ctx):\n    return float(ctx['work']['steps'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "lm_small", "source": "https://huggingface.co/a/test",
        "file": "benchmarks/chip/configs/lm_small.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "lm_small.train_ozaki_s6", "config": "lm_small",
        "traffic": "train_ozaki_s6", "chips": 1, "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("lm_small.train_ozaki_s6")
    bench["per_layer"].append({
        "name": "steps_done", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "train_tokens_per_s", "workloads": ["lm_small.train_ozaki_s6"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = run_cell("--workload", "lm_small.train_ozaki_s6", "--seed", "5",
                 "--seconds", "1", "--trace", "1", "--cpu-rehearsal",
                 root=root)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["steps_done"]["value"] == line["attempted"] > 0
