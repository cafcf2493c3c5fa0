"""The correctness check fails where it must.

Each cell is driven through a whole run (``run_cell.main`` with
``--cpu-rehearsal``, which skips the look for a chip) with its timed
path broken underneath, once for each fault the cell can have, and
``correct`` must come out false.  The controls (the reference in the
precision below the configuration's, put in the program's place) must
fail at least one of the cell's limits on every seed.

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import control  # noqa: E402
import run_cell  # noqa: E402

TRAIN = "smollm_360m.train_ozaki_s4"


def result(capsys, cell, seed=2200000011):
    rc = run_cell.main(["--workload", cell, "--seed", str(seed),
                        "--seconds", "0.5", "--trace", "0",
                        "--cpu-rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert result(capsys, TRAIN)["correct"] is True


def test_step_returns_its_state_unchanged(capsys, monkeypatch):
    from repro.train import AdamW

    monkeypatch.setattr(AdamW, "update",
                        lambda self, grads, params, state: (params, state))
    assert result(capsys, TRAIN)["correct"] is False


def test_half_the_batch_left_out(capsys, monkeypatch):
    """The loss's mean taken over the first half of the positions."""
    from repro.models import Model

    loss = Model.loss

    def half(self, params, tokens):
        return loss(self, params, tokens[:, :tokens.shape[1] // 2 + 1])

    monkeypatch.setattr(Model, "loss", half)
    assert result(capsys, TRAIN)["correct"] is False


def test_control_fails_a_limit_on_every_seed():
    """At the cell's own size, on a TPU only.

    The training cell's control (three bf16 passes) separates from the
    program at the published widths on the chip (PERF.md); at the
    rehearsal's toy widths on the CPU the two read alike.
    """
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the training control separates only at the cell's "
                    "own size, on the chip")
    seeds = [7100000003, 7100000005, 7100000007]
    prepared = run_cell.prepare(TRAIN, seeds[0], 0.0, False, False)
    rows = prepared.kind.readings(prepared, seeds)
    limits = prepared.limits
    for row in rows:
        assert all(row["program"][k] <= limits[k] for k in limits), row
        assert any(row["control"][k] > limits[k] for k in limits), row
    summary = control.summarize(rows, limits)
    assert set(summary["limits"]) == set(limits)
