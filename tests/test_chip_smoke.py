"""chip_smoke.py: the CPU rehearsal passes; without a chip it refuses.

The script runs in a child process: it owns its JAX configuration
(x64 toggles, the CPU platform), which must not leak into this worker.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).resolve().parent.parent / "chip_smoke.py"
OK_LINE = '"ok": true'


def _run(args, cwd=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(args[0]), *args[1:]],
                          cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_cpu_rehearsal_passes_end_to_end():
    proc = _run([SCRIPT, "--cpu-rehearsal"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1  # as JAX reports the host
    rows = [json.loads(line) for line in lines if line.startswith("{")]
    phases = {r["phase"] for r in rows if "phase" in r}
    assert {"gemm", "must", "train", "serve"} <= phases
    # The trained checkpoint lives only as long as the run.
    smoke = SCRIPT.parent / ".smoke"
    assert not smoke.exists() or not any(smoke.iterdir())


def test_without_a_tpu_exits_nonzero_and_claims_nothing():
    proc = _run([SCRIPT])
    assert proc.returncode != 0
    assert OK_LINE not in proc.stdout
    assert "no TPU" in proc.stderr


def test_alone_without_the_repo_exits_nonzero(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run([lone], cwd=tmp_path)
    assert proc.returncode != 0
    assert OK_LINE not in proc.stdout
