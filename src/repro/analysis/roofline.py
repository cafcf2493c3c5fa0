"""Roofline analysis of dry-run artifacts (paper §4 performance model).

Where no chip run exists, TPU-side performance is *modeled* (never
measured) through a roofline evaluated over *dry-run artifacts*: JSON
files describing the per-cell work of a lowered program (flops, HBM
bytes, collective bytes, device count).  :func:`analyze_cell` converts
one artifact into the three roofline times and names the binding
resource — the same decomposition the paper uses to argue when INT8
emulation pays off (compute-bound GEMM cells gain the full
int8/fp64-unit ratio; memory- or collective-bound cells do not).

Artifact schema (all numeric fields optional, default 0)::

    {
      "cell": "must_n4096_pod16x16",   # any label
      "num_devices": 256,
      "flops": 1.2e15,                  # total programme flops
      "int8_flops": 9.6e14,             # flops issued as INT8 MACs
      "hbm_bytes": 3.1e12,
      "collective_bytes": 4.0e10,
      "peaks": {                        # optional hardware override
        "flops": 1.97e14, "int8_flops": 3.94e14,
        "hbm_gbps": 8.19e11, "ici_gbps": 4.5e10
      }
    }

Per-device peaks default to TPU v5e: 197 TFLOPS bf16 / 394 TOPS int8,
819 GB/s HBM, 45 GB/s ICI per link.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Union

__all__ = ["V5E_PEAKS", "CellAnalysis", "analyze_cell"]

#: Per-device peak rates (TPU v5e).
V5E_PEAKS: Dict[str, float] = {
    "flops": 1.97e14,        # bf16/f32-accumulate MXU FLOP/s
    "int8_flops": 3.94e14,   # INT8 MAC/s — the emulation substrate
    "hbm_gbps": 8.19e11,     # HBM bytes/s
    "ici_gbps": 4.5e10,      # ICI bytes/s per link
}


@dataclasses.dataclass
class CellAnalysis:
    """Roofline times (seconds) for one dry-run cell."""

    cell: str
    num_devices: int
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        times = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(times, key=times.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def analyze_cell(artifact: Union[str, Path, Dict]) -> CellAnalysis:
    """Evaluate the roofline model for one dry-run JSON artifact.

    ``artifact`` may be a path to a JSON file or an already-parsed
    dict.  Raises ``ValueError`` on artifacts missing a usable label
    or carrying non-numeric work counts.
    """
    if isinstance(artifact, (str, Path)):
        path = Path(artifact)
        data = json.loads(path.read_text())
        default_cell = path.stem
    else:
        data = dict(artifact)
        default_cell = "cell"
    if not isinstance(data, dict):
        raise ValueError(f"artifact must be a JSON object, got "
                         f"{type(data).__name__}")

    cell = str(data.get("cell", default_cell))
    ndev = int(data.get("num_devices", 1) or 1)
    peaks = dict(V5E_PEAKS)
    peaks.update(data.get("peaks", {}))

    def work(key):
        v = data.get(key, 0.0)
        if not isinstance(v, (int, float)):
            raise ValueError(f"field {key!r} must be numeric, got {v!r}")
        return float(v)

    # Mixed-precision compute: f32/bf16 flops ride the MXU peak, the
    # INT8-emulated portion rides the (2x faster) int8 peak.
    f_total = work("flops")
    f_int8 = min(work("int8_flops"), f_total)
    compute_s = ((f_total - f_int8) / peaks["flops"]
                 + f_int8 / peaks["int8_flops"]) / ndev
    memory_s = work("hbm_bytes") / peaks["hbm_gbps"] / ndev
    collective_s = work("collective_bytes") / peaks["ici_gbps"] / ndev
    return CellAnalysis(cell=cell, num_devices=ndev,
                        compute_s=compute_s, memory_s=memory_s,
                        collective_s=collective_s)
