"""Work a cell requires, computed from its shapes: the benchmark's own count.

Kept apart from the program so that no change to the program can change
what a roofline or utilization share is measured against.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).

    A device that is not in the table is an error, never a default.
    """
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def lm_train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Model operations per trained token of a Llama-style decoder.

    6 x the matmul parameters (the LM head included, tied or not; the
    embedding gather excluded) plus attention's 12 L T d_attn, as PaLM
    counts it (Chowdhery et al. 2022, App. B).  Recomputation is not
    counted.  ``cfg`` holds the keys of a Hugging Face ``config.json``.
    """
    d = cfg["hidden_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    q_dim, kv_dim = heads * head_dim, kv_heads * head_dim
    f, layers = cfg["intermediate_size"], cfg["num_hidden_layers"]
    per_layer = d * q_dim + 2 * d * kv_dim + q_dim * d + 3 * d * f
    matmul_params = layers * per_layer + d * cfg["vocab_size"]
    return 6.0 * matmul_params + 12.0 * layers * seq_len * q_dim
