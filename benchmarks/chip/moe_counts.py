"""Work of an expert-layer train step, computed from the shapes.

The benchmark's own count for DeepSeek-V3-style configurations, beside
``counts.py`` and ``gemm_counts.py``: it reads a cell's configuration
(the keys of a DeepSeek-V3 ``config.json``, with ``experts_held``) and
traffic, never the program's site records, so that no change to the
program can move what ``mfu`` and ``grouped_roofline`` are measured
against.  Routed rows are counted at their expectation under balanced
routing, ``T x experts_per_tok x held / n_routed_experts`` a layer:
the deployment's auxiliary-loss-free balancing keeps the load near it,
and the cell's i.i.d. tokens do too.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

#: (what, m, k, n, executions a step) of one matrix product.
Product = Tuple[str, int, int, int, int]


def _dims(cfg: Dict) -> Dict:
    first, held = cfg["experts_held"]
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "experts": cfg["n_routed_experts"], "held": held,
            "k": cfg["num_experts_per_tok"],
            "dense": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def active_matmul_params(cfg: Dict) -> float:
    """Matrix parameters a token meets on this chip's share, the LM head
    included and the embedding gather excluded; the routed experts at
    ``experts_per_tok x held / n_routed_experts`` a token."""
    g = _dims(cfg)
    d, H = g["d"], g["heads"]
    mla = (d * H * g["qk"] + d * (g["r"] + g["rope"])
           + g["r"] * H * (g["nope"] + g["v"]) + H * g["v"] * d)
    moe_layers = g["layers"] - g["dense"]
    routed = 3 * d * g["fe"] * g["k"] * g["held"] / g["experts"]
    expert_layer = d * g["experts"] + 3 * d * g["fs"] + routed
    return (g["layers"] * mla + g["dense"] * 3 * d * g["f"]
            + moe_layers * expert_layer + d * g["vocab"])


def moe_train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Model operations per trained token: 6 x :func:`active_matmul_params`
    plus MLA's attention, ``12 L T heads (qk + v) / 2`` (PaLM's
    ``12 L T d_attn``, Chowdhery et al. 2022, App. B, with ``d_attn``
    the mean of the query-key and value widths of all heads).
    Recomputation is not counted."""
    g = _dims(cfg)
    attention = (12.0 * g["layers"] * seq_len * g["heads"]
                 * (g["qk"] + g["v"]) / 2)
    return 6.0 * active_matmul_params(cfg) + attention


def balanced_rows(cfg: Dict, traffic: Dict) -> float:
    """Routed rows a held expert layer computes a step, in expectation."""
    g = _dims(cfg)
    tokens = traffic["batch"] * traffic["seq_len"]
    return tokens * g["k"] * g["held"] / g["experts"]


def grouped_products(cfg: Dict, traffic: Dict,
                     rows: Optional[float] = None) -> List[Product]:
    """Every grouped product of one train step, as (what, m, k, n, times).

    Per expert projection ``(d_in, d_out)`` of each expert layer, on
    ``rows`` routed rows (default :func:`balanced_rows`): the forward
    ``(rows, d_in, d_out)``, with ``remat`` its recomputation (all
    three: the routing weights' gradient reads the down projection's
    output), ``dX (rows, d_out, d_in)`` and ``dW (d_in, rows, d_out)``.
    """
    g = _dims(cfg)
    rows = balanced_rows(cfg, traffic) if rows is None else rows
    d, fe, layers = g["d"], g["fe"], g["layers"] - g["dense"]
    remat = bool(traffic.get("remat", False))
    out: List[Product] = []
    for name, d_in, d_out in (("gate", d, fe), ("up", d, fe),
                              ("down", fe, d)):
        out.append((f"{name}.fwd", rows, d_in, d_out, layers))
        if remat:
            out.append((f"{name}.recompute", rows, d_in, d_out, layers))
        out.append((f"{name}.dx", rows, d_out, d_in, layers))
        out.append((f"{name}.dw", d_in, rows, d_out, layers))
    return out


def grouped_ops(cfg: Dict, traffic: Dict,
                rows: Optional[float] = None) -> float:
    """Operations (2 m k n) of the grouped products a step offloads.

    The size gate, ``min >= min_dim``, applies to each product's static
    extents, with the rows at their bound ``T x experts_per_tok``, as
    the program applies it to each site.
    """
    g = _dims(cfg)
    bound = traffic["batch"] * traffic["seq_len"] * g["k"]
    gate = traffic["min_dim"]
    work = 0.0
    for (what, m, k, n, times), (_, bm, bk, bn, _) in zip(
            grouped_products(cfg, traffic, rows),
            grouped_products(cfg, traffic, bound)):
        if min(bm, bk, bn) >= gate:
            work += 2.0 * m * k * n * times
    return work
