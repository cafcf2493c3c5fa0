"""Work of the offloaded GEMMs of a train step, computed from the shapes.

The benchmark's own count, beside ``counts.py``: it reads a cell's
configuration (a Hugging Face ``config.json`` of a Llama-style decoder)
and traffic, never the program's site records, so that no change to
the program can change what ``gemm_roofline`` is measured against.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

#: (what, m, k, n, executions a step) of one matrix product.
Product = Tuple[str, int, int, int, int]


def lm_train_products(cfg: Dict, traffic: Dict) -> List[Product]:
    """Every dense-projection and LM-head product of one train step.

    Per projection ``(d_in, d_out)`` of each layer, on the step's
    ``T = batch x seq_len`` tokens: the forward ``(T, d_in, d_out)``,
    with ``remat`` its recomputation in the backward pass, and both
    backward products, ``dX (T, d_out, d_in)`` and ``dW (d_in, T,
    d_out)``.  A layer's last product (``w_down``) is not recomputed:
    its output is only the next layer's input, which the backward pass
    does not read.  The LM head (tied or not) runs its forward and both
    backward products once.  Attention's score and value products are
    left out: one of their extents is the head size.
    """
    d = cfg["hidden_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    q_dim, kv_dim = heads * head_dim, kv_heads * head_dim
    f, layers = cfg["intermediate_size"], cfg["num_hidden_layers"]
    t = traffic["batch"] * traffic["seq_len"]
    remat = bool(traffic.get("remat", False))
    projections = [("wq", d, q_dim), ("wk", d, kv_dim), ("wv", d, kv_dim),
                   ("wo", q_dim, d), ("w_gate", d, f), ("w_up", d, f),
                   ("w_down", f, d)]
    out: List[Product] = []
    for name, d_in, d_out in projections:
        out.append((f"{name}.fwd", t, d_in, d_out, layers))
        if remat and name != "w_down":
            out.append((f"{name}.recompute", t, d_in, d_out, layers))
        out.append((f"{name}.dx", t, d_out, d_in, layers))
        out.append((f"{name}.dw", d_in, t, d_out, layers))
    vocab = cfg["vocab_size"]
    out += [("head.fwd", t, d, vocab, 1), ("head.dx", t, vocab, d, 1),
            ("head.dw", d, t, vocab, 1)]
    return out


def lm_train_offloaded_ops(cfg: Dict, traffic: Dict) -> float:
    """Operations (2 m k n) of the products a train step offloads.

    The offload policy's size gate, ``min(m, k, n) >= min_dim``, is
    applied to each product, as the program applies it to each site.
    """
    gate = traffic["min_dim"]
    return float(sum(2 * m * k * n * times
                     for _, m, k, n, times in lm_train_products(cfg, traffic)
                     if min(m, k, n) >= gate))
