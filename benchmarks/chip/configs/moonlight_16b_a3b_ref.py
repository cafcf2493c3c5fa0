"""Plain reference of Moonlight-16B-A3B training: forward, loss, gradient,
AdamW, on one chip's share of an expert-parallel deployment.

Moonlight-16B-A3B (moonshotai/Moonlight-16B-A3B, ``config.json``,
``model_type: deepseek_v3``) is a DeepSeek-V3 decoder: RMSNorm before
attention and before the MLP, multi-head latent attention (MLA), a
leading dense SwiGLU layer and then expert layers, a final RMSNorm and
an untied output head.

- MLA, without query compression (``q_lora_rank`` null): per head a
  query of ``qk_nope_head_dim + qk_rope_head_dim``; keys and values
  from one latent of ``kv_lora_rank`` (RMS-normalized) through
  ``wkv_b``; one RoPE key of ``qk_rope_head_dim`` shared by all heads;
  softmax scale ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``.  RoPE
  rotates half-dimension pairs where DeepSeek rotates interleaved
  pairs, a fixed permutation of the RoPE columns of ``wq``/``wkv_a``.
- The expert layer: sigmoid scores over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest picked (``noaux_tc``'s correction
  bias, a buffer held at zero, takes no part); the picked scores
  normalized to sum 1 (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``.  Each expert and the shared expert
  (``n_shared_experts`` x ``moe_intermediate_size`` wide) is a SwiGLU.
- This chip holds the experts ``experts_held = [first, count]``: the
  layer's result here is the held experts' part plus the shared
  expert, which is what the program computes (nothing stands in for
  the absent experts).  Every held expert runs densely on every token,
  times a routing weight that is zero where the token did not pick it:
  no sort, gather, scatter or grouped product.

Every matrix product is at one stated precision (``highest`` for the
reference, ``bf16_3x`` for the control).  It imports nothing of the
system under test; the pieces it shares with the SmolLM-360M reference
(seed words, the three-pass control, AdamW, leaf norms) come from that
file.  The parameters are laid out as the system lays them out:
``embed``; ``dense`` (the leading dense layers) and ``blocks`` (the
expert layers), each stacked on a leading layer axis, with
``attn_norm, wq, wkv_a, kv_norm, wkv_b, wo, mlp_norm`` and the dense
``w_gate, w_up, w_down`` or the ``router``, the held experts'
``expert_{gate,up,down}`` (experts on the axis after the layer axis)
and ``shared_{gate,up,down}``; ``final_norm``; ``lm_head``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _base():
    path = Path(__file__).with_name("smollm_360m_ref.py")
    spec = importlib.util.spec_from_file_location("moonlight_ref_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BASE = _base()
seed_words = _BASE.seed_words
three_pass = _BASE.three_pass
adamw = _BASE.adamw
_rms_norm = _BASE._rms_norm


def dims(cfg: dict) -> dict:
    first, held = cfg["experts_held"]
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "experts": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "first": first, "held": held,
            "dense": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def param_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    d, H = g["d"], g["heads"]

    def attention(L):
        return {"attn_norm": (L, d), "wq": (L, d, H * (g["nope"] + g["rope"])),
                "wkv_a": (L, d, g["r"] + g["rope"]), "kv_norm": (L, g["r"]),
                "wkv_b": (L, g["r"], H * (g["nope"] + g["v"])),
                "wo": (L, H * g["v"], d), "mlp_norm": (L, d)}

    Ld, Lm = g["dense"], g["layers"] - g["dense"]
    E, fe, fs = g["held"], g["fe"], g["fs"]
    return {"embed": (g["vocab"], d),
            "dense": {**attention(Ld), "w_gate": (Ld, d, g["f"]),
                      "w_up": (Ld, d, g["f"]), "w_down": (Ld, g["f"], d)},
            "blocks": {**attention(Lm), "router": (Lm, d, g["experts"]),
                       "expert_gate": (Lm, E, d, fe),
                       "expert_up": (Lm, E, d, fe),
                       "expert_down": (Lm, E, fe, d),
                       "shared_gate": (Lm, d, fs), "shared_up": (Lm, d, fs),
                       "shared_down": (Lm, fs, d)},
            "final_norm": (d,), "lm_head": (d, g["vocab"])}


def init_params(cfg: dict, words) -> dict:
    """Weights from the seed: N(0, initializer_range^2) matrices, unit norms.

    ``words`` is :func:`seed_words`; jit this with ``cfg`` bound.
    """
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    std = cfg["initializer_range"]
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        if "norm" in jax.tree_util.keystr(path):
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def routing(h, router, cfg: dict, mm):
    """(expert ids (N, k), weights (N, k)) of each token's picks."""
    g = dims(cfg)
    scores = jax.nn.sigmoid(mm(h, router))
    _, picked = jax.lax.top_k(scores, g["k"])
    weight = jnp.take_along_axis(scores, picked, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return picked, weight * cfg["routed_scaling_factor"]


def held_weights(picked, weight, cfg: dict):
    """(N, held) routing weight of each held expert, zero where unpicked."""
    g = dims(cfg)
    ids = g["first"] + jnp.arange(g["held"])
    hit = picked[:, :, None] == ids[None, None, :]
    return jnp.sum(jnp.where(hit, weight[:, :, None], 0.0), axis=1)


def _products(precision: str):
    def product(f):
        if precision == "bf16_3x":
            return three_pass(lambda a, b: f(a, b, "highest"))
        return lambda a, b: f(a, b, precision)

    def einsum(spec):
        return product(lambda a, b, p: jnp.einsum(spec, a, b, precision=p))

    return {"mm": product(lambda a, b, p: jnp.matmul(a, b, precision=p)),
            "scores": einsum("bthd,bshd->bhts"),
            "mix": einsum("bhts,bshd->bthd"),
            "expert_in": einsum("nd,edf->enf"),
            "expert_out": einsum("enf,efd->end")}


def expert_layer(h, p, cfg: dict, mm, expert_in, expert_out):
    """The held experts' part plus the shared expert, for h (N, d)."""
    picked, weight = routing(h, p["router"], cfg, mm)
    gate = expert_in(h, p["expert_gate"])
    up = expert_in(h, p["expert_up"])
    y = expert_out(jax.nn.silu(gate) * up, p["expert_down"])  # (E, N, d)
    routed = jnp.sum(held_weights(picked, weight, cfg).T[:, :, None] * y,
                     axis=0)
    shared = mm(jax.nn.silu(mm(h, p["shared_gate"])) * mm(h, p["shared_up"]),
                p["shared_down"])
    return routed + shared


def loss(params, tokens, cfg: dict, precision: str, keep: int | None = None):
    """Mean next-token cross-entropy of ``tokens`` (B, T+1), over the
    sliced vocabulary; ``keep`` takes the mean over the first ``keep``
    positions only (a fault the correctness check must catch)."""
    g = dims(cfg)
    H, nope, rope, r = g["heads"], g["nope"], g["rope"], g["r"]
    eps = cfg["rms_norm_eps"]
    ops = _products(precision)
    mm = ops["mm"]

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, T = inputs.shape
    x = params["embed"][inputs]
    half = rope // 2
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]

    def rot(t):  # (B, T, heads, rope)
        rotated = jnp.concatenate([-t[..., half:], t[..., :half]], -1)
        return t * cos + rotated * sin

    causal = jnp.tril(jnp.ones((T, T), bool))

    def attention(x, p):
        h = _rms_norm(x, p["attn_norm"], eps)
        q = mm(h, p["wq"]).reshape(B, T, H, nope + rope)
        ckv = mm(h, p["wkv_a"])
        kv = mm(_rms_norm(ckv[..., :r], p["kv_norm"], eps),
                p["wkv_b"]).reshape(B, T, H, nope + g["v"])
        k_pe = jnp.broadcast_to(rot(ckv[..., None, r:]), (B, T, H, rope))
        q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
        k = jnp.concatenate([kv[..., :nope], k_pe], -1)
        s = ops["scores"](q, k)
        s = jnp.where(causal, s * (nope + rope) ** -0.5, -jnp.inf)
        o = ops["mix"](jax.nn.softmax(s, axis=-1), kv[..., nope:])
        return x + mm(o.reshape(B, T, H * g["v"]), p["wo"])

    def dense_layer(x, p):
        x = attention(x, p)
        h = _rms_norm(x, p["mlp_norm"], eps)
        return x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                      p["w_down"]), None

    def moe_layer(x, p):
        x = attention(x, p)
        h = _rms_norm(x, p["mlp_norm"], eps).reshape(B * T, -1)
        y = expert_layer(h, p, cfg, mm, ops["expert_in"], ops["expert_out"])
        return x + y.reshape(B, T, -1), None

    # Each layer recomputed in the backward pass, as the program does.
    x, _ = jax.lax.scan(jax.checkpoint(dense_layer), x, params["dense"])
    x, _ = jax.lax.scan(jax.checkpoint(moe_layer), x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], eps)
    logp = jax.nn.log_softmax(mm(x, params["lm_head"]), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll)


def make_step(cfg: dict, opt: dict, precision: str,
              keep: int | None = None):
    """One compiled training step ``(p, mu, nu, t, tokens) -> (p, mu, nu,
    loss, leaf gradient norms)``, ``t`` counting from 1.  The state is
    donated: one chip holds one copy of the weights and moments."""
    def step(p, mu, nu, t, tokens):
        value, grads = jax.value_and_grad(loss)(p, tokens, cfg, precision,
                                                keep)
        p, mu, nu = adamw(p, grads, mu, nu, t, opt)
        return p, mu, nu, value, _BASE._norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_steps(params, batches, step):
    """Run ``step`` (:func:`make_step`) over ``batches`` from ``params``,
    which the first step consumes.

    Returns what the SmolLM reference's ``train_steps`` returns: the loss
    of each step, the norm of each leaf's first gradient, and the norm of
    each leaf's change over all the steps, measured against a host copy
    of ``params``.
    """
    start = jax.device_get(params)
    p = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    del params
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        p, mu, nu, value, norms = step(p, mu, nu, jnp.float32(t),
                                       jnp.asarray(tokens))
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = np.asarray(jax.device_get(norms))
    del mu, nu
    change = _BASE._diff_norms(p, jax.device_put(start))
    return (np.asarray(losses), grad_norms,
            np.asarray(jax.device_get(change)))
