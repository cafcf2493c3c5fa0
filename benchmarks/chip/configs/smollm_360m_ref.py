"""Plain reference of SmolLM-360M training: forward, loss, gradient, AdamW.

SmolLM-360M (HuggingFaceTB/SmolLM-360M, ``config.json``) is a Llama
decoder (transformers' ``LlamaForCausalLM``): RMSNorm before attention
and before the MLP, rotary position embedding over half-dimension
pairs, grouped-query attention, a SwiGLU MLP, a final RMSNorm and an
output head tied to the input embedding.  This is that model in plain
``jax.numpy`` and float32, every matrix product at one stated precision
(``highest`` for the reference, ``bf16_3x`` for the control: see
:func:`three_pass`), with AdamW (decoupled weight decay,
bias-corrected moments) as the optimizer.

It imports nothing of the system under test.  The parameters are a
dict laid out as that system lays them out (the layers stacked on a
leading axis: ``embed``, ``blocks/{attn_norm, wq, wk, wv, wo, mlp_norm,
w_gate, w_up, w_down}``, ``final_norm``, matrices as ``x @ w``); the
benchmark makes them here, from the seed, and hands the same arrays to
both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    return {"d": d, "heads": heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": head_dim, "f": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def param_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    L, d, f = g["layers"], g["d"], g["f"]
    q, kv = g["heads"] * g["head_dim"], g["kv_heads"] * g["head_dim"]
    shapes = {"embed": (g["vocab"], d),
              "blocks": {"attn_norm": (L, d), "wq": (L, d, q),
                         "wk": (L, d, kv), "wv": (L, d, kv),
                         "wo": (L, q, d), "mlp_norm": (L, d),
                         "w_gate": (L, d, f), "w_up": (L, d, f),
                         "w_down": (L, f, d)},
              "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (d, g["vocab"])
    return shapes


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words of any whole-number seed (a key for jax.random)."""
    return np.random.SeedSequence(seed % (1 << 63)).generate_state(2)


def init_params(cfg: dict, words) -> dict:
    """Weights from the seed: N(0, initializer_range^2) matrices, unit norms.

    ``words`` is :func:`seed_words`; jit this with ``cfg`` bound, so that
    one compiled program makes every seed's weights on the device.
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


def _bf16_split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def three_pass(f):
    """The bilinear product ``f(a, b)`` from three bf16 passes.

    Each operand is split into a bf16 high part and a bf16 remainder,
    and the product is hi.hi + hi.lo + lo.hi, each pass exact and summed
    in float32: XLA's ``high`` precision, in the forward product and in
    both transposed products of the backward pass alike, on any
    platform.  The control of a float32 configuration at ``highest``.
    """
    def passes(x, y, prod):
        (xh, xl), (yh, yl) = _bf16_split(x), _bf16_split(y)
        return prod(xh, yh) + (prod(xh, yl) + prod(xl, yh))

    @jax.custom_vjp
    def g(a, b):
        return passes(a, b, f)

    def fwd(a, b):
        return passes(a, b, f), (a, b)

    def bwd(res, ct):
        a, b = res
        da = passes(ct, b, lambda c, y: jax.vjp(lambda x: f(x, y), a)[1](c)[0])
        db = passes(ct, a, lambda c, x: jax.vjp(lambda y: f(x, y), b)[1](c)[0])
        return da, db

    g.defvjp(fwd, bwd)
    return g


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def loss(params, tokens, cfg: dict, precision: str, keep: int | None = None):
    """Mean next-token cross-entropy of ``tokens`` (B, T+1).

    ``keep`` takes the mean over the first ``keep`` positions only (a
    fault the correctness check must catch: half of the batch left out).
    """
    g = dims(cfg)
    hd, H, KV = g["head_dim"], g["heads"], g["kv_heads"]
    eps = cfg["rms_norm_eps"]

    def product(f):
        if precision == "bf16_3x":
            return three_pass(lambda a, b: f(a, b, "highest"))
        return lambda a, b: f(a, b, precision)

    mm = product(lambda a, b, p: jnp.matmul(a, b, precision=p))
    scores = product(lambda a, b, p: jnp.einsum("bthd,bshd->bhts", a, b,
                                                precision=p))
    mix = product(lambda a, b, p: jnp.einsum("bhts,bshd->bthd", a, b,
                                             precision=p))

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, T = inputs.shape
    x = params["embed"][inputs]
    half = hd // 2
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]

    def rope(t):  # (B, T, heads, hd)
        rotated = jnp.concatenate([-t[..., half:], t[..., :half]], -1)
        return t * cos + rotated * sin

    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        h = _rms_norm(x, p["attn_norm"], eps)
        q = rope(mm(h, p["wq"]).reshape(B, T, H, hd))
        k = rope(mm(h, p["wk"]).reshape(B, T, KV, hd))
        v = mm(h, p["wv"]).reshape(B, T, KV, hd)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = scores(q, k)
        s = jnp.where(causal, s * hd ** -0.5, -jnp.inf)
        o = mix(jax.nn.softmax(s, axis=-1), v).reshape(B, T, H * hd)
        x = x + mm(o, p["wo"])
        h = _rms_norm(x, p["mlp_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                   p["w_down"])
        return x, None

    # Each layer recomputed in the backward pass: a long sequence's
    # activations of all layers would not fit the chip beside the state.
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], eps)
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    logp = jax.nn.log_softmax(mm(x, head), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll)


def adamw(params, grads, mu, nu, t, opt: dict):
    """One AdamW step (``t`` counts from 1); returns params, mu, nu."""
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)

    def update(p, m, v):
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        return p - opt["lr"] * (m_hat / (jnp.sqrt(v_hat) + opt["eps"])
                                + opt["weight_decay"] * p)

    return jax.tree_util.tree_map(update, params, mu, nu), mu, nu


def make_step(cfg: dict, opt: dict, precision: str,
              keep: int | None = None):
    """One compiled training step ``(p, mu, nu, t, tokens) -> (p, mu, nu,
    loss, leaf gradient norms)``, ``t`` counting from 1."""
    def step(p, mu, nu, t, tokens):
        value, grads = jax.value_and_grad(loss)(p, tokens, cfg, precision,
                                                keep)
        p, mu, nu = adamw(p, grads, mu, nu, t, opt)
        return p, mu, nu, value, _norms(grads)

    return jax.jit(step)


def train_steps(params, batches, step):
    """Run ``step`` (:func:`make_step`) over ``batches`` from ``params``.

    Returns the loss of each step, the norm of each leaf's first
    gradient, and the norm of each leaf's change over all the steps
    (leaves in ``jax.tree_util.tree_leaves`` order), as numpy arrays.
    """
    p = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        p, mu, nu, value, norms = step(p, mu, nu, jnp.float32(t),
                                       jnp.asarray(tokens))
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = np.asarray(jax.device_get(norms))
    change = np.asarray(jax.device_get(_diff_norms(p, params)))
    return np.asarray(losses), grad_norms, change


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree_util.tree_leaves(tree)])


def _diff_norms(a, b):
    return _norms(jax.tree_util.tree_map(jnp.subtract, a, b))
