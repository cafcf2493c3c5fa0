"""Llama-style decoder-only LM as pure-JAX programs.

Three programs over one parameter pytree:

* :meth:`Model.apply` / :meth:`Model.loss` — full-context causal
  forward (training).  Blocks are stacked along a leading layer axis
  and run under ``jax.lax.scan`` (optionally rematerialized), so the
  traced program is one block body — exactly the shape the automatic
  offload transform (:mod:`repro.core.intercept`) descends into: the
  projection/MLP/head matmuls appear as ``scan{i}/dot{j}`` sites and
  get routed through the GEMM backend registry, while the attention
  ``QK^T``/``AV`` contractions (``k = head_dim``) stay under the size
  gate and run native.
* :meth:`Model.prefill` — batched prompt ingestion into a fresh KV
  cache (right-padded prompts, per-slot true lengths), returning the
  last-real-token logits.
* :meth:`Model.decode_step` — one greedy-decoding step against the
  cache (one token per slot, per-slot positions).

The *dense* cache layout is ``(num_layers, batch, kv_heads, max_len,
head_dim)`` so the layer axis lines up with the stacked block
parameters and both cache-touching programs are the same ``scan``.

The *paged* cache layout (:meth:`Model.init_paged_cache` plus the
``*_paged`` / ``prefill_chunk*`` programs) replaces the per-slot
``max_len`` rectangle with a shared pool of fixed-size blocks
``(num_layers, num_blocks, kv_heads, block_size, head_dim)`` addressed
through a per-slot block table — slots only consume blocks they have
actually written (see :mod:`repro.serve.kvcache` for the allocator).
The gathered attention view is bit-identical to the dense buffer, so
paged == dense is an exact equivalence, not an approximate one.

No framework dependency (flax/optax are not in the container): params
are plain dicts, initialization is explicit.

DeepSeek-V3 blocks (:class:`~repro.configs.LMConfig` with
``kv_lora_rank``, and possibly ``num_experts``, set) train through the same
:meth:`Model.loss`: the ``first_dense_layers`` leading dense layers run
once each (``params["dense"]``, stacked), then the expert layers under
the one ``scan`` (``params["blocks"]``).  The serving programs cover the
Llama block only.  Multi-head latent attention (:meth:`Model._mla`)
follows DeepSeek-V2/V3 without query compression (``q_lora_rank``
null); its departures from the published code:

* RoPE rotates half-dimension pairs (:func:`_rope`), where DeepSeek
  rotates interleaved pairs: the two differ by a fixed permutation of
  the RoPE columns of ``wq`` and ``wkv_a``;
* no YaRN rope scaling, so the softmax scale is ``1/sqrt(qk_head_dim)``.

The expert layer (:meth:`Model._moe`) scores all ``num_experts``
experts with a sigmoid, picks ``experts_per_tok`` by score plus the
``noaux_tc`` correction bias (a buffer held at zero here, so the pick is
by score), normalizes the picked scores to sum 1 and scales them by
``routed_scaling``.  It keeps the (token, choice) pairs of the experts
it holds (``LMConfig.experts_held``), sorts them by expert, and runs
each expert projection as one ``jax.lax.ragged_dot`` over the held
experts' stacked weights; absent experts add nothing (on one device
there is no exchange), and the shared expert is added once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import LMConfig

__all__ = ["Model"]

# Finite mask value: -inf breaks softmax rows that are fully masked
# (inactive serve slots attend to nothing real); a large negative
# float32 yields harmless uniform attention there instead of NaNs.
_MASK_VALUE = -1e30


def _rms_norm(x, weight, eps):
    # At-least-f32: f32 for f32/bf16 activations (unchanged), f64 for
    # an f64 model — a hardcoded f32 here would push the *weight
    # gradient* (a cross-batch reduction) down to f32, capping the
    # data-parallel == single-device training equivalence at f32 ulps.
    dt = jnp.promote_types(x.dtype, jnp.float32)
    h = x.astype(dt)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * weight.astype(dt)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate half-dim pairs of ``x`` (..., T, H, head_dim).

    ``positions`` is (..., T) — absolute positions, so cached keys and
    fresh queries agree on the rotation regardless of where in the
    sequence this call starts.
    """
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over the head axis
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _sdpa(q, k, v, mask):
    """Softmax(QK^T / sqrt(d)) V with a boolean keep-mask.

    q: (B, T, H, d); k, v: (B, S, H, d); mask: (B, T, S) True = attend.
    Scores are computed and normalized in float32.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    scores = scores * scale
    scores = jnp.where(mask[:, None, :, :], scores, _MASK_VALUE)
    attn = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", attn.astype(v.dtype), v)
    return out


def _tp_enter(axis):
    """Identity forward, ``psum`` over ``axis`` backward.

    Megatron's ``f``: wraps the (replicated) input of a tensor-parallel
    block.  Each tp shard's backward produces only its partial
    contribution to the cotangent; the psum completes it, so the
    residual stream and every replicated parameter upstream see the
    full gradient.
    """
    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None),
             lambda _, g: (jax.lax.psum(g, axis),))
    return f


def _tp_exit(axis):
    """``psum`` over ``axis`` forward, identity backward.

    Megatron's ``g``: closes a tensor-parallel block after the
    row-parallel matmul (``wo`` / ``w_down``), summing the per-shard
    partial products into the replicated output.  The backward is the
    identity because the incoming cotangent is already replicated.
    """
    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None),
             lambda _, ct: (ct,))
    return g


class Model:
    """A decoder-only LM bound to an :class:`~repro.configs.LMConfig`.

    All methods are pure functions of ``(params, ...)`` and safe to
    ``jit`` / ``grad`` / wrap in :func:`repro.core.intercept.offload`.

    ``tp_axis`` (a mesh axis name) switches the block math to
    Megatron-style tensor parallelism for use *inside* a ``shard_map``
    body: the attention projections and the SwiGLU hidden dim are
    column-parallel (each shard holds ``num_heads/tp`` heads and
    ``d_ff/tp`` hidden columns), ``wo``/``w_down`` are row-parallel,
    and each sublayer output is completed with one ``lax.psum`` over
    ``tp_axis``.  The head counts are derived from the *local*
    parameter shapes, so the same code runs the full model
    (``tp_axis=None``) and any shard width.  Gradients of replicated
    parameters (norms, embeddings, head) are completed by the
    identity-forward/psum-backward wrapper around each block input, so
    ``value_and_grad`` of :meth:`loss` is exact per shard.
    """

    def __init__(self, cfg: LMConfig, tp_axis: str | None = None):
        if tp_axis and cfg.mla:
            raise NotImplementedError(
                "tensor parallelism covers the Llama block only")
        self.cfg = cfg
        self.tp_axis = tp_axis
        self.dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    def _tp_in(self, x):
        return _tp_enter(self.tp_axis)(x) if self.tp_axis else x

    def _tp_out(self, x):
        return _tp_exit(self.tp_axis)(x) if self.tp_axis else x

    # -- parameters --------------------------------------------------

    def init_params(self, rng) -> dict:
        """Initialize the parameter pytree.

        Projections get scaled-normal init; the LM head starts at zero
        (untied), so the initial loss is exactly ``log(vocab)`` and the
        first optimizer steps descend monotonically — which is what the
        smoke examples assert.
        """
        cfg = self.cfg
        keys = jax.random.split(rng, 8)
        L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff

        def init(key, shape, scale):
            w = scale * jax.random.normal(key, shape, dtype=jnp.float32)
            return w.astype(self.param_dtype)

        s_in = d ** -0.5
        s_out = s_in / (2 * L) ** 0.5  # residual-branch damping
        if cfg.mla:
            return self._init_deepseek(keys[0])
        params = {
            "embed": init(keys[0], (cfg.vocab_size, d), 0.02),
            "blocks": {
                "attn_norm": jnp.ones((L, d), self.param_dtype),
                "wq": init(keys[1], (L, d, cfg.q_dim), s_in),
                "wk": init(keys[2], (L, d, cfg.kv_dim), s_in),
                "wv": init(keys[3], (L, d, cfg.kv_dim), s_in),
                "wo": init(keys[4], (L, cfg.q_dim, d), s_out),
                "mlp_norm": jnp.ones((L, d), self.param_dtype),
                "w_gate": init(keys[5], (L, d, f), s_in),
                "w_up": init(keys[6], (L, d, f), s_in),
                "w_down": init(keys[7], (L, f, d), s_out),
            },
            "final_norm": jnp.ones((d,), self.param_dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = jnp.zeros((d, cfg.vocab_size),
                                          self.param_dtype)
        return params

    def _init_deepseek(self, rng) -> dict:
        """Parameters of a DeepSeek-V3 block stack (MLA, with or without
        experts).

        ``dense`` stacks the leading dense layers and ``blocks`` the
        expert layers (all layers in ``blocks`` without experts); the
        held experts' weights stack on the axis after the layer axis.
        Normal(0, 0.02) matrices, unit norms, as DeepSeek initializes.
        """
        cfg = self.cfg
        d, H = cfg.d_model, cfg.num_heads
        keys = iter(jax.random.split(rng, 32))

        def init(*shape):
            w = 0.02 * jax.random.normal(next(keys), shape, jnp.float32)
            return w.astype(self.param_dtype)

        def ones(*shape):
            return jnp.ones(shape, self.param_dtype)

        def attention(L):
            r, v = cfg.kv_lora_rank, cfg.v_head_dim
            return {"attn_norm": ones(L, d), "wq": init(L, d, cfg.q_dim),
                    "wkv_a": init(L, d, r + cfg.qk_rope_head_dim),
                    "kv_norm": ones(L, r),
                    "wkv_b": init(L, r, H * (cfg.qk_nope_head_dim + v)),
                    "wo": init(L, H * v, d)}

        def swiglu(L, prefix, *lead, f):
            return {f"{prefix}gate": init(L, *lead, d, f),
                    f"{prefix}up": init(L, *lead, d, f),
                    f"{prefix}down": init(L, *lead, f, d)}

        Ld = cfg.dense_layers
        params = {"embed": init(cfg.vocab_size, d)}
        dense = {**attention(Ld), "mlp_norm": ones(Ld, d),
                 **swiglu(Ld, "w_", f=cfg.d_ff)}
        if cfg.moe:
            Lm = cfg.num_layers - Ld
            params["dense"] = dense
            params["blocks"] = {
                **attention(Lm), "mlp_norm": ones(Lm, d),
                "router": init(Lm, d, cfg.num_experts),
                **swiglu(Lm, "expert_", cfg.held[1], f=cfg.moe_d_ff)}
            if cfg.shared_d_ff:
                params["blocks"].update(
                    swiglu(Lm, "shared_", f=cfg.shared_d_ff))
        else:
            params["blocks"] = dense
        params["final_norm"] = ones(d)
        if not cfg.tie_embeddings:
            params["lm_head"] = init(d, cfg.vocab_size)
        return params

    # -- shared block pieces -----------------------------------------

    def _qkv(self, lp, x, positions):
        """Project + reshape + rope.  x: (B, T, d) -> q/k/v heads.

        Head counts come from the projection shapes, not the config,
        so under tensor parallelism (column-sharded ``wq``/``wk``/
        ``wv``) each shard produces its ``num_heads / tp`` local heads
        from the same code.
        """
        cfg = self.cfg
        B, T = x.shape[:2]
        h = self._tp_in(_rms_norm(x, lp["attn_norm"], cfg.norm_eps))
        q = (h @ lp["wq"]).reshape(B, T, -1, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(B, T, -1, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(B, T, -1, cfg.head_dim)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        return q, k, v

    def _attn_out(self, lp, x, o):
        B, T = x.shape[:2]
        o = o.reshape(B, T, -1)
        return x + self._tp_out(o @ lp["wo"])

    def _mlp(self, lp, x):
        h = self._tp_in(_rms_norm(x, lp["mlp_norm"],
                                  self.cfg.norm_eps))
        gate = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32))
        up = (h @ lp["w_up"]).astype(jnp.float32)
        return x + self._tp_out((gate * up).astype(x.dtype)
                                @ lp["w_down"])

    def _attend(self, lp, x, positions, mask):
        """x plus the layer's attention (GQA or MLA), full context."""
        if self.cfg.mla:
            return self._mla(lp, x, positions, mask)
        q, k, v = self._qkv(lp, x, positions)
        H = q.shape[2]
        o = _sdpa(q, self._repeat_kv(k, H), self._repeat_kv(v, H), mask)
        return self._attn_out(lp, x, o)

    def _mla(self, lp, x, positions, mask):
        """Multi-head latent attention, DeepSeek-V3 without query
        compression (departures in the module docstring).

        Keys and values come from one latent of width ``kv_lora_rank``
        (RMS-normalized), and one RoPE key of width ``qk_rope_head_dim``
        is shared by all heads.
        """
        cfg = self.cfg
        B, T = x.shape[:2]
        H, nope = cfg.num_heads, cfg.qk_nope_head_dim
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, T, H, nope + rope)
        ckv = h @ lp["wkv_a"]
        latent = _rms_norm(ckv[..., :r], lp["kv_norm"], cfg.norm_eps)
        kv = (latent @ lp["wkv_b"]).reshape(B, T, H, -1)
        k_pe = _rope(ckv[..., None, r:], positions, cfg.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions, cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))],
            axis=-1)
        o = _sdpa(q, k, kv[..., nope:], mask)
        return x + o.reshape(B, T, -1) @ lp["wo"]

    def _route(self, lp, h):
        """(experts (N, k), weights (N, k)) each token of ``h`` (N, d)
        picks, over all ``num_experts`` experts."""
        cfg = self.cfg
        with jax.named_scope("moe_route"):
            scores = jax.nn.sigmoid((h @ lp["router"]).astype(jnp.float32))
            # noaux_tc picks by score plus a correction bias, a buffer
            # held at zero here: the pick is by score.
            _, expert = jax.lax.top_k(scores, cfg.experts_per_tok)
            weight = jnp.take_along_axis(scores, expert, axis=-1)
            weight = (weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
                      * cfg.routed_scaling)
        return expert, weight

    def _moe(self, lp, x):
        """x plus the expert layer of its normalized rows."""
        B, T, d = x.shape
        h = _rms_norm(x, lp["mlp_norm"], self.cfg.norm_eps)
        return x + self._experts(lp, h.reshape(B * T, d)).reshape(B, T, d)

    def _experts(self, lp, h):
        """The expert layer of ``h`` (N, d): the routed experts held
        here, and the shared expert (the routing is in the module
        docstring)."""
        cfg = self.cfg
        first, held = cfg.held
        k = cfg.experts_per_tok
        expert, weight = self._route(lp, h)
        with jax.named_scope("moe_dispatch"):
            local = expert.reshape(-1) - first
            mine = (local >= 0) & (local < held)
            local = jnp.where(mine, local, held)  # absent experts last
            order = jnp.argsort(local, stable=True)
            token = order // k
            sizes = jnp.bincount(local, length=held + 1)[:held].astype(
                jnp.int32)
            # Rows past the held pairs belong to no group, and XLA:TPU's
            # grouped product leaves them unwritten: they are selected
            # away here and in the combine, forward and backward.
            routed = mine[order]
            rows = jnp.where(routed[:, None], h[token], 0)
            weight = jnp.where(mine, weight.reshape(-1), 0.0)[order]
        gate = jax.lax.ragged_dot(rows, lp["expert_gate"], sizes)
        up = jax.lax.ragged_dot(rows, lp["expert_up"], sizes)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(h.dtype)
        out = jax.lax.ragged_dot(act, lp["expert_down"], sizes)
        with jax.named_scope("moe_combine"):
            out = (jnp.where(routed[:, None], out, 0)
                   * weight[:, None].astype(out.dtype))
            y = jnp.zeros(h.shape, out.dtype).at[token].add(out)
        if cfg.shared_d_ff:
            gate = jax.nn.silu((h @ lp["shared_gate"]).astype(jnp.float32))
            up = (h @ lp["shared_up"]).astype(jnp.float32)
            y = y + (gate * up).astype(h.dtype) @ lp["shared_down"]
        return y

    def _repeat_kv(self, kv, num_heads):
        """(B, S, KV, d) -> (B, S, H, d) for grouped-query attention.

        ``num_heads`` is the query head count *of this shard* (under
        tp, ``cfg.num_heads / tp``), so the group size is preserved.
        """
        rep = num_heads // kv.shape[2]
        return jnp.repeat(kv, rep, axis=2) if rep > 1 else kv

    def _head(self, params, x):
        """Final norm + LM head on (..., d) activations."""
        x = _rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        return x @ head

    # -- full-context forward (training) -----------------------------

    def apply(self, params, tokens) -> jax.Array:
        """Causal logits for ``tokens`` (B, T) -> (B, T, vocab)."""
        cfg = self.cfg
        B, T = tokens.shape
        x = params["embed"][tokens].astype(self.dtype)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        causal = jnp.tril(jnp.ones((T, T), bool))
        mask = jnp.broadcast_to(causal, (B, T, T))

        def dense(x, lp):
            return self._mlp(lp, self._attend(lp, x, positions, mask)), None

        def expert(x, lp):
            return self._moe(lp, self._attend(lp, x, positions, mask)), None

        if cfg.remat:
            dense, expert = jax.checkpoint(dense), jax.checkpoint(expert)
        # DeepSeek-V3: the leading dense layers once each, then the
        # expert layers under the scan.
        for i in range(cfg.dense_layers if cfg.moe else 0):
            x, _ = dense(x, jax.tree_util.tree_map(lambda a: a[i],
                                                   params["dense"]))
        x, _ = jax.lax.scan(expert if cfg.moe else dense, x,
                            params["blocks"])
        return self._head(params, x)

    def loss(self, params, tokens) -> jax.Array:
        """Mean causal cross-entropy over ``tokens`` (B, T+1).

        Computed in at-least-f32: f32 for f32/bf16 activations
        (unchanged), f64 for an f64 model — downcasting would cap
        data-parallel == single-device loss agreement at f32 ulps.
        The mean is taken per sequence first and then over the batch,
        the grouping a batch-sharded (dp) step reduces in, so the two
        agree to the rounding of the last B-term mean rather than of a
        single B*T-term f32 sum.
        """
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = self.apply(params, inputs)
        logits = logits.astype(jnp.promote_types(logits.dtype,
                                                 jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(jnp.mean(nll, axis=(1, 2)))

    # -- KV-cache programs (serving) ---------------------------------

    def _llama_only(self, what: str) -> None:
        if self.cfg.mla:
            raise NotImplementedError(
                f"{what} holds the Llama block's keys and values only")

    def init_cache(self, batch: int, max_len: int) -> dict:
        """Empty cache: stacked K/V buffers + per-slot lengths."""
        cfg = self.cfg
        self._llama_only("the KV cache")
        shape = (cfg.num_layers, batch, cfg.num_kv_heads, max_len,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype),
                "length": jnp.zeros((batch,), jnp.int32)}

    def _cached_forward(self, params, cache, tokens, start):
        """Shared prefill/decode body.

        tokens: (B, T) new tokens; start: (B,) their first absolute
        position (0 for prefill, current length for decode).  Writes
        the new K/V at ``start..start+T-1`` per slot, attends over the
        whole buffer under a key_pos <= query_pos mask, and returns
        ``(new_cache_kv, hidden (B, T, d))``.
        """
        cfg = self.cfg
        B, T = tokens.shape
        S = cache["k"].shape[3]
        x = params["embed"][tokens].astype(self.dtype)
        positions = start[:, None] + jnp.arange(T)          # (B, T)
        key_pos = jnp.arange(S)                             # (S,)
        # Causal over absolute positions; anything above the query's
        # position is either future or stale buffer garbage — masked.
        mask = key_pos[None, None, :] <= positions[:, :, None]

        def write(buf, new, p):
            # buf: (KV, S, d); new: (T, KV, d); p: scalar start.  All
            # three start indices must share p's dtype (int32) or x64
            # mode promotes the literal zeros to int64.
            zero = jnp.zeros((), p.dtype)
            return jax.lax.dynamic_update_slice(
                buf, jnp.moveaxis(new, 0, 1), (zero, p, zero))

        def block(x, layer):
            lp, k_buf, v_buf = layer
            q, k, v = self._qkv(lp, x, positions)
            k_buf = jax.vmap(write)(k_buf, k, start)
            v_buf = jax.vmap(write)(v_buf, v, start)
            k_all = jnp.moveaxis(k_buf, 1, 2)  # (B, S, KV, d)
            v_all = jnp.moveaxis(v_buf, 1, 2)
            H = q.shape[2]
            o = _sdpa(q, self._repeat_kv(k_all, H),
                      self._repeat_kv(v_all, H), mask)
            x = self._attn_out(lp, x, o)
            x = self._mlp(lp, x)
            return x, (k_buf, v_buf)

        x, (k_new, v_new) = jax.lax.scan(
            block, x, (params["blocks"], cache["k"], cache["v"]))
        return k_new, v_new, x

    def prefill(self, params, tokens, lengths, max_len: int):
        """Ingest right-padded prompts into a fresh cache.

        tokens: (b, P) prompts padded to a common length P; lengths:
        (b,) true prompt lengths.  Returns ``(cache, last_logits)``
        where ``last_logits`` (b, vocab) are taken at each prompt's
        final real token.  Padding positions do get written to the
        buffer, but decode queries never attend past ``length`` and the
        next decode write overwrites position ``length`` first.
        """
        b = tokens.shape[0]
        cache = self.init_cache(b, max_len)
        start = jnp.zeros((b,), jnp.int32)
        k_new, v_new, x = self._cached_forward(params, cache, tokens,
                                               start)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = self._head(params, last[:, 0, :])
        return ({"k": k_new, "v": v_new,
                 "length": lengths.astype(jnp.int32)}, logits)

    def decode_step(self, params, cache, tokens, active):
        """One decoding step: consume ``tokens`` (B,), emit next logits.

        ``active`` (B, bool) gates the length bump so idle slots don't
        creep toward the buffer end; their K/V writes land at their
        stale ``length`` and are overwritten on the next admission.
        """
        start = cache["length"]
        k_new, v_new, x = self._cached_forward(params, cache,
                                               tokens[:, None], start)
        logits = self._head(params, x[:, 0, :])
        new_len = jnp.where(active, start + 1, start)
        return ({"k": k_new, "v": v_new, "length": new_len}, logits)

    def prefill_chunk(self, params, k, v, tokens, start, piece_len):
        """One chunk of a (possibly multi-wave) dense prefill.

        k/v: gathered cache rows (L, rows, KV, max_len, d) for the
        slots in this wave; tokens: (rows, W) chunk tokens right-padded
        to the wave width; start: (rows,) absolute position of each
        chunk's first token; piece_len: (rows,) true chunk lengths.
        Returns the updated rows plus logits at each chunk's last real
        token (only meaningful for chunks that complete their prompt).

        Chunk padding is written at ``start + piece_len ..`` within the
        slot's own rectangle and overwritten by the next chunk/decode
        write before anything attends to it, exactly like the padded
        tail of an unchunked prefill wave.
        """
        k_new, v_new, x = self._cached_forward(
            params, {"k": k, "v": v}, tokens, start)
        last = jnp.take_along_axis(
            x, (piece_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = self._head(params, last[:, 0, :])
        return k_new, v_new, logits

    # -- paged KV-cache programs (serving) ---------------------------

    def init_paged_cache(self, num_blocks: int, block_size: int) -> dict:
        """Empty K/V block pools for the paged cache layout.

        The block table and per-slot lengths are owned by the allocator
        (:class:`repro.serve.kvcache.PagedKVCache`), which assembles
        the full cache dict around these pools.
        """
        cfg = self.cfg
        self._llama_only("the paged KV cache")
        shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads,
                 block_size, cfg.head_dim)
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype)}

    def _paged_forward(self, params, k_pool, v_pool, table, tokens,
                       start, write_mask):
        """Shared paged prefill/decode body (block-table indirection).

        table: (B, nb + 1) int32 physical block ids; entry ``j`` maps
        the slot's logical block ``j`` (positions ``j*bs .. j*bs+bs-1``)
        into the pool, and the *trailing column* is the slot's trash
        block — writes of padded / inactive positions are routed there
        instead of at a real block, so chunk padding and masked decode
        writes can never corrupt another slot's cache.  write_mask:
        (B, T) bool, True where the token is real.

        The per-slot view gathered for attention is laid out exactly
        like the dense buffer's ``(B, S, KV, d)`` with
        ``S = nb * block_size``: every unmasked position holds the same
        written value, every masked position is squashed to the same
        ``_MASK_VALUE`` score and an exactly-zero attention weight —
        which is what makes paged == dense *bitwise*, not just close.
        """
        cfg = self.cfg
        B, T = tokens.shape
        nb = table.shape[1] - 1
        bs = k_pool.shape[3]
        S = nb * bs
        x = params["embed"][tokens].astype(self.dtype)
        positions = start[:, None] + jnp.arange(T)          # (B, T)
        key_pos = jnp.arange(S)
        mask = key_pos[None, None, :] <= positions[:, :, None]

        # Destination of each new token: logical block + offset, mapped
        # through the table; padded tokens index the trash column.
        col = jnp.where(write_mask, positions // bs, nb)
        phys = jnp.take_along_axis(table, col, axis=1)      # (B, T)
        flat_phys = phys.reshape(-1)
        flat_off = (positions % bs).reshape(-1)
        attend = table[:, :nb]                              # (B, nb)

        def write(pool, new):
            # pool: (NB, KV, bs, d); new: (B, T, KV, d).  The advanced
            # indices at dims 0/2 broadcast to the front, so updates
            # are (B*T, KV, d).  Trash-block collisions are fine: that
            # block is only ever read under the mask.
            return pool.at[flat_phys, :, flat_off, :].set(
                new.reshape(B * T, new.shape[2], new.shape[3]))

        def gather(pool):
            # (B, nb, KV, bs, d) -> the dense buffer's (B, KV, S, d),
            # then the dense path's own moveaxis.  Going through the
            # buffer layout is load-bearing for bitwise paged == dense:
            # feeding the attention einsum a differently-laid-out (but
            # value-identical) operand changes the GEMM's accumulation
            # order on CPU by ~1 ulp.
            buf = pool[attend].transpose(0, 2, 1, 3, 4).reshape(
                B, -1, S, cfg.head_dim)
            return jnp.moveaxis(buf, 1, 2)                  # (B, S, KV, d)

        def block(x, layer):
            lp, kp, vp = layer
            q, k, v = self._qkv(lp, x, positions)
            kp = write(kp, k)
            vp = write(vp, v)
            k_all = gather(kp)
            v_all = gather(vp)
            H = q.shape[2]
            o = _sdpa(q, self._repeat_kv(k_all, H),
                      self._repeat_kv(v_all, H), mask)
            x = self._attn_out(lp, x, o)
            x = self._mlp(lp, x)
            return x, (kp, vp)

        x, (k_new, v_new) = jax.lax.scan(
            block, x, (params["blocks"], k_pool, v_pool))
        return k_new, v_new, x

    def prefill_chunk_paged(self, params, k, v, table, tokens, start,
                            piece_len):
        """Paged analogue of :meth:`prefill_chunk` over the block pools.

        k/v are the *whole* pools (every wave writes through the block
        table, no gather/scatter of rows); table holds the wave rows'
        block-table entries (incl. the trash column).
        """
        T = tokens.shape[1]
        write_mask = jnp.arange(T)[None, :] < piece_len[:, None]
        k_new, v_new, x = self._paged_forward(
            params, k, v, table, tokens, start, write_mask)
        last = jnp.take_along_axis(
            x, (piece_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = self._head(params, last[:, 0, :])
        return k_new, v_new, logits

    def decode_step_paged(self, params, cache, tokens, active):
        """One decoding step against the paged cache.

        Same contract as :meth:`decode_step`; inactive slots' writes
        are routed to their trash block (the dense path writes them at
        the stale length instead), and the length bump is gated the
        same way.
        """
        start = cache["length"]
        k_new, v_new, x = self._paged_forward(
            params, cache["k"], cache["v"], cache["block_table"],
            tokens[:, None], start, active[:, None])
        logits = self._head(params, x[:, 0, :])
        new_len = jnp.where(active, start + 1, start)
        return ({"k": k_new, "v": v_new,
                 "block_table": cache["block_table"],
                 "length": new_len}, logits)

    def greedy(self, logits) -> jax.Array:
        """Greedy token choice (B, vocab) -> (B,) int32."""
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
