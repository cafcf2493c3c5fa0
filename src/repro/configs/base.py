"""Model/run configuration: one frozen record, named presets, overrides.

The LM subsystem (``repro.models`` / ``repro.train`` / ``repro.serve``)
is configured by a single immutable :class:`LMConfig`.  Presets are
registered by name (``get_config("smollm_360m")``) and specialized with
``cfg.replace(num_layers=2, d_model=128)`` — the pattern the example
drivers use to scale the same architecture from CI-smoke size up to the
full model without touching model code.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

__all__ = ["LMConfig", "get_config", "register_config", "available_configs"]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Architecture + numerics of a decoder-only LM.

    The default block is Llama's (RMSNorm, RoPE, grouped-query attention,
    SwiGLU).  The optional block fields, each off at its default, turn
    on DeepSeek-V3's parts; which are set decides the block:

    * ``kv_lora_rank > 0``: multi-head latent attention (MLA) with
      ``qk_nope_head_dim``/``qk_rope_head_dim`` query-key widths and
      ``v_head_dim`` value width per head (no query compression);
    * ``num_experts > 0`` (with MLA): the layers after the first
      ``first_dense_layers`` are expert layers with ``num_experts``
      routed experts of width ``moe_d_ff``, ``experts_per_tok`` a token,
      routing weights scaled by ``routed_scaling``, and one shared
      SwiGLU of width ``shared_d_ff`` (0: none).  ``experts_held`` is
      the ``(first, count)`` range of routed experts this device holds
      (expert parallelism: the router still scores all of them); the
      default holds them all.

    Attributes:
      name: preset name this config was derived from.
      vocab_size: token vocabulary size.
      num_layers: number of decoder blocks (stacked, run under ``scan``).
      d_model: residual stream width.
      num_heads: query heads.
      num_kv_heads: key/value heads (GQA when ``< num_heads``).
      head_dim: per-head width (RoPE operates on this axis).
      d_ff: SwiGLU hidden width.
      max_seq_len: nominal context length (serving default; RoPE itself
        is position-parametric and does not bake this in).
      rope_theta: RoPE frequency base.
      norm_eps: RMSNorm epsilon.
      dtype: activation dtype name (``"float32"`` / ``"bfloat16"``).
      param_dtype: parameter dtype name.
      remat: rematerialize each block under ``jax.checkpoint`` (the
        offload transform inlines remat bodies, so emulated sites
        survive the recompute schedule).
      tie_embeddings: reuse the embedding matrix as the LM head.
      eos_id: end-of-sequence token id for serving, or ``None`` to
        decode until ``max_new_tokens``.
      kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim:
        MLA widths (see above); ``num_heads`` is then MLA's head count.
      first_dense_layers, num_experts, experts_per_tok, moe_d_ff,
        shared_d_ff, routed_scaling, experts_held: the expert layers
        (see above); ``d_ff`` is then the leading dense layers' width.
    """

    name: str = "smollm_360m"
    vocab_size: int = 49152
    num_layers: int = 32
    d_model: int = 960
    num_heads: int = 15
    num_kv_heads: int = 5
    head_dim: int = 64
    d_ff: int = 2560
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "float32"
    param_dtype: str = "float32"
    remat: bool = False
    tie_embeddings: bool = False
    eos_id: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_dense_layers: int = 0
    num_experts: int = 0
    experts_per_tok: int = 0
    moe_d_ff: int = 0
    shared_d_ff: int = 0
    routed_scaling: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
            first, count = self.experts_held
            if not (0 <= first and count >= 1
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} is not a range of "
                    f"the {self.num_experts} experts")
        if self.moe and not (0 < self.experts_per_tok <= self.num_experts
                             and self.first_dense_layers < self.num_layers):
            raise ValueError(
                f"experts_per_tok={self.experts_per_tok} of "
                f"num_experts={self.num_experts}, first_dense_layers="
                f"{self.first_dense_layers} of num_layers={self.num_layers}")
        if self.moe and not self.mla:
            raise ValueError("expert layers come in the DeepSeek-V3 block, "
                             "with MLA: set kv_lora_rank")
        if self.mla and self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim} "
                             "must be even (RoPE rotates half-dim pairs)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} must be a multiple of "
                f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even "
                             "(RoPE rotates half-dim pairs)")

    def replace(self, **overrides) -> "LMConfig":
        """A copy with ``overrides`` applied (validation re-runs)."""
        return dataclasses.replace(self, **overrides)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts held here."""
        return self.experts_held or (0, self.num_experts)

    @property
    def dense_layers(self) -> int:
        """Layers with a dense MLP: all of them without experts."""
        return self.first_dense_layers if self.moe else self.num_layers

    @property
    def qk_head_dim(self) -> int:
        """MLA's query/key width per head (no-RoPE part + RoPE part)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def q_dim(self) -> int:
        if self.mla:
            return self.num_heads * self.qk_head_dim
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def attn_params(self) -> int:
        """Parameters of one layer's attention (its norm included)."""
        d = self.d_model
        if self.mla:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            h = self.num_heads
            return (d + d * self.q_dim + d * (r + rope) + r
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        return d + d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    def num_params(self) -> int:
        """Exact parameter count of :meth:`repro.models.lm.Model.init_params`."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        dense = self.attn_params() + d + 3 * d * f  # norm + SwiGLU
        moe = 0
        if self.moe:
            moe = (self.attn_params() + d + d * self.num_experts
                   + 3 * d * self.moe_d_ff * self.held[1]
                   + 3 * d * self.shared_d_ff)
        head = 0 if self.tie_embeddings else d * v
        return (v * d + self.dense_layers * dense
                + (self.num_layers - self.dense_layers) * moe + d + head)


_CONFIGS: Dict[str, LMConfig] = {}


def register_config(cfg: LMConfig) -> LMConfig:
    """Register ``cfg`` under ``cfg.name``; returns it for chaining."""
    _CONFIGS[cfg.name] = cfg
    return cfg


def available_configs():
    """Sorted registered preset names."""
    return sorted(_CONFIGS)


def get_config(name: str) -> LMConfig:
    """Look up a preset by name.

    The returned config is frozen; specialize with ``.replace(...)``.
    """
    try:
        return _CONFIGS[name]
    except KeyError:
        raise ValueError(f"unknown config {name!r}; available: "
                         f"{', '.join(available_configs())}") from None


# SmolLM-360M geometry (the paper-scale serving target of the ROADMAP
# dry runs); the examples shrink it with .replace for CPU runs.
register_config(LMConfig(name="smollm_360m"))

# A CI/test-scale preset: two blocks at d128 — large enough that the
# projection GEMMs clear the default offload size gate (m=k=n >= 128
# once batch*seq >= 128) while a full train step stays sub-second on
# CPU, small enough that attention (k = head_dim = 32) stays native.
register_config(LMConfig(
    name="tiny", vocab_size=512, num_layers=2, d_model=128,
    num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
    max_seq_len=256))

# CPU-sized reductions of the same architecture, used by the example
# drivers (examples/train_lm.py presets "reduced" and "100m").
register_config(LMConfig(
    name="reduced", vocab_size=4096, num_layers=6, d_model=256,
    num_heads=8, num_kv_heads=4, head_dim=32, d_ff=1024,
    max_seq_len=1024))
register_config(LMConfig(
    name="reduced_100m", vocab_size=16384, num_layers=12, d_model=1024,
    num_heads=16, num_kv_heads=8, head_dim=64, d_ff=2816,
    max_seq_len=2048))
