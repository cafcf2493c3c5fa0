"""Analytic tile-cost model for the v2 fused split-GEMM kernel.

Closed-form selection of ``block_m/n/k`` and the slice-pair schedule
per ``(m, k, n, s, dtype)`` — no autotuning sweep.  Three quantities
are modeled, all hand-computable from the constants below:

* **VMEM footprint** of one grid step: double-buffered input and
  output blocks, the in-kernel slicing working set (fused mode), the
  int32 k-sum scratch and the fold's temporaries.  A candidate block
  shape is admissible only if the footprint fits
  :attr:`TPUParams.vmem_budget`, and the kernels ask Mosaic for that
  footprint (at least the budget) as their VMEM limit.
* **MXU issue cycles** per int8 tile product: the 128x128 systolic
  array retires one 128x128x128 MAC block per 128 cycles, so a
  ``(bm, bk) @ (bk, bn)`` tile costs ``ceil(bm/128) * ceil(bn/128) *
  ceil(bk/128) * 128`` cycles.
* **HBM bytes per grid step**: the kernel streams one A block and one
  B block per step (1 byte/elem int8 pre-sliced, 8 bytes/elem for the
  two f32 halves in fused mode); hi/lo output tiles are written once
  per (m, n) tile because the reduction dims (pair, k-tile) iterate
  innermost.

Candidates are scored by the per-flop bottleneck time
``max(mxu_cycles, hbm_cycles) / (bm*bn*bk)`` with deterministic tie
breaks, so the same inputs always select the same tiles — plans stay
byte-identical across meshes and machines.

The model is also the accounting authority for the v1 -> v2 traffic
claim: v1 materialized every slice *pair* in HBM (``s*(s+1)/2`` gathered
copies of the slice arrays — O(s²·m·k) bytes staged and read), while v2
keeps the ``(s, m, k)``/``(s, k, n)`` slice arrays intact and picks the
pair from the grid via BlockSpec index maps, so the slice data read from
HBM drops to O(s·m·k) — a ``(s+1)/2``x read reduction (3.5x at s=6).
:func:`traffic` reports both so benchmarks can gate on the ratio.

Nothing in this module imports Pallas: the tuner and the offload
interceptor consult it on hosts where ``jax.experimental.pallas`` may
be unavailable.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core.ozaki import num_pair_gemms, pair_indices

__all__ = [
    "TPUParams",
    "TileDecision",
    "Traffic",
    "align_up",
    "pair_schedule",
    "vmem_bytes",
    "mxu_tile_cycles",
    "hbm_bytes_per_step",
    "traffic",
    "select_tiles",
    "split_cost",
]

# Minimum int8 tile on the TPU MXU: 32 sublanes x 128 lanes.  Every
# block dimension the kernel uses must be a multiple of these.
SUBLANE_INT8 = 32
LANE = 128


@dataclasses.dataclass(frozen=True)
class TPUParams:
    """Hardware constants the model prices against (TPU v5e defaults).

    ``bytes_per_cycle`` (HBM bandwidth per core clock) and
    ``macs_per_cycle`` (one 128x128 systolic column step) are the only
    two rates the score uses, so the model stays a two-resource
    roofline: a block shape is memory-bound when streaming its inputs
    takes longer than issuing its MACs.
    """

    vmem_budget: int = 16 * 1024 * 1024   # bytes of VMEM per core
    mxu_dim: int = 128                    # systolic array edge
    clock_hz: float = 940e6               # core clock
    hbm_bw: float = 819e9                 # bytes/s of HBM bandwidth

    @property
    def bytes_per_cycle(self) -> float:
        return self.hbm_bw / self.clock_hz

    @property
    def macs_per_cycle(self) -> int:
        return self.mxu_dim * self.mxu_dim


DEFAULT_PARAMS = TPUParams()

# Candidate block sizes enumerated by select_tiles.  Small by design:
# the score below is exact arithmetic, so enumerating ~3x3x3 shapes is
# a closed-form pick, not an autotuning sweep.
_BM_CANDIDATES = (32, 64, 128, 256)
_BN_CANDIDATES = (128, 256, 512)
_BK_CANDIDATES = (128, 256, 512)


def align_up(x: int, multiple: int) -> int:
    """Round ``x`` up to a multiple of ``multiple`` (min one multiple)."""
    return max(multiple, ((x + multiple - 1) // multiple) * multiple)


def pair_schedule(num_splits: int, mode: str = "ordered"):
    """Slice-pair visit order (ii, jj) for the kernel's pair grid dim.

    ``"ordered"`` — by ascending total shift ``i + j`` (largest weight
    first), identical to :func:`repro.core.ozaki.pair_indices`.  This is
    the only schedule the kernel runs: compensated accumulation order is
    part of the bit-identity contract with the jnp df32 reference.

    ``"grouped"`` — by A-slice index ``i`` so consecutive grid steps
    reuse the resident A block.  Evaluated for traffic accounting only;
    running it would reorder the TwoSum stream and break bit-identity.
    """
    ii, jj = pair_indices(num_splits)
    if mode == "ordered":
        return ii, jj
    if mode == "grouped":
        order = sorted(range(len(ii)), key=lambda p: (ii[p], jj[p]))
        return ii[order], jj[order]
    raise ValueError(f"unknown pair schedule {mode!r};"
                     " expected 'ordered' or 'grouped'")


def vmem_bytes(bm: int, bn: int, bk: int, *, fused: bool = False) -> int:
    """VMEM footprint of one grid step, in bytes.

    An upper bound on what Mosaic allocates for the kernel: compiled
    for a v5e, every candidate block at s = 2 to 12 needs between 0.49x
    and 0.97x of this figure in scoped VMEM.  Per input element: the double-buffered
    block (x2, the Pallas pipeline overlaps the next DMA with the
    current product) of int8 slices (1 byte) or, fused, of two f32
    halves (8 bytes), plus, fused, the quantization's f32 working set
    (the hi/lo remainders, the rounded slice and its residual, the
    int8 select: 20 bytes).  Per output element, 40 bytes: the hi/lo
    f32 output blocks, also double-buffered (16), the int32 k-sum
    scratch and tile product (8), and the fold's f32 temporaries (16).
    """
    in_bytes = 2 * 8 + 20 if fused else 2 * 1
    return in_bytes * (bm * bk + bk * bn) + 40 * bm * bn


def mxu_tile_cycles(bm: int, bn: int, bk: int,
                    params: TPUParams = DEFAULT_PARAMS) -> int:
    """MXU issue cycles for one (bm, bk) @ (bk, bn) int8 tile product."""
    d = params.mxu_dim
    return (math.ceil(bm / d) * math.ceil(bn / d) * math.ceil(bk / d)
            * params.mxu_dim)


def hbm_bytes_per_step(bm: int, bn: int, bk: int, *,
                       fused: bool = False) -> int:
    """Bytes streamed from HBM by one grid step (one A + one B block)."""
    elem_bytes = 8 if fused else 1  # f32 hi+lo halves vs int8 slices
    return elem_bytes * (bm * bk + bk * bn)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Modeled HBM bytes for one emulated GEMM, v1 vs v2.

    ``slice_read_bytes_*`` count the slice data the kernel path must
    read: v1 reads ``s*(s+1)/2`` gathered pair copies, v2 reads the
    ``s`` slice arrays — the O(s²) -> O(s) reduction.  ``stage`` adds
    the staging writes (and the gather's reads) that produce what the
    kernel consumes; ``stream`` is the per-grid-step block traffic
    (identical shape v1/v2 — the win is staging, which is why
    ``read_reduction`` is defined on the slice reads); ``out`` is the
    hi/lo f32 result write.
    """

    slice_read_bytes_v1: int
    slice_read_bytes_v2: int
    stage_bytes_v1: int
    stage_bytes_v2: int
    stream_bytes: int
    out_bytes: int

    @property
    def total_v1(self) -> int:
        return self.stage_bytes_v1 + self.stream_bytes + self.out_bytes

    @property
    def total_v2(self) -> int:
        return self.stage_bytes_v2 + self.stream_bytes + self.out_bytes

    @property
    def read_reduction(self) -> float:
        """Slice bytes read, v1 / v2 == (s + 1) / 2."""
        return self.slice_read_bytes_v1 / self.slice_read_bytes_v2


def traffic(m: int, k: int, n: int, num_splits: int,
            bm: int, bn: int, bk: int, *, fused: bool = False) -> Traffic:
    """Model the HBM bytes one emulated (m, k) @ (k, n) GEMM moves.

    All counts use the padded dims the kernel actually runs on.  In
    fused mode the slices never exist in HBM: staging is the f32 hi/lo
    halves (8 bytes/elem) and the "slice read" is the halves stream.
    """
    mp, kp, np_ = align_up(m, bm), align_up(k, bk), align_up(n, bn)
    elems = mp * kp + kp * np_            # one slice layer, A + B
    pairs = num_pair_gemms(num_splits)
    grid = (mp // bm) * (np_ // bn) * pairs * (kp // bk)
    stream = grid * hbm_bytes_per_step(bm, bn, bk, fused=fused)
    out = 2 * 4 * mp * np_                # hi + lo f32
    # v1: build s slice layers (write), gather s(s+1)/2 pair copies
    # (read the source layers + write the copies).
    v1_read = pairs * elems
    v1_stage = num_splits * elems + 2 * pairs * elems
    if fused:
        v2_read = num_splits * elems      # each layer decoded s times in VMEM
        v2_stage = 2 * 4 * elems          # write the f32 hi/lo halves once
    else:
        v2_read = num_splits * elems      # the (s, ., .) arrays, once each
        v2_stage = num_splits * elems     # slice build writes
    return Traffic(slice_read_bytes_v1=v1_read,
                   slice_read_bytes_v2=v2_read,
                   stage_bytes_v1=v1_stage,
                   stage_bytes_v2=v2_stage,
                   stream_bytes=stream,
                   out_bytes=out)


@dataclasses.dataclass(frozen=True)
class TileDecision:
    """The model's pick for one GEMM site (everything derived, no sweep)."""

    block_m: int
    block_n: int
    block_k: int
    num_splits: int
    pairs: int                    # pair-schedule length s*(s+1)/2
    schedule: str                 # always "ordered" (bit-identity)
    fused: bool
    vmem_bytes: int               # footprint of one grid step
    mxu_cycles_step: int          # issue cycles per tile product
    hbm_bytes_step: int           # streamed bytes per grid step
    # Shape-dependent totals; None when selected canonically (m/n
    # unknown, e.g. for plan recording where tiles must not depend on
    # per-shard geometry).
    kernel_invocations: int | None = None
    traffic_model: Traffic | None = None

    def summary(self) -> dict:
        """Compact dict for Site records / plan JSON / obs events."""
        return {"block_m": self.block_m, "block_n": self.block_n,
                "block_k": self.block_k, "pairs": self.pairs,
                "schedule": self.schedule}


def _candidates(dim: int | None, options, multiple: int):
    """Admissible block sizes for one dim: aligned, not past the padded
    extent (picking a block larger than align_up(dim) only adds pad)."""
    if dim is None:
        return list(options)
    cap = align_up(dim, multiple)
    cands = [c for c in options if c <= cap]
    return cands or [options[0]]


def select_tiles(m: int | None, k: int | None, n: int | None,
                 num_splits: int, dtype=None, *, fused: bool = False,
                 params: TPUParams = DEFAULT_PARAMS) -> TileDecision:
    """Pick ``block_m/n/k`` for an emulated GEMM — closed form, no sweep.

    Pass ``m``/``n`` (and ``k``) as ``None`` for the *canonical*
    decision that depends only on split count and mode — what tuned
    plans record, so a plan solved on a dp=8 mesh is byte-identical to
    one solved on a single device regardless of per-shard geometry.

    ``dtype`` is accepted for the (m, k, n, s, dtype) contract; the
    kernel streams int8 slices (or f32 halves when fused) whatever the
    source dtype, so it does not change the pick today.
    """
    del dtype
    best = None
    best_key = None
    for bm in _candidates(m, _BM_CANDIDATES, SUBLANE_INT8):
        for bn in _candidates(n, _BN_CANDIDATES, LANE):
            for bk in _candidates(k, _BK_CANDIDATES, LANE):
                vb = vmem_bytes(bm, bn, bk, fused=fused)
                if vb > params.vmem_budget:
                    continue
                mxu = mxu_tile_cycles(bm, bn, bk, params)
                hbm = hbm_bytes_per_step(bm, bn, bk, fused=fused)
                hbm_cycles = hbm / params.bytes_per_cycle
                flops = bm * bn * bk
                score = max(mxu, hbm_cycles) / flops
                # Deterministic tie-breaks: per-flop bottleneck time,
                # then per-flop HBM traffic (favor reuse), then the
                # largest block (fewest invocations), then lexicographic.
                key = (score, hbm / flops, -flops, bm, bn, bk)
                if best_key is None or key < best_key:
                    best_key = key
                    best = (bm, bn, bk, vb, mxu, hbm)
    if best is None:  # pragma: no cover - smallest candidate always fits
        raise ValueError("no block shape fits the VMEM budget")
    bm, bn, bk, vb, mxu, hbm = best
    pairs = num_pair_gemms(num_splits)
    invocations = None
    tm = None
    if m is not None and k is not None and n is not None:
        mp, kp, np_ = align_up(m, bm), align_up(k, bk), align_up(n, bn)
        invocations = (mp // bm) * (np_ // bn) * pairs * (kp // bk)
        tm = traffic(m, k, n, num_splits, bm, bn, bk, fused=fused)
    return TileDecision(block_m=bm, block_n=bn, block_k=bk,
                        num_splits=num_splits, pairs=pairs,
                        schedule="ordered", fused=fused, vmem_bytes=vb,
                        mxu_cycles_step=mxu, hbm_bytes_step=hbm,
                        kernel_invocations=invocations, traffic_model=tm)


# Nominal output extent used to convert the slice-stream bytes of
# split_cost into MXU-cycle units without knowing m/n (the tuner prices
# sites by k and flops only; 1024 matches the LM examples' hidden dims).
_NOMINAL_EXTENT = 1024


def split_cost(num_splits: int,
               params: TPUParams = DEFAULT_PARAMS) -> float:
    """Modeled cost of one emulated GEMM at split ``s``, in units of
    one pair-GEMM's MXU time — the tuner's replacement for the bare
    ``n_pairs(s)`` proxy.

    cost(s) = pairs(s) + s * slice_tax, where the tax converts the O(s)
    slice-array read (v2 traffic model) into pair-GEMM units::

        slice_tax = macs_per_cycle * (1/m + 1/n) / bytes_per_cycle

    at the nominal extent above.  The tax is small (~0.04 pair-GEMMs
    per slice on v5e numbers): v2 is compute-bound, which is exactly
    the paper's roofline argument — but the term keeps the solver's
    marginal costs honest about the traffic each extra split adds.
    """
    tax = (params.macs_per_cycle * (2.0 / _NOMINAL_EXTENT)
           / params.bytes_per_cycle)
    return num_pair_gemms(num_splits) + num_splits * tax
