"""Pallas tiled Ozaki split-GEMM kernels (v2 fused pair-indexing).

One kernel computes the whole emulated GEMM: the grid walks
``(m-tiles, n-tiles, slice-pairs, k-tiles)`` and every step issues one
INT8xINT8->INT32 tile product on the MXU and sums it in an int32 VMEM
scratch, over the k-tiles and over the pairs of one
:func:`repro.core.ozaki.fold_runs` run (pairs of one shift); after the
run's last pair and k-tile the summed product is weighted by the run's
power-of-two shift and folded into a
compensated float32 accumulator held in the revisited output tiles
(TwoSum, the reference path's own step, so the ~48-bit "df32" accuracy
survives the single-f32 output constraint of FP64-free hardware).  The kernel emits separate hi/lo
f32 outputs; the wrapper combines them in the requested output dtype.

**v2 (default,** :func:`split_gemm_pallas` **)** never materializes
slice pairs: the slices stay as one ``(s, m, k)`` / ``(s, k, n)``
array and the pair ``(i, j)`` for each grid step is looked up from a
scalar-prefetch pair schedule (``pltpu.PrefetchScalarGridSpec``) inside
the BlockSpec index maps; the pair weight is reconstructed in-kernel
from its integer exponent by exact bit manipulation.  HBM slice reads
drop from the O(s²·m·k) gathered pair copies of v1 to the O(s·m·k)
slice arrays themselves (see :mod:`repro.kernels.tile_model`, the
accounting authority).  The v1 kernel is kept as
:func:`split_gemm_pallas_v1` for A/B equivalence tests.

**Fused slicing** (:func:`split_gemm_pallas_fused`, opt-in via
``ozaki_matmul(..., fuse_slicing=True)`` or the ``pallas_int8*:fused``
backend spec) goes further: operands enter as exact f32 hi/lo halves
and are quantized to int8 tile-by-tile in VMEM with
:mod:`repro.kernels.slicing`, so slices never exist in HBM at all.

Slicing arithmetic is shared with :mod:`repro.core.ozaki` /
:mod:`repro.kernels.slicing`, so all paths are bit-for-bit comparable
in tests.

Each ``pallas_call`` carries a stable name — ``ozaki_int8_tile`` (v2),
``ozaki_int8_tile_fused`` and ``ozaki_int8_tile_v1`` — which names the
kernel's ops in a device trace.

On CPU there is no Mosaic backend: pass ``interpret=True`` (the
benchmarks do) to run the kernel through the Pallas interpreter —
correctness-only, but it exercises the exact same kernel body that
compiles for TPU.

**Tile alignment rule**: int8 operands on the TPU MXU require (32,
128) minimum tiles, so every block dimension is rounded *up* to a
valid multiple — ``block_m`` to 32, ``block_n``/``block_k`` to 128 —
after clamping to the operand's own padded extent (a block larger than
``align_up(dim)`` only adds dead padding).  Small or ragged shapes are
therefore zero-padded up to one aligned tile rather than shrinking the
block below MXU alignment (the old ``min(block_m, m)`` clamp emitted
unlowerable sub-(32, 128) tiles for small sites).  Zero padding is
exact: padded rows/columns contribute nothing to any slice product.
Block sizes default to the analytic model in
:mod:`repro.kernels.tile_model` — no autotuning sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ozaki import (SLICE_BITS, _fold_df32, fold_runs,
                              pair_indices, slice_matrix)
from repro.kernels import slicing, tile_model
from repro.kernels.tile_model import LANE, SUBLANE_INT8, align_up

__all__ = [
    "ozaki_matmul",
    "split_gemm_pallas",
    "split_gemm_pallas_fused",
    "split_gemm_pallas_v1",
]


def _pow2_f32(e, shape):
    """Exact f32 ``2.0**e``, broadcast to ``shape``, from an int32
    scalar exponent via bit assembly.

    Valid for e in [-126, 127]; the kernels only need non-negative
    shifts <= (s-1)*slice_bits.  Avoids ``exp2`` (inexact on some
    backends) and table lookups inside the kernel.  The bits are
    broadcast before the bitcast because Mosaic bitcasts vectors only,
    and ``e`` is a scalar read from SMEM.
    """
    bits = jnp.full(shape, (e + 127) << 23, jnp.int32)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _accumulate(hi_ref, lo_ref, acc_ref, part, wexp, edge):
    """Sum one INT32 tile product over a run's k-tiles and pairs, then
    fold it in.

    The shared tail of every kernel body.  ``edge`` is the pair's
    entry of the schedule's run edges (:func:`_pair_schedule_arrays`):
    bit 0 marks the first pair of a fold run, bit 1 its last.  The
    reduction over the k-tiles and the run's pairs stays in int32
    (exact) in the ``acc_ref`` scratch, reset at the run's first pair
    and k-tile; after its last pair and k-tile the run's product is
    weighted by ``2**wexp`` and folded into the hi/lo refs by
    :func:`repro.core.ozaki._fold_df32`, the step the jnp df32 path
    takes for the same run.  So the kernel equals that path bit for bit
    at any tiling, and its compensation sees one term per run rather
    than one per k-tile.
    """
    p = pl.program_id(2)
    kt = pl.program_id(3)
    first = jnp.logical_and(kt == 0, (edge & 1) == 1)

    @pl.when(first)
    def _():
        acc_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[...] += part

    @pl.when(jnp.logical_and(p == 0, kt == 0))
    def _():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    @pl.when(jnp.logical_and(kt == pl.num_programs(3) - 1,
                             (edge & 2) == 2))
    def _():
        prod = acc_ref[...]
        hi_ref[...], lo_ref[...] = _fold_df32(
            hi_ref[...], lo_ref[...], prod, _pow2_f32(wexp, prod.shape))


def _split_gemm_kernel_v2(ii_ref, jj_ref, wexp_ref, edge_ref, a_ref,
                          b_ref, hi_ref, lo_ref, acc_ref):
    """Grid: (m/bm, n/bn, num_pairs, k/bk). One INT8 tile product.

    The slice pair for step ``p`` was already selected by the BlockSpec
    index maps (scalar-prefetch ``ii``/``jj``); the kernel only has to
    reconstruct the pair weight from its prefetched integer exponent.
    Output tiles are revisited across the two reduction grid dims
    (pair index, k-tile) and double as the compensated accumulator.
    """
    del ii_ref, jj_ref  # consumed by the index maps
    p = pl.program_id(2)
    part = jax.lax.dot_general(
        a_ref[0], b_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    _accumulate(hi_ref, lo_ref, acc_ref, part, wexp_ref[p], edge_ref[p])


def _split_gemm_kernel_fused(ii_ref, jj_ref, wexp_ref, edge_ref, ah_ref,
                             al_ref, bh_ref, bl_ref, hi_ref, lo_ref,
                             acc_ref, *, num_splits, slice_bits):
    """Fused variant: quantize f32-pair tiles to int8 in VMEM first."""
    p = pl.program_id(2)
    a_q = slicing.quantize_tile(ah_ref[...], al_ref[...], ii_ref[p],
                                num_splits, slice_bits)
    b_q = slicing.quantize_tile(bh_ref[...], bl_ref[...], jj_ref[p],
                                num_splits, slice_bits)
    part = jax.lax.dot_general(
        a_q, b_q,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    _accumulate(hi_ref, lo_ref, acc_ref, part, wexp_ref[p], edge_ref[p])


def _compiler_params(bm: int, bn: int, bk: int, fused: bool = False):
    """Mosaic's scoped-VMEM limit for one kernel's blocks.

    The tile model's footprint of the step (an upper bound on what
    Mosaic allocates), never below the model's budget: blocks the model
    picks itself fit the budget, and explicit larger blocks get what
    they need.  A v5e core has 128 MiB of VMEM.
    """
    need = tile_model.vmem_bytes(bm, bn, bk, fused=fused)
    return pltpu.CompilerParams(vmem_limit_bytes=max(
        need, tile_model.DEFAULT_PARAMS.vmem_budget))


def _pad_to(x, multiple, axis):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block(dim: int, requested: int, multiple: int) -> int:
    """Aligned block size: clamp to the padded extent, round up to the
    MXU multiple (the module-docstring alignment rule)."""
    return align_up(min(requested, align_up(dim, multiple)), multiple)


def _pair_schedule_arrays(num_splits: int, slice_bits: int, k: int):
    """(ii, jj, wexp, edge) int32 device arrays for the scalar-prefetch
    grid.

    ``edge`` marks the :func:`repro.core.ozaki.fold_runs` runs of a
    contraction of ``k`` (the operands' own extent, not the padded one,
    so the runs are the jnp path's): bit 0 on a run's first pair, bit 1
    on its last.
    """
    ii, jj = pair_indices(num_splits)
    smax = num_splits - 1
    wexp = (smax - (ii + jj)) * slice_bits
    edge = np.zeros(ii.shape, np.int32)
    for start, stop in fold_runs(num_splits, k, slice_bits):
        edge[start] |= 1
        edge[stop - 1] |= 2
    return tuple(jnp.asarray(x, jnp.int32) for x in (ii, jj, wexp, edge))


@functools.partial(jax.jit, static_argnames=(
    "num_splits", "slice_bits", "block_m", "block_n", "block_k",
    "interpret"))
def split_gemm_pallas(a_sl, b_sl, num_splits: int,
                      slice_bits: int = SLICE_BITS,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, interpret: bool = False):
    """Run the v2 pair-indexing kernel over pre-sliced operands.

    Args:
      a_sl: (s, m, k) int8 slices of A.
      b_sl: (s, k, n) int8 slices of B.

    Returns:
      (hi, lo) float32 arrays of shape (m, n); the emulated scaled
      product is ``(hi + lo) * 2**(-slice_bits*(num_splits+1))`` (the
      deferred shift keeps all in-kernel weights >= 1 so they stay
      exact in f32).

    This never gathers slice pairs: the scalar-prefetch
    schedule drives the BlockSpec index maps straight into the
    ``(s, ., .)`` slice arrays, so HBM holds (and the grid reads) s
    slice layers instead of s*(s+1)/2 pair copies.
    """
    _, m, k = a_sl.shape
    _, _, n = b_sl.shape
    ii, jj, wexp, edge = _pair_schedule_arrays(num_splits, slice_bits, k)
    num_pairs = ii.shape[0]

    bm = _block(m, block_m, SUBLANE_INT8)
    bn = _block(n, block_n, LANE)
    bk = _block(k, block_k, LANE)
    a_sl = _pad_to(_pad_to(a_sl, bm, 1), bk, 2)
    b_sl = _pad_to(_pad_to(b_sl, bk, 1), bn, 2)
    mp, kp = a_sl.shape[1:]
    np_ = b_sl.shape[2]
    grid = (mp // bm, np_ // bn, num_pairs, kp // bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda i, j, p, kt, ii, jj, we, ed: (ii[p], i, kt)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (jj[p], kt, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    hi, lo = pl.pallas_call(
        _split_gemm_kernel_v2,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        ],
        compiler_params=_compiler_params(bm, bn, bk),
        interpret=interpret,
        name="ozaki_int8_tile",
    )(ii, jj, wexp, edge, a_sl, b_sl)
    return hi[:m, :n], lo[:m, :n]


@functools.partial(jax.jit, static_argnames=(
    "num_splits", "slice_bits", "block_m", "block_n", "block_k",
    "interpret"))
def split_gemm_pallas_fused(a_hi, a_lo, b_hi, b_lo, num_splits: int,
                            slice_bits: int = SLICE_BITS,
                            block_m: int = 128, block_n: int = 128,
                            block_k: int = 128,
                            interpret: bool = False):
    """v2 kernel with in-VMEM slicing: operands as exact f32 pairs.

    Args:
      a_hi, a_lo: (m, k) f32 halves of the sigma-scaled A
        (``repro.kernels.slicing.to_operand_pair``).
      b_hi, b_lo: (k, n) f32 halves of the sigma-scaled B.

    Same (hi, lo) contract as :func:`split_gemm_pallas`.  Slices never
    exist in HBM: each grid step re-derives its int8 tile from the f32
    pair in VMEM (schedule/weights identical, so results match the
    pre-sliced path bit-for-bit when the slices agree — exactly, for
    f32 sources).
    """
    m, k = a_hi.shape
    _, n = b_hi.shape
    ii, jj, wexp, edge = _pair_schedule_arrays(num_splits, slice_bits, k)
    num_pairs = ii.shape[0]

    bm = _block(m, block_m, SUBLANE_INT8)
    bn = _block(n, block_n, LANE)
    bk = _block(k, block_k, LANE)
    a_hi, a_lo = (_pad_to(_pad_to(x, bm, 0), bk, 1) for x in (a_hi, a_lo))
    b_hi, b_lo = (_pad_to(_pad_to(x, bk, 0), bn, 1) for x in (b_hi, b_lo))
    mp, kp = a_hi.shape
    np_ = b_hi.shape[1]
    grid = (mp // bm, np_ // bn, num_pairs, kp // bk)

    a_spec = pl.BlockSpec((bm, bk),
                          lambda i, j, p, kt, ii, jj, we, ed: (i, kt))
    b_spec = pl.BlockSpec((bk, bn),
                          lambda i, j, p, kt, ii, jj, we, ed: (kt, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    hi, lo = pl.pallas_call(
        functools.partial(_split_gemm_kernel_fused,
                          num_splits=num_splits, slice_bits=slice_bits),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        ],
        compiler_params=_compiler_params(bm, bn, bk, fused=True),
        interpret=interpret,
        name="ozaki_int8_tile_fused",
    )(ii, jj, wexp, edge, a_hi, a_lo, b_hi, b_lo)
    return hi[:m, :n], lo[:m, :n]


@functools.partial(jax.jit, static_argnames=(
    "num_splits", "slice_bits", "block_m", "block_n", "block_k",
    "interpret"))
def split_gemm_pallas_v1(a_sl, b_sl, num_splits: int,
                         slice_bits: int = SLICE_BITS,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, interpret: bool = False):
    """Legacy v1 kernel: gathers every slice pair into HBM first.

    Kept as the A/B reference for the v2 traffic claim (see
    ``tile_model.traffic``) and for bit-identity regression tests —
    same schedule, same accumulation, so v1 == v2 exactly.  Do not use
    for new call sites: it stages s*(s+1)/2 pair copies in HBM.
    """
    _, m, k = a_sl.shape
    _, _, n = b_sl.shape
    ii, jj, wexp, edge = _pair_schedule_arrays(num_splits, slice_bits, k)
    a_pairs = jnp.take(a_sl, ii, axis=0)
    b_pairs = jnp.take(b_sl, jj, axis=0)

    bm = _block(m, block_m, SUBLANE_INT8)
    bn = _block(n, block_n, LANE)
    bk = _block(k, block_k, LANE)
    a_pairs = _pad_to(_pad_to(a_pairs, bm, 1), bk, 2)
    b_pairs = _pad_to(_pad_to(b_pairs, bk, 1), bn, 2)
    mp, kp = a_pairs.shape[1:]
    np_ = b_pairs.shape[2]
    grid = (mp // bm, np_ // bn, ii.shape[0], kp // bk)

    # The v2 body; only the index maps differ: step p reads gathered
    # pair p instead of looking its slices up in the schedule.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda i, j, p, kt, ii, jj, we, ed: (p, i, kt)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (p, kt, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
            pl.BlockSpec((bm, bn),
                         lambda i, j, p, kt, ii, jj, we, ed: (i, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    hi, lo = pl.pallas_call(
        _split_gemm_kernel_v2,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        ],
        compiler_params=_compiler_params(bm, bn, bk),
        interpret=interpret,
        name="ozaki_int8_tile_v1",
    )(ii, jj, wexp, edge, a_pairs, b_pairs)
    return hi[:m, :n], lo[:m, :n]


def ozaki_matmul(a, b, num_splits: int = 6, accumulator: str = "df32",
                 out_dtype=None, slice_bits: int = SLICE_BITS,
                 interpret: bool = False, block_m: int | None = None,
                 block_n: int | None = None, block_k: int | None = None,
                 fuse_slicing: bool = False,
                 tiles: tile_model.TileDecision | None = None):
    """Pallas-backed drop-in for :func:`repro.core.ozaki.ozaki_matmul`.

    Same signature and semantics as the jnp reference path, plus
    ``interpret`` (run through the Pallas interpreter — required on
    CPU), tile-size overrides, ``fuse_slicing`` (quantize in VMEM, no
    slices in HBM) and ``tiles`` (a precomputed
    :class:`~repro.kernels.tile_model.TileDecision`).  When neither
    explicit blocks nor ``tiles`` are given, the analytic tile model
    picks the blocks — no autotuning sweep.

    The kernel's compensated-f32 accumulation *is* the reference
    ``"df32"`` accumulator; any other value raises ``ValueError``
    rather than silently computing something else (``None`` is
    accepted as "backend default").
    """
    if accumulator not in ("df32", None):
        raise ValueError(
            f"unsupported accumulator {accumulator!r} for the Pallas "
            "kernel: it always accumulates compensated-f32 ('df32'); "
            "pass 'df32' or None, or use repro.core.ozaki_matmul for "
            "'f64'")
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("ozaki_matmul expects 2-D operands, got "
                         f"{a.shape} @ {b.shape}")
    if out_dtype is None:
        out_dtype = jnp.result_type(a.dtype, b.dtype)
    out_dtype = jnp.dtype(out_dtype)
    if jnp.issubdtype(out_dtype, jnp.complexfloating):
        raise NotImplementedError(
            "complex operands: route through repro.core.ozaki_matmul")

    m, k = a.shape
    n = b.shape[1]
    if tiles is None and None in (block_m, block_n, block_k):
        tiles = tile_model.select_tiles(m, k, n, num_splits,
                                        dtype=out_dtype,
                                        fused=fuse_slicing)
    if tiles is not None:
        block_m = tiles.block_m if block_m is None else block_m
        block_n = tiles.block_n if block_n is None else block_n
        block_k = tiles.block_k if block_k is None else block_k

    if fuse_slicing:
        a_hi, a_lo, sigma_a = slicing.to_operand_pair(a, axis=1)
        b_hi, b_lo, sigma_b = slicing.to_operand_pair(b, axis=0)
        hi, lo = split_gemm_pallas_fused(
            a_hi, a_lo, b_hi, b_lo, num_splits, slice_bits=slice_bits,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret)
    else:
        a_sl, sigma_a = slice_matrix(a, num_splits, axis=1,
                                     slice_bits=slice_bits)
        b_sl, sigma_b = slice_matrix(b, num_splits, axis=0,
                                     slice_bits=slice_bits)
        hi, lo = split_gemm_pallas(a_sl, b_sl, num_splits,
                                   slice_bits=slice_bits,
                                   block_m=block_m, block_n=block_n,
                                   block_k=block_k, interpret=interpret)
    deferred = 2.0 ** (-slice_bits * (num_splits + 1))
    c = (hi.astype(out_dtype) + lo.astype(out_dtype)) * deferred
    scale = (sigma_a[:, None] * sigma_b[None, :]).astype(out_dtype)
    return c * scale
