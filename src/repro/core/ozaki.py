"""Ozaki-scheme INT8 split-GEMM emulation of high-precision matmuls.

The Ozaki scheme writes a floating-point matrix as an exact sum of
narrow integer "slices"

    A / sigma_A  =  sum_t  S_t * 2**(-w*(t+1)),      S_t in int8,

where ``sigma_A`` is a per-row power-of-two scale and ``w`` is the slice
width in bits.  Products of slices are then exact in INT8xINT8->INT32
arithmetic (the datatype tensor cores / the TPU MXU natively consume),
and the high-precision product is recovered by accumulating the pair
products ``S_i(A) @ S_j(B)`` with the appropriate power-of-two weights.

With ``s`` slices per operand we follow the standard truncated scheme
and keep only the pairs with ``i + j < s`` — ``s*(s+1)/2`` pair
products of MXU work — so the split count tunes accuracy continuously:
each extra split buys roughly ``w`` more mantissa bits.

Pairs with the same shift ``i + j`` carry the same power-of-two weight,
so their INT32 products are summed exactly in int32 before any float
work (:func:`fold_runs` caps a run where int32 could overflow).  A run
``(i, t-i), i = i0..i1-1`` is one INT8 GEMM over a contraction of
``(i1-i0)*k``: ``[A_i0 | ... | A_(i1-1)] @ [B_(t-i0); ...; B_(t-i1+1)]``,
a slab of A's slices laid side by side along K against a slab of B's
stacked in reverse along K.  At practical ``k`` a GEMM therefore
issues ``s`` INT8 dots (contractions ``k, 2k, ..., s*k``) and ``s``
accumulator folds.

Two accumulators are provided:

* ``"f64"``   — accumulate the scaled INT32 pair products in float64
  (what ozIMMU does on CUDA hardware with FP64 units);
* ``"df32"``  — "double-float32": every INT32 pair product is split
  exactly into a hi/lo pair of float32 values and the weighted sum is
  carried with compensated (TwoSum) float32 arithmetic, giving ~48
  effective mantissa bits without touching an FP64 unit.  This is the
  accumulator of interest for FP64-free accelerators (TPU v5e).

Complex inputs are handled by four real split-GEMMs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SLICE_BITS",
    "complex_matmul_via_real",
    "fold_runs",
    "num_pair_gemms",
    "ozaki_ragged_dot",
    "ragged_form",
    "real_pair_matmul",
    "pair_indices",
    "slice_matrix",
    "ozaki_matmul",
]

# Bits of mantissa carried per int8 slice.  Slice values live in
# [-2**(SLICE_BITS-1), 2**(SLICE_BITS-1)] so an int8 comfortably holds
# them, and |q_a*q_b| <= 2**(2w-2): a run of r pair products summed
# over a contraction of k stays exact in int32 while
# r*k < 2**(33-2w) (2**21 pair-elements at w=6; see fold_runs, which
# shortens runs to keep it).  Six bits per slice keeps
# the s=3..9 accuracy ladder strictly monotone before hitting the f64
# reference floor, mirroring the paper's Table 1 trend.
SLICE_BITS = 6


def num_pair_gemms(num_splits: int) -> int:
    """Number of INT8 slice-pair products (MXU work, in units of one
    ``m*k*n`` GEMM) for a given split count.  The jnp path issues them
    as :func:`fold_runs` groups them, not one dot each."""
    return num_splits * (num_splits + 1) // 2


def pair_indices(num_splits: int) -> tuple[np.ndarray, np.ndarray]:
    """Slice-index pairs (i, j) with i + j < num_splits, by ascending i+j.

    Ordering by total shift means the compensated accumulation adds
    terms from largest to smallest magnitude.
    """
    pairs = [(i, j) for i in range(num_splits) for j in range(num_splits)
             if i + j < num_splits]
    pairs.sort(key=lambda ij: (ij[0] + ij[1], ij[0]))
    ii = np.array([p[0] for p in pairs], dtype=np.int32)
    jj = np.array([p[1] for p in pairs], dtype=np.int32)
    return ii, jj


def fold_runs(num_splits: int, k: int,
              slice_bits: int = SLICE_BITS) -> tuple[tuple[int, int], ...]:
    """Runs of :func:`pair_indices` whose INT32 products are summed in
    int32 and folded into the accumulator once.

    Returns ``(start, stop)`` ranges into the pair order: consecutive,
    each of one shift ``i + j``, their union all pairs in order.  Every
    term of a pair product is at most ``2**(2w-2)`` in magnitude
    (slices lie in ``[-2**(w-1), 2**(w-1)]``), so a run of ``r`` pairs
    over a contraction of ``k`` sums exactly in int32 while

        r * k * 2**(2*slice_bits - 2) < 2**31,

    and each run is capped at the largest such ``r``: at ``w = 6``,
    ``r * k < 2**21``.  Where the cap is 1 (``k >= 2**20`` at
    ``w = 6``) every pair is its own run, one fold each; a single pair
    is exact for ``k < 2**(33-2w)``.
    """
    ii, jj = pair_indices(num_splits)
    cap = max(1, (2**31 - 1) // (max(k, 1) << (2 * slice_bits - 2)))
    shifts = ii + jj
    runs = []
    start = 0
    for p in range(1, len(ii) + 1):
        if (p == len(ii) or shifts[p] != shifts[start]
                or p - start == cap):
            runs.append((start, p))
            start = p
    return tuple(runs)


def _exact_pow2(e: jax.Array, dtype) -> jax.Array:
    """``2.0**e`` for an int32 array ``e``, exact wherever ``dtype``
    holds the power (inf above its range, as ``ldexp`` gives).

    A product of constant powers of two, one per set bit of ``|e|``:
    every partial product lies between 1 and the result, so none
    rounds.  ``jnp.exp2`` is approximate on some backends, and
    ``jnp.ldexp`` on f64 bitcasts through s64, which XLA:TPU refuses.
    """
    dtype = np.dtype(dtype)
    out = jnp.ones(e.shape, dtype)
    mag = jnp.abs(e)
    for bit in range(int(np.finfo(dtype).maxexp).bit_length()):
        with np.errstate(over="ignore"):
            up = np.array(2.0, dtype) ** (1 << bit)
        down = np.array(0.5, dtype) ** (1 << bit)
        factor = jnp.where(e < 0, down, up)
        out = jnp.where((mag >> bit) & 1 == 1, out * factor, out)
    return out


def _pow2_scale(x: jax.Array, axis: int) -> jax.Array:
    """Per-row/col power-of-two scale sigma with |x| / sigma <= 1/2."""
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    # exponent e with 2**e >= 2*absmax; zero rows get sigma = 1.
    e = jnp.where(absmax > 0, jnp.ceil(jnp.log2(absmax)) + 1.0, 0.0)
    return _exact_pow2(e.astype(jnp.int32), absmax.dtype)


def _slices(x: jax.Array, num_splits: int, axis: int, slice_bits: int):
    """The int8 slices of ``x``, most significant first, and its sigma
    (with ``axis`` kept): the recurrence :func:`slice_matrix` stacks."""
    compute_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    x = x.astype(compute_dtype)
    sigma = _pow2_scale(x, axis=axis)
    r = x / sigma  # |r| <= 0.5, scaling by a power of two is exact
    radix = float(2 ** slice_bits)
    out = []
    for _ in range(num_splits):
        q = jnp.round(r * radix)  # |q| <= 2**(slice_bits-1) after step 1
        out.append(q.astype(jnp.int8))
        r = r * radix - q  # exact: both operands share an exponent window
    return out, sigma


def slice_matrix(x: jax.Array, num_splits: int, axis: int,
                 slice_bits: int = SLICE_BITS):
    """Split ``x`` into int8 slices along its value (mantissa) axis.

    Returns ``(slices, sigma)`` with ``slices`` of shape
    ``(num_splits, *x.shape)`` (int8) and ``sigma`` the per-row (axis=1)
    or per-column (axis=0) power-of-two scale, such that

        x ~= sigma * sum_t slices[t] * 2**(-slice_bits*(t+1)).

    The remainder after ``num_splits`` slices is < 2**(-w*s - 1) per
    element (relative to sigma): the splitting itself is exact in f64
    arithmetic, only the truncation to ``num_splits`` slices loses bits.
    """
    out, sigma = _slices(x, num_splits, axis, slice_bits)
    return jnp.stack(out), jnp.squeeze(sigma, axis=axis)


def _int8_dot(a, b):
    return jax.lax.dot_general(a, b,
                               dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _run_products(a_k, b_k, k, num_splits, runs, dot=_int8_dot,
                  a_axis=1, b_axis=0):
    """One INT8 GEMM per fold run -> ``[(shift, int32 product)]``.

    ``a_k`` is ``[A_0 | ... | A_(s-1)]`` (m, s*k) and ``b_k`` is
    ``[B_(s-1); ...; B_0]`` (s*k, n).  The run of pairs ``(i, t-i)``,
    ``i0 <= i < i1``, reads A's columns ``[i0*k, i1*k)`` and the rows of
    ``b_k`` holding ``B_(t-i0)`` down to ``B_(t-i1+1)``, which lie
    side by side in that order: both operands are contiguous slabs.
    ``dot(a_slab, b_slab)`` multiplies one run's slabs, cut from axes
    ``a_axis`` and ``b_axis`` (the grouped products lay the slices out
    on other axes: :func:`ozaki_ragged_dot`).

    Each product is written out whole (an optimization barrier): left
    free to fuse the folds into the dots' output fusions, XLA:TPU took
    8x as long to compile the SmolLM-360M train step for a v5e.
    """
    ii, jj = pair_indices(num_splits)
    shifts, prods = [], []
    for start, stop in runs:
        shift = int(ii[start] + jj[start])
        lo, hi = int(ii[start]) * k, (int(ii[stop - 1]) + 1) * k
        off = (num_splits - 1 - shift) * k
        shifts.append(shift)
        prods.append(dot(
            jax.lax.slice_in_dim(a_k, lo, hi, axis=a_axis),
            jax.lax.slice_in_dim(b_k, off + lo, off + hi, axis=b_axis)))
    return list(zip(shifts, jax.lax.optimization_barrier(prods)))


def _accumulate_f64(prods, slice_bits):
    """Weighted float64 sum of the runs' INT32 products."""
    c = None
    for shift, prod in prods:  # runs ordered large -> small
        # Exact host-side power of two (jnp.exp2 is NOT exact for
        # integer args on XLA CPU).
        term = prod.astype(jnp.float64) * np.ldexp(1.0, -(shift + 2)
                                                   * slice_bits)
        c = term if c is None else c + term
    return c


def _two_sum(acc, term):
    """Knuth TwoSum: acc + term = s + err exactly (any float dtype)."""
    s = acc + term
    bp = s - acc
    err = (acc - (s - bp)) + (term - bp)
    return s, err


def _fold_df32(acc, comp, prod, w):
    """Fold one INT32 run product, weighted by ``w``, into (acc, comp).

    ``prod`` is split exactly into hi/lo float32 parts (hi is integral,
    and under :func:`fold_runs`' cap |prod| <= 2**31 - 2**(2w-2), which
    f32 rounds to no more than itself, so the cast back to int32 is
    exact — and unlike int64 it does not warn when jax_enable_x64 is
    off).  ``w`` is a non-negative power of two, so the weighting is
    exact in f32.  The one step shared by the jnp path and the Pallas
    kernels, which keeps them bit-identical.
    """
    hi = prod.astype(jnp.float32)
    lo = (prod - hi.astype(prod.dtype)).astype(jnp.float32)
    acc, err = _two_sum(acc, hi * w)
    comp = comp + err
    acc, err = _two_sum(acc, lo * w)
    return acc, comp + err


def _accumulate_df32(prods, slice_bits, num_splits):
    """Compensated double-float32 accumulation.

    Each run's INT32 product is folded into a compensated (sum, err)
    float32 pair by :func:`_fold_df32`, with a *non-negative*
    power-of-two weight (exact in f32, never underflows).  The caller
    divides by the deferred scale 2**(w*(s+1)) at combine time.
    """
    smax = num_splits - 1
    shape = prods[0][1].shape
    acc = jnp.zeros(shape, jnp.float32)
    comp = jnp.zeros(shape, jnp.float32)
    for shift, prod in prods:  # runs ordered large -> small
        # Shift t gets weight 2**(w*(smax - t)), an exact host-side
        # power of two (jnp.exp2 is approximate on CPU).
        w = np.ldexp(np.float32(1.0), (smax - shift) * slice_bits)
        acc, comp = _fold_df32(acc, comp, prod, jnp.float32(w))
    deferred = 2.0 ** (-slice_bits * (smax + 2))
    return acc, comp, deferred


def _check_accumulator(accumulator):
    if accumulator not in ("df32", "f64"):
        raise ValueError(f"unknown accumulator {accumulator!r};"
                         " expected 'df32' or 'f64'")


def _fold(prods, num_splits, accumulator, out_dtype, slice_bits):
    """The runs' INT32 products folded into ``out_dtype``, unscaled."""
    if accumulator == "f64":
        return _accumulate_f64(prods, slice_bits).astype(out_dtype)
    acc, comp, deferred = _accumulate_df32(prods, slice_bits, num_splits)
    return (acc.astype(out_dtype) + comp.astype(out_dtype)) * deferred


@functools.partial(jax.jit, static_argnames=("num_splits", "accumulator",
                                             "out_dtype", "slice_bits"))
def _real_ozaki(a, b, num_splits, accumulator, out_dtype, slice_bits):
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    _check_accumulator(accumulator)
    with jax.named_scope("phase_slice"):
        a_sl, sigma_a = _slices(a, num_splits, 1, slice_bits)
        b_sl, sigma_b = _slices(b, num_splits, 0, slice_bits)
        a_k = jnp.concatenate(a_sl, axis=1)        # (m, s*k)
        b_k = jnp.concatenate(b_sl[::-1], axis=0)  # (s*k, n), reversed
        # Written out once for every run's dot to read: left fusible,
        # XLA:TPU re-derives the slabs inside each dot's fusion, which
        # ran the SmolLM-360M train step 5% slower on a v5e.
        a_k, b_k = jax.lax.optimization_barrier((a_k, b_k))
    with jax.named_scope("phase_pairs"):
        prods = _run_products(a_k, b_k, k, num_splits,
                              fold_runs(num_splits, k, slice_bits))
    with jax.named_scope("phase_fold"):
        c = _fold(prods, num_splits, accumulator, out_dtype, slice_bits)
        scale = (sigma_a * sigma_b).astype(out_dtype)
        return c * scale


#: ``ragged_dot_general`` dimension numbers of the two grouped forms
#: :func:`ozaki_ragged_dot` computes, both as ``jax.grad`` of
#: ``jax.lax.ragged_dot`` emits them: rows ragged against a stack of
#: group matrices, ``(m, k) x (g, k, n) -> (m, n)`` (the forward and
#: dX), and a ragged contraction, ``(m, k) x (m, n) -> (g, k, n)`` (dW).
RAGGED_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (1,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=(0,))
RAGGED_CONTRACTION = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def ragged_form(dims) -> str | None:
    """``"rows"`` or ``"contraction"`` for the two forms above, else None."""
    for name, form in (("rows", RAGGED_ROWS),
                       ("contraction", RAGGED_CONTRACTION)):
        if (dims.dot_dimension_numbers == form.dot_dimension_numbers
                and tuple(dims.lhs_ragged_dimensions)
                == form.lhs_ragged_dimensions
                and tuple(dims.rhs_group_dimensions)
                == form.rhs_group_dimensions):
            return name
    return None


#: The int8 grouped products' precision, stated so that an enclosing
#: ``jax.default_matmul_precision("highest")`` does not reach them:
#: Mosaic refuses XLA:TPU's grouped-product kernel on int8 operands at
#: float32 contract precision.
_INT8 = jax.lax.Precision.DEFAULT


@functools.partial(jax.jit, static_argnames=("form", "num_splits",
                                             "accumulator", "out_dtype",
                                             "slice_bits"))
def _ragged_ozaki(a, b, group_sizes, form, num_splits, accumulator,
                  out_dtype, slice_bits):
    _check_accumulator(accumulator)
    s = num_splits
    if form == "rows":
        # a (m, k) per row; b (g, k, n) per group and column.  Runs
        # concatenate slices along k, as the dense product does.
        k = a.shape[1]
        with jax.named_scope("phase_slice"):
            a_sl, sigma_a = _slices(a, s, 1, slice_bits)
            b_sl, sigma_b = _slices(b, s, 1, slice_bits)
            a_k = jnp.concatenate(a_sl, axis=1)        # (m, s*k)
            b_k = jnp.concatenate(b_sl[::-1], axis=1)  # (g, s*k, n)
            a_k, b_k = jax.lax.optimization_barrier((a_k, b_k))

        def dot(x, y):
            return jax.lax.ragged_dot_general(
                x, y, group_sizes, RAGGED_ROWS, precision=_INT8,
                preferred_element_type=jnp.int32)

        with jax.named_scope("phase_pairs"):
            prods = _run_products(a_k, b_k, k, s,
                                  fold_runs(s, k, slice_bits), dot,
                                  a_axis=1, b_axis=1)
        with jax.named_scope("phase_fold"):
            # Row i takes its group's column scales.  XLA:TPU leaves the
            # rows past the groups unwritten: they are zeroed here.
            g, m = b.shape[0], a.shape[0]
            c = _fold(prods, s, accumulator, out_dtype, slice_bits)
            group = jnp.repeat(jnp.arange(g), group_sizes,
                               total_repeat_length=m)
            scale = (sigma_a * sigma_b[:, 0, :][group]).astype(out_dtype)
            grouped = jnp.arange(m) < jnp.sum(group_sizes)
            return jnp.where(grouped[:, None], c * scale, 0)
    # Ragged contraction: a (m, k) and b (m, n), each scaled along its
    # free axis over all m rows (across groups: still powers of two).
    # Slices interleave row by row, (m, s, ...), so that a run's slabs
    # flatten to (m*r, ...) with each group's rows still contiguous.
    m = a.shape[0]
    with jax.named_scope("phase_slice"):
        a_sl, sigma_a = _slices(a, s, 0, slice_bits)
        b_sl, sigma_b = _slices(b, s, 0, slice_bits)
        a_k = jnp.stack(a_sl, axis=1)        # (m, s, k)
        b_k = jnp.stack(b_sl[::-1], axis=1)  # (m, s, n)
        a_k, b_k = jax.lax.optimization_barrier((a_k, b_k))

    def dot(x, y):
        r = x.shape[1]
        return jax.lax.ragged_dot_general(
            x.reshape(m * r, -1), y.reshape(m * r, -1),
            group_sizes * r, RAGGED_CONTRACTION, precision=_INT8,
            preferred_element_type=jnp.int32)

    with jax.named_scope("phase_pairs"):
        prods = _run_products(a_k, b_k, 1, s, fold_runs(s, m, slice_bits),
                              dot, a_axis=1, b_axis=1)
    with jax.named_scope("phase_fold"):
        c = _fold(prods, s, accumulator, out_dtype, slice_bits)
        scale = sigma_a[0][None, :, None] * sigma_b[0][None, None, :]
        return c * scale.astype(out_dtype)


def ozaki_ragged_dot(lhs, rhs, group_sizes, dims, num_splits: int = 6,
                     accumulator: str = "df32", out_dtype=None,
                     slice_bits: int = SLICE_BITS):
    """Emulated high-precision grouped product via INT8 split GEMMs.

    ``jax.lax.ragged_dot_general(lhs, rhs, group_sizes, dims)`` for the
    two forms of :func:`ragged_form`: each operand is sliced once
    (:func:`slice_matrix`'s recurrence), one int8
    ``ragged_dot_general`` into int32 is issued per :func:`fold_runs`
    run, and the runs fold as the dense product's do.  Rows past
    ``sum(group_sizes)`` come out zero.
    """
    form = ragged_form(dims)
    if form is None:
        raise ValueError(f"no grouped Ozaki form for {dims}")
    if num_splits < 1:
        raise ValueError(f"num_splits must be >= 1, got {num_splits}")
    lhs, rhs = jnp.asarray(lhs), jnp.asarray(rhs)
    if out_dtype is None:
        out_dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    return _ragged_ozaki(lhs, rhs, jnp.asarray(group_sizes, jnp.int32),
                         form, num_splits, accumulator,
                         jnp.dtype(out_dtype), slice_bits)


def real_pair_matmul(real_matmul, a, b, real_out):
    """Complex product of ``(re, im)`` pairs from four real GEMMs.

    ``real_matmul(x, y, real_out)`` runs one real matmul.  Returns the
    ``(re, im)`` pair of the product.  Callers that must keep complex
    arrays off the device (XLA:TPU has no complex128 matmul) split and
    join on the host and pass real pairs straight in.
    """
    ar, ai = a
    br, bi = b
    cr = real_matmul(ar, br, real_out) - real_matmul(ai, bi, real_out)
    ci = real_matmul(ar, bi, real_out) + real_matmul(ai, br, real_out)
    return cr, ci


def complex_matmul_via_real(real_matmul, a, b, out_dtype):
    """Complex product from four real GEMMs — shared by every engine.

    The decomposition (:func:`real_pair_matmul`), the real working
    dtype (f64 for complex128, f32 otherwise) and the final cast live
    here so the jnp and Pallas paths cannot drift apart.
    """
    out_dtype = jnp.dtype(out_dtype)
    real_out = jnp.float64 if out_dtype in (jnp.complex128, jnp.float64) \
        else jnp.float32
    cr, ci = real_pair_matmul(real_matmul, (jnp.real(a), jnp.imag(a)),
                              (jnp.real(b), jnp.imag(b)), real_out)
    return jax.lax.complex(cr, ci).astype(out_dtype)


def ozaki_matmul(a, b, num_splits: int = 6, accumulator: str = "df32",
                 out_dtype=None, slice_bits: int = SLICE_BITS):
    """Emulated high-precision matmul ``a @ b`` via INT8 split GEMMs.

    Args:
      a: (m, k) real or complex floating array.
      b: (k, n) real or complex floating array.
      num_splits: slice count ``s``; does ``s*(s+1)/2`` INT8 pair
        products of MXU work, issued as one INT8 dot per
        :func:`fold_runs` run (``s`` at practical ``k``), and carries
        roughly ``slice_bits * s`` mantissa bits.
      accumulator: ``"df32"`` (compensated float32 pairs, FP64-free) or
        ``"f64"`` (plain float64 accumulation).
      out_dtype: result dtype; defaults to the common input dtype.
      slice_bits: mantissa bits per int8 slice.

    Returns:
      (m, n) array of ``out_dtype``.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("ozaki_matmul expects 2-D operands, got "
                         f"{a.shape} @ {b.shape}")
    if num_splits < 1:
        raise ValueError(f"num_splits must be >= 1, got {num_splits}")
    if out_dtype is None:
        out_dtype = jnp.result_type(a.dtype, b.dtype)
    out_dtype = jnp.dtype(out_dtype)

    if jnp.issubdtype(a.dtype, jnp.complexfloating) or \
       jnp.issubdtype(b.dtype, jnp.complexfloating) or \
       jnp.issubdtype(out_dtype, jnp.complexfloating):
        def part(x, y, real_out):
            return _real_ozaki(x, y, num_splits=num_splits,
                               accumulator=accumulator,
                               out_dtype=real_out,
                               slice_bits=slice_bits)

        return complex_matmul_via_real(part, a, b, out_dtype)

    return _real_ozaki(a, b, num_splits, accumulator, out_dtype,
                       slice_bits)
