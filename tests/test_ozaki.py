"""Accuracy ladder and arithmetic invariants of the Ozaki engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SLICE_BITS, num_pair_gemms, ozaki_matmul,
                        pair_indices, slice_matrix)
from repro.core.ozaki import _fold_df32, fold_runs


def _gauss(m, k, seed, dtype=np.float64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((m, k)).astype(dtype))


def _max_rel(c, ref, a, b):
    denom = jnp.abs(a) @ jnp.abs(b)
    return float(jnp.max(jnp.abs(c - ref) / denom))


class TestAccuracyLadder:
    @pytest.mark.parametrize("accumulator", ["df32", "f64"])
    def test_monotone_and_hits_1e12_by_s9(self, accumulator):
        a, b = _gauss(256, 256, 0), _gauss(256, 256, 1)
        ref = a @ b
        errs = []
        for s in range(3, 10):
            c = ozaki_matmul(a, b, num_splits=s, accumulator=accumulator,
                             out_dtype=jnp.float64)
            errs.append(_max_rel(c, ref, a, b))
        assert errs[-1] < 1e-12, errs
        for lo, hi in zip(errs[1:], errs[:-1]):
            assert lo < hi, f"ladder not monotone: {errs}"

    def test_more_slice_bits_more_accuracy(self):
        a, b = _gauss(128, 128, 2), _gauss(128, 128, 3)
        ref = a @ b
        e6 = _max_rel(ozaki_matmul(a, b, 4, slice_bits=6,
                                   out_dtype=jnp.float64), ref, a, b)
        e7 = _max_rel(ozaki_matmul(a, b, 4, slice_bits=7,
                                   out_dtype=jnp.float64), ref, a, b)
        assert e7 < e6

    def test_extreme_row_scales(self):
        # Per-row/col power-of-two scaling must absorb wild dynamic
        # range without overflowing the int8 slices.
        a = _gauss(64, 64, 4) * jnp.logspace(-12, 12, 64)[:, None]
        b = _gauss(64, 64, 5) * jnp.logspace(8, -8, 64)[None, :]
        ref = a @ b
        c = ozaki_matmul(a, b, num_splits=9, accumulator="f64",
                         out_dtype=jnp.float64)
        assert _max_rel(c, ref, a, b) < 1e-12


class TestSlicing:
    def test_reconstruction_is_exact_up_to_truncation(self):
        x = _gauss(32, 48, 6)
        s, w = 5, 6
        slices, sigma = slice_matrix(x, s, axis=1, slice_bits=w)
        assert slices.shape == (s, 32, 48)
        assert slices.dtype == jnp.int8
        recon = sum(
            slices[t].astype(jnp.float64) * 2.0 ** (-w * (t + 1))
            for t in range(s))
        resid = jnp.abs(x / sigma[:, None] - recon)
        assert float(jnp.max(resid)) <= 2.0 ** (-w * s - 1)

    def test_sigma_is_power_of_two(self):
        x = _gauss(16, 16, 7) * 3.7e-5
        _, sigma = slice_matrix(x, 3, axis=1)
        frac, _ = np.frexp(np.asarray(sigma))
        assert np.all(frac == 0.5)  # exact powers of two

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_exact_pow2_matches_ldexp_over_whole_range(self, dtype):
        # sigma is built without ldexp (whose f64 lowering XLA:TPU
        # refuses); it must still be bitwise the device's ldexp for
        # every exponent the dtype can carry, subnormal results and
        # overflow included.
        from repro.core.ozaki import _exact_pow2

        info = np.finfo(dtype)
        lo = info.minexp - info.nmant
        e = jnp.arange(lo, info.maxexp + 2, dtype=jnp.int32)
        want = np.asarray(jnp.ldexp(jnp.ones(e.shape, dtype), e))
        got = np.asarray(_exact_pow2(e, dtype))
        np.testing.assert_array_equal(got.view(np.uint8),
                                      want.view(np.uint8))

    def test_sigma_spans_f64_exponents(self):
        # Row scales far outside f32's exponent range stay exact.
        x = np.array([[3.0e-300, 1.0], [7.0e250, -1.0]])
        x = x * np.array([[1.0, 0.0], [1.0, 0.0]])
        _, sigma = slice_matrix(x, 3, axis=1)
        absmax = np.abs(x).max(axis=1)
        frac, _ = np.frexp(np.asarray(sigma))
        assert np.all(frac == 0.5)
        assert np.all((absmax <= np.asarray(sigma) / 2)
                      & (absmax > np.asarray(sigma) / 4))

    def test_pair_count(self):
        for s in range(1, 10):
            ii, jj = pair_indices(s)
            assert len(ii) == num_pair_gemms(s) == s * (s + 1) // 2
            assert np.all(ii + jj < s)


class TestDtypesAndShapes:
    def test_f32_inputs_default_out(self):
        a, b = _gauss(96, 64, 8, np.float32), _gauss(64, 80, 9, np.float32)
        c = ozaki_matmul(a, b, num_splits=6)
        assert c.dtype == jnp.float32
        assert c.shape == (96, 80)
        ref = a.astype(jnp.float64) @ b.astype(jnp.float64)
        assert _max_rel(c.astype(jnp.float64), ref, a, b) < 1e-6

    def test_complex128(self):
        rng = np.random.default_rng(10)
        a = jnp.asarray(rng.standard_normal((64, 64))
                        + 1j * rng.standard_normal((64, 64)))
        b = jnp.asarray(rng.standard_normal((64, 64))
                        + 1j * rng.standard_normal((64, 64)))
        c = ozaki_matmul(a, b, num_splits=9, accumulator="f64")
        assert c.dtype == jnp.complex128
        ref = a @ b
        rel = float(jnp.max(jnp.abs(c - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 1e-12

    def test_rejects_bad_rank_and_splits(self):
        a = _gauss(8, 8, 11)
        with pytest.raises(ValueError):
            ozaki_matmul(a.reshape(2, 4, 8), a)
        with pytest.raises(ValueError):
            ozaki_matmul(a, a, num_splits=0)
        with pytest.raises(ValueError):
            ozaki_matmul(a, a, accumulator="f16")


def _per_pair_oracle(a, b, num_splits, slice_bits=SLICE_BITS):
    """The engine as it was before fold runs: every slice pair gathered,
    multiplied and folded into the df32 accumulator on its own."""
    a_sl, sigma_a = slice_matrix(a, num_splits, axis=1,
                                 slice_bits=slice_bits)
    b_sl, sigma_b = slice_matrix(b, num_splits, axis=0,
                                 slice_bits=slice_bits)
    ii, jj = pair_indices(num_splits)
    prod = jax.lax.dot_general(
        jnp.take(a_sl, jnp.asarray(ii), axis=0),
        jnp.take(b_sl, jnp.asarray(jj), axis=0),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)
    smax = num_splits - 1
    w = np.ldexp(np.float32(1.0), (smax - (ii + jj)) * slice_bits)
    acc = jnp.zeros(prod.shape[1:], jnp.float32)
    comp = jnp.zeros(prod.shape[1:], jnp.float32)
    for p in range(prod.shape[0]):
        acc, comp = _fold_df32(acc, comp, prod[p], jnp.float32(w[p]))
    out = jnp.result_type(a.dtype, b.dtype)
    c = ((acc.astype(out) + comp.astype(out))
         * 2.0 ** (-slice_bits * (smax + 2)))
    return c * (sigma_a[:, None] * sigma_b[None, :]).astype(out)


class TestFoldRuns:
    @pytest.mark.parametrize("num_splits", [1, 2, 4, 6, 9])
    @pytest.mark.parametrize("k", [1, 960, 49152, 2**19 + 2**18, 2**20,
                                   2**22])
    def test_runs_are_one_shift_and_exact_in_int32(self, num_splits, k):
        runs = fold_runs(num_splits, k)
        ii, jj = pair_indices(num_splits)
        shifts = ii + jj
        # Consecutive ranges whose union is the pair order.
        assert runs[0][0] == 0 and runs[-1][1] == len(ii)
        for (_, stop), (start, _) in zip(runs[:-1], runs[1:]):
            assert stop == start
        for start, stop in runs:
            assert stop > start
            assert len(set(shifts[start:stop].tolist())) == 1
            # Every term is at most 2**(2w-2): the run's sum fits int32
            # (a single pair is the floor, whatever k).
            r = stop - start
            assert r == 1 or r * k * 2**(2 * SLICE_BITS - 2) < 2**31

    def test_one_run_per_shift_at_the_cell_widths(self):
        # Every product of the SmolLM-360M cell: s*k <= 4 * 49152.
        for k in (960, 2560, 2048, 49152):
            assert len(fold_runs(4, k)) == 4
        assert [stop - start for start, stop in fold_runs(4, 960)] == \
            [1, 2, 3, 4]

    @pytest.mark.parametrize("k", [2**20, 2**21, 2**24])
    def test_one_pair_per_run_at_huge_k(self, k):
        assert fold_runs(4, k) == tuple((p, p + 1) for p in range(10))

    def test_cap_shortens_runs_where_int32_would_overflow(self):
        # 2**31 / (k * 2**10) = 2.67 at k = 786432: runs of two.
        assert [stop - start for start, stop in
                fold_runs(4, 2**19 + 2**18)] == [1, 2, 2, 1, 2, 2]


class TestRunGrouping:
    """The run-wise engine against the per-pair oracle."""

    @pytest.mark.parametrize("m,k,n", [(64, 96, 80), (128, 128, 128),
                                       (37, 130, 51), (1, 257, 3)])
    @pytest.mark.parametrize("num_splits", [4, 6])
    def test_bitwise_equal_to_per_pair_oracle(self, m, k, n, num_splits):
        rng = np.random.default_rng(m * k + n)
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        got = ozaki_matmul(a, b, num_splits=num_splits)
        want = _per_pair_oracle(a, b, num_splits)
        assert got.dtype == want.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_s9_never_less_accurate_than_per_pair(self, seed):
        # Summing a run in int32 is exact and each fold rounds, so fewer
        # folds never lose accuracy against an f64 reference.
        a, b = _gauss(37, 130, 20 + seed), _gauss(130, 51, 40 + seed)
        ref = a @ b
        grouped = _max_rel(ozaki_matmul(a, b, 9, out_dtype=jnp.float64),
                           ref, a, b)
        per_pair = _max_rel(_per_pair_oracle(a, b, 9), ref, a, b)
        assert grouped <= per_pair

    @pytest.mark.parametrize("accumulator", ["f64", "df32"])
    def test_exact_past_the_cap(self, accumulator):
        # Slices of 31 in all four places: a run of four shift-3 pairs
        # would sum to 4 * 961 * k > 2**31 and wrap; fold_runs cuts the
        # runs at two, so the scheme's own sum comes out exactly.
        s, k = 4, 2**19 + 2**18
        assert 4 * 31 * 31 * k >= 2**31 > 2 * 31 * 31 * k
        x = sum(31 * 64.0 ** -(t + 1) for t in range(s))
        a = jnp.full((2, k), x)
        b = jnp.full((k, 3), -x)
        a_sl, _ = slice_matrix(a, s, axis=1)
        assert np.all(np.asarray(a_sl) == 31)
        # The truncated scheme in exact arithmetic: pairs i + j < s,
        # each -961 * k * 2**(-6*(i+j+2)); every partial sum is a
        # multiple of 2**-30 below 2**19, so f64 holds it exactly.
        want = sum(-961 * k * 2.0 ** (-6 * (i + j + 2))
                   for i, j in zip(*pair_indices(s)))
        c = np.asarray(ozaki_matmul(a, b, s, accumulator=accumulator,
                                    out_dtype=jnp.float64))
        if accumulator == "f64":
            assert np.all(c == want)
        else:
            assert np.max(np.abs(c - want)) <= abs(want) * 2.0 ** -44

    def test_stages_one_int8_dot_per_run_and_no_gather(self):
        from repro.core.ozaki import _real_ozaki

        a = jnp.zeros((64, 96), jnp.float32)
        b = jnp.zeros((96, 80), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda x, y: _real_ozaki(
            x, y, num_splits=4, accumulator="df32",
            out_dtype=jnp.float32, slice_bits=SLICE_BITS))(a, b)
        prims = _primitives(jaxpr.jaxpr)
        dots = [e for e in prims if e.primitive.name == "dot_general"]
        assert len(dots) == 4
        assert all(v.aval.dtype == jnp.int8 for e in dots
                   for v in e.invars)
        # Contractions k, 2k, 3k, 4k: one dot per shift.
        assert sorted(e.invars[0].aval.shape[1] for e in dots) == \
            [96, 192, 288, 384]
        assert not any(e.primitive.name == "gather" for e in prims)
        # The slabs are written out before the dots read them, and the
        # products before the folds do (fused folds multiply XLA:TPU's
        # compile time).
        barriers = [[v.aval.dtype for v in e.invars] for e in prims
                    if e.primitive.name == "optimization_barrier"]
        assert barriers == [[jnp.int8] * 2, [jnp.int32] * 4]


def _primitives(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                out.extend(_primitives(getattr(sub, "jaxpr", sub)))
    return out
