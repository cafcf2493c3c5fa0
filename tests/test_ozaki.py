"""Accuracy ladder and arithmetic invariants of the Ozaki engine."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (num_pair_gemms, ozaki_matmul, pair_indices,
                        slice_matrix)


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
