"""MuST Green's-function contour study: self-consistency + Table-1 trend."""

import numpy as np
import pytest

from repro.apps import must as MU


@pytest.fixture(scope="module")
def small():
    cfg = MU.MustConfig(n=64, block=16, n_energies=5)
    return cfg, MU.build_system(cfg)


class TestDgemmSelfConsistency:
    def test_blocked_inverse_matches_lapack(self, small):
        cfg, system = small
        z = cfg.fermi + 0.2 + 1j * cfg.eta
        m = z * np.eye(cfg.n) - system["H"]
        g_blk = MU._blocked_inverse(m, cfg.block, MU._make_gemm("dgemm"))
        g_dir = np.linalg.inv(m)
        rel = np.max(np.abs(g_blk - g_dir)) / np.max(np.abs(g_dir))
        assert rel < 1e-12

    def test_run_contour_deterministic(self, small):
        cfg, system = small
        r1 = MU.run_contour(cfg, "dgemm", system)
        r2 = MU.run_contour(cfg, "dgemm", system)
        assert r1["etot"] == r2["etot"]
        assert r1["ne"] == r2["ne"]
        np.testing.assert_array_equal(r1["g_diag"], r2["g_diag"])

    def test_reference_against_itself_is_zero(self, small):
        cfg, system = small
        ref = MU.run_contour(cfg, "dgemm", system)
        err = MU.relative_errors(ref, ref)
        assert err["max_real"] == 0.0
        assert err["max_imag"] == 0.0
        assert err["d_etot"] == 0.0

    def test_observables_sane(self, small):
        # -1/pi Im Tr G integrates the spectral weight: with the whole
        # spectrum under the contour window the electron-count analogue
        # must be positive and O(n).
        cfg, system = small
        ref = MU.run_contour(cfg, "dgemm", system)
        assert ref["ne"] > 0
        assert ref["etot"] != 0


class TestEmulatedContour:
    def test_error_decreases_with_splits(self, small):
        cfg, system = small
        ref = MU.run_contour(cfg, "dgemm", system)
        errs = []
        for s in (3, 5, 7):
            test = MU.run_contour(cfg, f"fp64_int8_{s}", system)
            e = MU.relative_errors(ref, test)
            errs.append(e["max_real"])
            assert e["per_z_real"].shape == (cfg.n_energies,)
        assert errs[0] > errs[1] > errs[2]
        assert errs[2] < 1e-8

    def test_observables_converge(self, small):
        cfg, system = small
        ref = MU.run_contour(cfg, "dgemm", system)
        e3 = MU.relative_errors(
            ref, MU.run_contour(cfg, "fp64_int8_3", system))
        e7 = MU.relative_errors(
            ref, MU.run_contour(cfg, "fp64_int8_7", system))
        assert e7["d_etot"] < e3["d_etot"]
        assert e7["d_ne"] < e3["d_ne"]

    def test_unknown_mode_rejected(self, small):
        cfg, system = small
        with pytest.raises(ValueError):
            MU.run_contour(cfg, "fp32", system)

    def test_any_registry_spec_is_a_mode(self, small):
        # The mode string is now a backend spec: adaptive per-site
        # tuning drives the same contour without further plumbing.
        cfg, system = small
        ref = MU.run_contour(cfg, "dgemm", system)
        ada = MU.run_contour(cfg, "adaptive:1e-8", system)
        err = MU.relative_errors(ref, ada)
        assert err["max_real"] < 1e-5  # pole amplification over 1e-8


class TestConfig:
    def test_block_must_divide_n(self):
        with pytest.raises(ValueError):
            MU.MustConfig(n=100, block=48)

    def test_system_spectrum_clusters_at_fermi(self):
        cfg = MU.MustConfig(n=128, block=32)
        system = MU.build_system(cfg)
        evals = system["evals"]
        h = system["H"]
        assert np.max(np.abs(h - h.conj().T)) == 0.0
        near = np.sum(np.abs(evals - cfg.fermi) < 3 * cfg.cluster_width)
        assert near >= cfg.cluster_frac * cfg.n * 0.5


class TestDeviceDtypes:
    def test_block_gemm_sends_only_real_f64_to_the_backend(self):
        # XLA:TPU has no complex128 matmul: complex blocks are split
        # into (re, im) pairs on the host, and only real f64 arrays
        # reach a device program.
        from repro.core import GemmBackend, register_backend
        from repro.core import backends as B

        seen = []

        class Recording(GemmBackend):
            def matmul(self, a, b, *, out_dtype=None, num_splits=None,
                       site="default"):
                seen.append((a.dtype, b.dtype, out_dtype))
                return a @ b

        register_backend("recording", lambda spec, policy, splits, arg:
                         Recording(spec, policy))
        try:
            gemm = MU._make_gemm("recording")
            rng = np.random.default_rng(3)
            a = rng.standard_normal((8, 8)) + 1j * rng.standard_normal((8, 8))
            b = rng.standard_normal((8, 8)) + 1j * rng.standard_normal((8, 8))
            np.testing.assert_allclose(gemm(a, b), a @ b, rtol=1e-13)
        finally:
            B._FACTORIES.pop("recording", None)
        assert len(seen) == 4
        assert all(np.dtype(x) == np.float64 for row in seen for x in row)
