"""Backend registry: spec grammar, round-trips, policy binding."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PrecisionPolicy, example_specs, get_backend,
                        register_backend, registered_families)
from repro.core.backends import (AdaptiveBackend, DgemmBackend,
                                 GemmBackend, OzakiBackend,
                                 PallasBackend)


def _gauss(n, seed, dtype=None):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, n)))
    return x.astype(dtype) if dtype else x


class TestRegistry:
    def test_round_trip_every_example_spec(self):
        # The registry's contract: every advertised spec resolves, and
        # the backend remembers the spec it came from.
        for spec in example_specs():
            backend = get_backend(spec)
            assert isinstance(backend, GemmBackend), spec
            assert backend.spec == spec

    def test_families_registered(self):
        fams = registered_families()
        for fam in ("dgemm", "fp64_int8", "pallas_int8", "adaptive"):
            assert fam in fams

    def test_spec_parsing(self):
        assert isinstance(get_backend("dgemm"), DgemmBackend)
        oz = get_backend("fp64_int8_9")
        assert isinstance(oz, OzakiBackend)
        assert oz.pinned_splits == 9
        assert get_backend("fp64_int8").pinned_splits is None
        assert isinstance(get_backend("pallas_int8_4"), PallasBackend)
        ad = get_backend("adaptive:1e-6")
        assert isinstance(ad, AdaptiveBackend)
        assert ad.target_rel == 1e-6

    def test_unknown_and_malformed_specs_rejected(self):
        for bad in ("fp32", "", "dgemm_6", "adaptive_3", "fp64_int8:x"):
            with pytest.raises(ValueError):
                get_backend(bad)

    def test_custom_family_registration(self):
        calls = []

        class Doubling(GemmBackend):
            def matmul(self, a, b, *, out_dtype=None, num_splits=None,
                       site="default"):
                calls.append(site)
                return 2.0 * (a @ b)

        register_backend("doubling",
                         lambda spec, policy, splits, arg:
                         Doubling(spec, policy))
        try:
            backend = get_backend("doubling")
            a = _gauss(8, 0)
            np.testing.assert_allclose(np.asarray(backend(a, a, site="x")),
                                       2.0 * np.asarray(a @ a))
            assert calls == ["x"]
        finally:
            from repro.core import backends as B
            B._FACTORIES.pop("doubling", None)


class TestPolicyBinding:
    def test_pinned_spec_is_authoritative(self):
        pol = PrecisionPolicy(default_splits=3,
                              site_splits={"hot": 9})
        pinned = get_backend("fp64_int8_6", policy=pol)
        assert pinned.resolve_splits(None, "hot") == 6
        assert pinned.resolve_splits(4, "hot") == 6

    def test_unpinned_spec_defers_to_policy(self):
        pol = PrecisionPolicy(default_splits=3, site_splits={"hot": 9})
        free = get_backend("fp64_int8", policy=pol)
        assert free.resolve_splits(None, "hot") == 9
        assert free.resolve_splits(None, "cold") == 3
        assert free.resolve_splits(5, "cold") == 5

    def test_accumulator_binding(self):
        a, b = _gauss(128, 1), _gauss(128, 2)
        ref = a @ b
        denom = jnp.abs(a) @ jnp.abs(b)
        for acc in ("df32", "f64"):
            backend = get_backend(
                "fp64_int8_7", policy=PrecisionPolicy(accumulator=acc))
            c = backend(a, b, out_dtype=jnp.float64)
            err = float(jnp.max(jnp.abs(c - ref) / denom))
            assert err < 1e-11, acc


def _dot_precisions(fn, *args):
    """``precision`` params of every dot_general ``fn`` stages."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e.params["precision"] for e in jaxpr.eqns
            if e.primitive.name == "dot_general"]


class TestBackendNumerics:
    def test_dgemm_matches_native(self):
        a, b = _gauss(64, 3), _gauss(64, 4)
        np.testing.assert_array_equal(
            np.asarray(get_backend("dgemm")(a, b)), np.asarray(a @ b))

    def test_dgemm_reference_runs_at_highest_precision(self):
        # The reference every emulation error is measured against must
        # not be a TPU's one-pass bf16 DEFAULT.  CPU values cannot show
        # it, so read the staged precision.
        import jax

        a = _gauss(16, 3, jnp.float32)
        highest = (jax.lax.Precision.HIGHEST,) * 2
        assert _dot_precisions(get_backend("dgemm"), a, a) == [highest]

    def test_pallas_backend_refuses_other_platforms(self, monkeypatch):
        import jax

        assert get_backend("pallas_int8_4").interpret  # CPU: interpreted
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="needs a TPU"):
            get_backend("pallas_int8_4")

    def test_ozaki_accuracy_ladder(self):
        a, b = _gauss(128, 5), _gauss(128, 6)
        ref = a @ b
        denom = jnp.abs(a) @ jnp.abs(b)
        errs = []
        for s in (3, 6, 9):
            c = get_backend(f"fp64_int8_{s}")(a, b, out_dtype=jnp.float64)
            errs.append(float(jnp.max(jnp.abs(c - ref) / denom)))
        assert errs[0] > errs[1] > errs[2]

    def test_pallas_matches_jnp_reference(self):
        # interpret-mode kernel vs jnp df32 path: bit-identical by
        # construction (shared slicing + shared TwoSum accumulation).
        a = _gauss(96, 7, jnp.float32)
        b = _gauss(96, 8, jnp.float32)
        pol = PrecisionPolicy(accumulator="df32")
        c_pal = get_backend("pallas_int8_5", policy=pol)(a, b)
        c_jnp = get_backend("fp64_int8_5", policy=pol)(a, b)
        np.testing.assert_array_equal(np.asarray(c_pal),
                                      np.asarray(c_jnp))

    def test_pallas_complex_operands(self):
        rng = np.random.default_rng(9)
        a = jnp.asarray(rng.standard_normal((64, 64))
                        + 1j * rng.standard_normal((64, 64)))
        b = jnp.asarray(rng.standard_normal((64, 64))
                        + 1j * rng.standard_normal((64, 64)))
        c = get_backend("pallas_int8_7")(a, b, out_dtype=jnp.complex128)
        ref = a @ b
        err = float(jnp.max(jnp.abs(c - ref)) / jnp.max(jnp.abs(ref)))
        assert err < 1e-10

    def test_adaptive_probes_and_caches(self):
        backend = get_backend("adaptive:1e-9")
        a, b = _gauss(128, 10), _gauss(128, 11)
        c = backend(a, b, site="tau")
        assert backend.gemm.sites["tau"].err_estimate <= 1e-9
        backend(a, b, site="tau")
        assert backend.gemm.sites["tau"].calls == 2
        ref = a @ b
        denom = jnp.abs(a) @ jnp.abs(b)
        assert float(jnp.max(jnp.abs(c - ref) / denom)) <= 1e-9

    def test_adaptive_traceable(self):
        # Under jit the operands are abstract: the backend must fall
        # back to the a-priori split model instead of probing.
        import jax

        backend = get_backend("adaptive:1e-9")
        a, b = _gauss(128, 12), _gauss(128, 13)
        c = jax.jit(lambda a, b: backend(a, b, site="jit"))(a, b)
        ref = a @ b
        denom = jnp.abs(a) @ jnp.abs(b)
        assert float(jnp.max(jnp.abs(c - ref) / denom)) <= 1e-9
        assert "jit" not in backend.gemm.sites  # no concrete probe ran
