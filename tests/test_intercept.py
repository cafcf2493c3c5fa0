"""Automatic offload: site discovery and numerical agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PrecisionPolicy, estimate_rel_error, offload,
                        site_report, transform_jaxpr)


def _solver(a, b):
    x = jnp.tanh(a @ b)
    for _ in range(2):
        x = x @ b / jnp.linalg.norm(x)
    return jnp.sum(x)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((192, 192)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((192, 192)), jnp.float32)
    return a, b


class TestSiteReport:
    def test_discovers_all_matmuls(self, operands):
        a, b = operands
        sites = site_report(_solver, PrecisionPolicy(min_dim=128))(a, b)
        assert len(sites) == 3
        assert all(s.offloaded for s in sites)
        assert [s.name for s in sites] == ["dot0", "dot1", "dot2"]
        assert sites[0].lhs_shape == (192, 192)

    def test_min_dim_gates_sites(self, operands):
        a, b = operands
        sites = site_report(_solver, PrecisionPolicy(min_dim=256))(a, b)
        assert all(not s.offloaded for s in sites)
        assert "min_dim" in sites[0].reason

    def test_small_dims_reported_not_offloaded(self):
        def f(a, b):
            return (a @ b) @ b.T  # k=8 below any sane min_dim

        a = jnp.ones((256, 8))
        b = jnp.ones((8, 256))
        sites = site_report(f, PrecisionPolicy(min_dim=64))(a, b)
        assert [s.offloaded for s in sites] == [False, False]

    def test_site_splits_override(self, operands):
        a, b = operands
        pol = PrecisionPolicy(default_splits=4, min_dim=64,
                              site_splits={"dot1": 9})
        sites = site_report(_solver, pol)(a, b)
        assert [s.splits for s in sites] == [4, 9, 4]

    def test_pallas_sites_carry_tile_choice(self, operands):
        # Pallas-family sites record the analytic tile model's block
        # pick (and show it in repr); jnp-family sites record None.
        a, b = operands
        pol = PrecisionPolicy(backend="pallas_int8", default_splits=4,
                              min_dim=64)
        sites = site_report(_solver, pol)(a, b)
        for s in sites:
            assert set(s.tiles) == {"block_m", "block_n", "block_k",
                                    "pairs", "schedule"}
            assert s.tiles["schedule"] == "ordered"
            assert "tiles=" in repr(s)
        jnp_sites = site_report(_solver,
                                PrecisionPolicy(min_dim=64))(a, b)
        assert all(s.tiles is None for s in jnp_sites)


class TestOffloadNumerics:
    def test_agrees_with_native(self, operands):
        # In f32 the signed sum cancels (sum|x| / |sum x| ~ 1.6e3), so
        # native and emulated f32 runs each sit ~5e-6 from the exact
        # value and their gap falls either side of 1e-5 by host.  In
        # f64 that noise is gone and the bound measures the GEMMs.
        a, b = (x.astype(jnp.float64) for x in operands)
        pol = PrecisionPolicy(default_splits=7, min_dim=128)
        ref = float(_solver(a, b))
        got = float(offload(_solver, pol)(a, b))
        assert abs(got - ref) / abs(ref) < 1e-5

    def test_composes_with_jit(self, operands):
        a, b = operands
        pol = PrecisionPolicy(default_splits=6, min_dim=128)
        eager = offload(_solver, pol)(a, b)
        jitted = jax.jit(offload(_solver, pol))(a, b)
        np.testing.assert_allclose(np.asarray(jitted),
                                   np.asarray(eager), rtol=1e-6)

    def test_gated_function_is_bit_identical(self, operands):
        # min_dim above every site => the interpreter must reproduce
        # the native computation exactly (same primitives, same order).
        a, b = operands
        pol = PrecisionPolicy(min_dim=4096)
        ref = _solver(a, b)
        got = offload(_solver, pol)(a, b)
        assert float(ref) == float(got)

    def test_pytree_outputs_and_kwargs(self):
        def f(a, scale=2.0):
            return {"y": (a @ a) * scale, "trace": jnp.trace(a)}

        a = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((160, 160)), jnp.float32)
        pol = PrecisionPolicy(default_splits=7, min_dim=64)
        ref = f(a, scale=3.0)
        got = offload(f, pol)(a, scale=3.0)
        assert set(got) == {"y", "trace"}
        np.testing.assert_allclose(np.asarray(got["y"]),
                                   np.asarray(ref["y"]), rtol=1e-4,
                                   atol=1e-3)
        assert float(got["trace"]) == float(ref["trace"])

    def test_transposed_contraction(self):
        def f(a, b):
            return jax.lax.dot_general(
                a, b, dimension_numbers=(((0,), (1,)), ((), ())))

        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.standard_normal((128, 96)))
        b = jnp.asarray(rng.standard_normal((144, 128)))
        pol = PrecisionPolicy(default_splits=9, min_dim=64,
                              accumulator="f64")
        ref = np.asarray(f(a, b))
        got = np.asarray(offload(f, pol)(a, b))
        np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-10)


class TestTransformCacheLRU:
    def test_cache_info_counts(self, operands):
        a, b = operands
        pol = PrecisionPolicy(default_splits=4, min_dim=64)
        w = offload(_solver, pol)
        assert w.cache_info() == (0, 0, 64, 0)
        w(a, b)
        w(a, b)
        w(a[:96], b)
        info = w.cache_info()
        assert (info.hits, info.misses, info.currsize) == (1, 2, 2)
        assert info.maxsize == 64
        w.cache_clear()
        assert w.cache_info() == (0, 0, 64, 0)

    def test_signature_churn_is_bounded(self, operands):
        # Serve-style churn: every padded batch size is a new
        # signature; the cache must evict, not grow without bound.
        _, b = operands

        def f(a, b):
            return a @ b

        w = offload(f, PrecisionPolicy(min_dim=64), cache_size=4)
        for rows in range(64, 64 + 10):
            w(jnp.ones((rows, 192)), b)
        info = w.cache_info()
        assert info.currsize == 4 and info.misses == 10

    def test_eviction_is_least_recently_used(self, operands):
        _, b = operands

        def f(a, b):
            return a @ b

        w = offload(f, PrecisionPolicy(min_dim=64), cache_size=2)
        a64, a80, a96 = (jnp.ones((r, 192)) for r in (64, 80, 96))
        w(a64, b)
        w(a80, b)
        w(a64, b)   # refresh a64: a80 is now the LRU entry
        w(a96, b)   # evicts a80
        assert w.cache_info().currsize == 2
        w(a64, b)   # still cached
        assert w.cache_info().hits == 2
        w(a80, b)   # was evicted -> re-traces
        assert w.cache_info().misses == 4

    def test_rejects_senseless_cache_size(self):
        with pytest.raises(ValueError, match="cache_size"):
            offload(lambda x: x, cache_size=0)


class TestSharedSiteNames:
    def test_nested_pjit_names_identical(self, operands):
        # Regression: PR-1 numbered sites differently in site_report
        # (prefix+len) and offload (flat counter).  The shared walker
        # must yield identical names for nested-pjit functions.
        a, b = operands

        @jax.jit
        def inner(x, y):
            return x @ y

        def f(a, b):
            u = inner(a, b)          # inside a pjit body
            v = jnp.tanh(a) @ u      # top level
            return jnp.sum(inner(v, b))  # second pjit body

        pol = PrecisionPolicy(default_splits=5, min_dim=64)
        report_names = [s.name for s in site_report(f, pol)(a, b)]
        offload_names = [s.name for s in offload(f, pol).sites(a, b)]
        assert report_names == offload_names
        assert report_names == ["dot0", "dot1", "dot2"]

    def test_control_flow_names_are_path_scoped(self, operands):
        a, b = operands

        def f(a, b):
            def body(c, x):
                return c @ x, jnp.sum(c)
            c, sums = jax.lax.scan(body, a, jnp.stack([b, b]))
            return jnp.sum(c @ b) + jnp.sum(sums)

        pol = PrecisionPolicy(default_splits=5, min_dim=64)
        report_names = [s.name for s in site_report(f, pol)(a, b)]
        offload_names = [s.name for s in offload(f, pol).sites(a, b)]
        assert report_names == offload_names
        assert report_names == ["scan0/dot0", "dot0"]

    def test_offload_of_jitted_fn_names_identical(self, operands):
        # offload(jax.jit(f)): the whole function arrives as one pjit
        # eqn; inlining must keep the flat dot numbering of f itself.
        a, b = operands
        f = jax.jit(_solver)
        pol = PrecisionPolicy(default_splits=5, min_dim=64)
        report_names = [s.name for s in site_report(f, pol)(a, b)]
        offload_names = [s.name for s in offload(f, pol).sites(a, b)]
        assert report_names == offload_names
        assert report_names == ["dot0", "dot1", "dot2"]

    def test_vmap_of_offload_names_identical(self, operands):
        # jax.vmap(offload(f)) traces the wrapper with batch tracers:
        # sites must be discovered on the *per-example* shapes with the
        # same names an unbatched call produces, and execution must
        # match vmap of the native function.
        a, b = operands
        pol = PrecisionPolicy(default_splits=8, min_dim=64)
        wrapped = offload(_solver, pol)
        batched = jax.vmap(wrapped, in_axes=(0, None))
        # f64 operands, as in test_agrees_with_native.
        stack = jnp.stack([a, 2.0 * a, a - 1.0]).astype(jnp.float64)
        b64 = b.astype(jnp.float64)
        got = np.asarray(batched(stack, b64))
        ref = np.asarray(jax.vmap(_solver, in_axes=(0, None))(stack, b64))
        np.testing.assert_allclose(got, ref, rtol=1e-5)
        # The signature seen under vmap is the per-example one: names
        # (and decisions) are identical to the unbatched report.
        assert [s.name for s in wrapped.sites(a, b)] == \
            [s.name for s in site_report(_solver, pol)(a, b)]

    def test_site_override_applies_through_offload(self, operands):
        # The stable names must be usable PrecisionPolicy.site_splits
        # keys: overriding one site changes only that site's splits.
        a, b = operands
        pol = PrecisionPolicy(default_splits=4, min_dim=64,
                              site_splits={"dot1": 9})
        sites = offload(_solver, pol).sites(a, b)
        assert [s.splits for s in sites] == [4, 9, 4]


class TestBatchedOffload:
    def test_rank3_batched_dot_general(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((4, 160, 160)))
        y = jnp.asarray(rng.standard_normal((4, 160, 160)))

        def f(x, y):
            return jnp.einsum("bij,bjk->bik", x, y)

        pol = PrecisionPolicy(default_splits=8, min_dim=128)
        sites = offload(f, pol).sites(x, y)
        assert len(sites) == 1 and sites[0].offloaded
        ref = np.asarray(f(x, y))
        got = np.asarray(offload(f, pol)(x, y))
        denom = np.asarray(jnp.einsum("bij,bjk->bik", jnp.abs(x),
                                      jnp.abs(y)))
        tol = estimate_rel_error(8, 160)
        assert np.max(np.abs(got - ref) / denom) < tol

    def test_batch_dims_not_counted_toward_min_dim(self):
        x = jnp.ones((256, 32, 32))
        y = jnp.ones((256, 32, 32))
        sites = site_report(
            lambda x, y: jnp.einsum("bij,bjk->bik", x, y),
            PrecisionPolicy(min_dim=128))(x, y)
        assert [s.offloaded for s in sites] == [False]
        assert "min_dim" in sites[0].reason

    def test_rank4_free_dims_merge(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((12, 12, 144)))
        y = jnp.asarray(rng.standard_normal((144, 144)))

        def f(x, y):  # (12*12, 144) @ (144, 144) after merging
            return jnp.tensordot(x, y, axes=([2], [0]))

        pol = PrecisionPolicy(default_splits=8, min_dim=128)
        sites = offload(f, pol).sites(x, y)
        assert len(sites) == 1 and sites[0].offloaded
        ref = np.asarray(f(x, y))
        got = np.asarray(offload(f, pol)(x, y))
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=estimate_rel_error(8, 144)
                                   * np.max(np.abs(ref)))


class TestControlFlowOffload:
    def test_scan_body_offloaded(self):
        rng = np.random.default_rng(7)
        c0 = jnp.asarray(rng.standard_normal((144, 144)))
        xs = jnp.asarray(rng.standard_normal((3, 144, 144)))

        def f(c0, xs):
            def body(c, x):
                return jnp.tanh(c @ x), jnp.trace(c)
            return jax.lax.scan(body, c0, xs)

        pol = PrecisionPolicy(default_splits=8, min_dim=128)
        sites = offload(f, pol).sites(c0, xs)
        assert [s.name for s in sites] == ["scan0/dot0"]
        assert sites[0].offloaded
        ref_c, ref_t = f(c0, xs)
        got_c, got_t = offload(f, pol)(c0, xs)
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(ref_c),
                                   rtol=0, atol=1e-9)
        np.testing.assert_allclose(np.asarray(got_t), np.asarray(ref_t),
                                   rtol=1e-9)

    def test_cond_branches_offloaded(self):
        rng = np.random.default_rng(8)
        a = jnp.asarray(rng.standard_normal((144, 144)))

        def f(pred, a):
            return jax.lax.cond(pred, lambda x: x @ x,
                                lambda x: x + 1.0, a)

        pol = PrecisionPolicy(default_splits=8, min_dim=128)
        wrapped = offload(f, pol)
        names = [s.name for s in wrapped.sites(True, a)]
        assert names == ["cond0/br1/dot0"] or names == ["cond0/br0/dot0"]
        for pred in (True, False):
            ref = np.asarray(f(pred, a))
            got = np.asarray(wrapped(pred, a))
            np.testing.assert_allclose(got, ref, rtol=0, atol=1e-9)

    def test_while_body_offloaded(self):
        rng = np.random.default_rng(9)
        a = jnp.asarray(0.01 * rng.standard_normal((144, 144)))

        def f(a):
            def body(v):
                i, x = v
                return i + 1, x @ x
            def cond(v):
                return v[0] < 3
            return jax.lax.while_loop(cond, body, (0, a))[1]

        pol = PrecisionPolicy(default_splits=9, min_dim=128)
        wrapped = offload(f, pol)
        assert [s.name for s in wrapped.sites(a)] == ["while0/dot0"]
        ref = np.asarray(f(a))
        got = np.asarray(wrapped(a))
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=1e-10 * max(1.0,
                                                    np.max(np.abs(ref))))


class TestOffloadAutodiff:
    def test_grad_through_offload(self, operands):
        a, b = operands

        def f(a, b):
            return jnp.sum(jnp.tanh(a @ b))

        pol = PrecisionPolicy(default_splits=8, min_dim=64)
        g_ref = np.asarray(jax.grad(f)(a, b))
        g_off = np.asarray(jax.grad(offload(f, pol))(a, b))
        assert np.max(np.abs(g_off - g_ref)) < 1e-3
        assert np.max(np.abs(g_off - g_ref)) / np.max(np.abs(g_ref)) \
            < 1e-2

    def test_grad_is_also_emulated(self, operands):
        # The backward pass must route through the backend too: with a
        # very low split count the gradient error is visibly larger
        # than with a high one (pure-native backward would show no
        # dependence on the split count).
        a, b = operands

        def f(a, b):
            return jnp.sum((a @ b) ** 2)

        def gerr(splits):
            pol = PrecisionPolicy(default_splits=splits, min_dim=64)
            g = np.asarray(jax.grad(offload(f, pol))(a, b))
            g_ref = np.asarray(jax.grad(f)(a, b))
            return np.max(np.abs(g - g_ref))

        assert gerr(2) > 10 * gerr(6)


class TestTransformJaxpr:
    def test_no_per_call_retracing(self, operands):
        # offload must trace fn exactly once per input signature.
        a, b = operands
        calls = [0]

        def f(a, b):
            calls[0] += 1
            return jnp.sum(a @ b)

        pol = PrecisionPolicy(default_splits=4, min_dim=64)
        wrapped = offload(f, pol)
        wrapped(a, b)
        wrapped(a, b)
        wrapped(a, b)
        assert calls[0] == 1
        wrapped(a[:96], b)  # new signature -> one more trace
        assert calls[0] == 2

    def test_transform_is_jaxpr_to_jaxpr(self, operands):
        a, b = operands
        pol = PrecisionPolicy(default_splits=5, min_dim=64)
        closed = jax.make_jaxpr(_solver)(a, b)
        transformed, sites = transform_jaxpr(closed, pol)
        assert type(transformed) is type(closed)
        assert len([s for s in sites if s.offloaded]) == 3
        # The rewritten program must contain no bare dot_general at the
        # top level: every site now lives inside its custom_vjp wrapper.
        top = [e.primitive.name for e in transformed.jaxpr.eqns]
        assert "dot_general" not in top
        out = jax.core.eval_jaxpr(transformed.jaxpr, transformed.consts,
                                  a, b)
        ref = float(_solver(a, b))
        assert abs(float(out[0]) - ref) / abs(ref) < 1e-3


class TestCallPrimitiveBoundaries:
    def test_remat_body_is_offloaded(self, operands):
        # jax.checkpoint stages through the 'remat2' primitive: its
        # body must be inlined and its matmuls rewritten (regression:
        # a stale primitive-name set silently skipped remat bodies).
        a, b = operands

        def f(a, b):
            return jnp.sum(jax.checkpoint(lambda x, y: x @ y)(a, b))

        pol = PrecisionPolicy(default_splits=3, min_dim=64)
        wrapped = offload(f, pol)
        sites = wrapped.sites(a, b)
        assert [s.name for s in sites] == ["dot0"]
        assert sites[0].offloaded
        # s=3 is coarse enough that emulation must visibly differ.
        assert float(wrapped(a, b)) != float(f(a, b))
        g_ref = np.asarray(jax.grad(f)(a, b))
        g_off = np.asarray(jax.grad(wrapped)(a, b))
        assert np.max(np.abs(g_off - g_ref)) < 1e-1

    def test_custom_jvp_rule_preserved(self, operands):
        # Custom-derivative functions are opaque: offload must not
        # replace the user's jvp rule by differentiating an inlined
        # primal (regression: inlining gave nonzero grad here).
        a, b = operands

        @jax.custom_jvp
        def gmat(x, y):
            return x @ y

        @gmat.defjvp
        def gmat_jvp(primals, tangents):
            x, y = primals
            return x @ y, jnp.zeros((x.shape[0], y.shape[1]),
                                    x.dtype)

        def f(a, b):
            return jnp.sum(gmat(a, b))

        pol = PrecisionPolicy(default_splits=3, min_dim=64)
        wrapped = offload(f, pol)
        assert wrapped.sites(a, b) == []  # opaque: no sites inside
        assert float(wrapped(a, b)) == float(f(a, b))
        g = np.asarray(jax.grad(wrapped)(a, b))
        assert np.max(np.abs(g)) == 0.0  # the zero-tangent rule held

    def test_custom_vjp_rule_preserved(self, operands):
        a, b = operands

        @jax.custom_vjp
        def vmat(x, y):
            return x @ y

        def vfwd(x, y):
            return x @ y, (x, y)

        def vbwd(res, g):
            x, y = res
            return jnp.zeros_like(x), jnp.zeros_like(y)

        vmat.defvjp(vfwd, vbwd)

        def f(a, b):
            return jnp.sum(vmat(a, b))

        pol = PrecisionPolicy(default_splits=3, min_dim=64)
        wrapped = offload(f, pol)
        assert float(wrapped(a, b)) == float(f(a, b))
        assert float(jax.jit(wrapped)(a, b)) == float(f(a, b))
        g = np.asarray(jax.grad(wrapped)(a, b))
        assert np.max(np.abs(g)) == 0.0

    def test_shared_inner_jaxpr_sites_stay_distinct(self, operands):
        # JAX's tracing cache reuses one body jaxpr object (and thus
        # the same eqn objects) for every call of a jit-ed inner
        # function.  Decisions must key on the structural name, not on
        # equation identity, or a site_splits override for dot0 is
        # silently applied from dot1's decision (regression).
        a, b = operands
        inner = jax.jit(lambda x, y: x @ y)

        def f(a, b):
            return jnp.sum(inner(a, b)) + jnp.sum(inner(b, a))

        base = PrecisionPolicy(default_splits=3, min_dim=64)
        tuned = PrecisionPolicy(default_splits=3, min_dim=64,
                                site_splits={"dot0": 9})
        assert [s.splits for s in offload(f, tuned).sites(a, b)] == [9, 3]
        # The override must change execution, not just the report.
        assert float(offload(f, tuned)(a, b)) != \
            float(offload(f, base)(a, b))
        # And with both sites pinned high, the result tracks native.
        # In f64, as in test_agrees_with_native: the f32 sums cancel.
        both = PrecisionPolicy(default_splits=8, min_dim=64)
        a, b = a.astype(jnp.float64), b.astype(jnp.float64)
        ref = float(f(a, b))
        assert abs(float(offload(f, both)(a, b)) - ref) / abs(ref) < 1e-5


class TestSiteScopes:
    """Each site's ops carry its named scope in the compiled program."""

    @staticmethod
    def _scan_program(c, xs):
        def body(c, x):
            y = jnp.tanh(c @ x)                    # offloaded
            z = y[:, :32].T @ y[:, :32]            # gated: 32 < min_dim
            return y + jnp.sum(z), None
        return jax.lax.scan(body, c, xs)[0]

    @staticmethod
    def _op_names(compiled_text):
        import re
        return set(re.findall(r'op_name="([^"]+)"', compiled_text))

    def test_compiled_hlo_names_offloaded_and_native_sites(self):
        c = jnp.ones((128, 128), jnp.float32) * 0.01
        xs = jnp.ones((3, 128, 128), jnp.float32) * 0.01
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=2,
                              min_dim=64)
        wrapped = offload(self._scan_program, pol)
        sites = {s.name: s.offloaded for s in wrapped.sites(c, xs)}
        assert sites == {"scan0/dot0": True, "scan0/dot1": False}
        names = self._op_names(
            jax.jit(wrapped).lower(c, xs).compile().as_text())
        components = {part for n in names for part in n.split("/")}
        assert {"ozaki_scan0.dot0", "native_scan0.dot1"} <= components
        # The scope wraps the whole backend subgraph: the slicing and
        # the int8 pair products, not only one op.
        ozaki = [n for n in names if "/ozaki_scan0.dot0/" in n]
        assert len(ozaki) > 5
        assert any(n.endswith("/dot_general") for n in ozaki)

    def test_backward_runs_under_the_site_scope(self, operands):
        a, b = operands
        pol = PrecisionPolicy(backend="fp64_int8", default_splits=2,
                              min_dim=64)
        grad = jax.jit(jax.grad(offload(lambda a, b: jnp.sum(a @ b),
                                        pol)))
        names = self._op_names(grad.lower(a, b).compile().as_text())
        backward = [n for n in names
                    if "transpose" in n and "/ozaki_dot0/" in n]
        assert any(n.endswith("/dot_general") for n in backward)

    def test_site_scope_format(self):
        from repro.core.intercept import Site, site_scope

        def site(name, offloaded):
            return Site(name, (4, 4), (4, 4), jnp.float32, offloaded, 2, "")

        assert site_scope(site("scan0/dot3", True)) == "ozaki_scan0.dot3"
        assert (site_scope(site("while2/cond/dot0", False))
                == "native_while2.cond.dot0")
        assert site_scope(site("dot0", True)) == "ozaki_dot0"
