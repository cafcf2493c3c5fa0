"""Precision-plan tuner: calibration, solver, plan artifact, consumers.

The acceptance bar (ISSUE 5): on the LM reduced preset a solved plan
meets the same end-to-end loss tolerance as uniform ``fp64_int8_6``
while issuing strictly fewer INT8 GEMMs per step, and a plan saved
from a dp=8 sharded calibration run is byte-identical to the
single-device plan for the same config.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (PrecisionPolicy, canonical_site, offload,
                        site_report)
from repro.launch.train import build_train_step
from repro.models import Model
from repro.train import AdamW, SyntheticText
from repro.tune import (PLAN_VERSION, Calibrator, PlanError,
                        PlanStaleError, PrecisionPlan, SiteRecord,
                        count_int8_gemms, default_budget,
                        site_set_fingerprint, solve_plan,
                        unpinned_family)

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _two_site_fn(a, b):
    return jnp.sum(jnp.tanh(a @ b) @ b)


def _operands(n=192, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, n))),
            jnp.asarray(rng.standard_normal((n, n))))


def _record(site="dot0", k=256, dtype="float64", flops=10**7,
            measured=None, probe=6):
    return SiteRecord(site=site, k=k, dtype=dtype, flops=flops,
                      probe_splits=probe, measured_rel=measured,
                      lhs_exp=0, rhs_exp=0)


def _result(records, policy=None, fingerprint="sha256:test"):
    from repro.tune.calibrate import CalibrationResult

    return CalibrationResult(records=records, fingerprint=fingerprint,
                             policy=policy or PrecisionPolicy(),
                             probe_splits=records[0].probe_splits
                             if records else 6)


class TestCanonicalSite:
    def test_strips_spmd_scopes_only(self):
        assert canonical_site("shmap0/dot1") == "dot1"
        assert canonical_site("pmap2/scan0/dot3") == "scan0/dot3"
        assert canonical_site("scan1/cond0/br1/dot0") == \
            "scan1/cond0/br1/dot0"
        assert canonical_site("dot0") == "dot0"

    def test_policy_lookup_is_canonical(self):
        pol = PrecisionPolicy(default_splits=3,
                              site_splits={"scan0/dot1": 9},
                              site_backends={"dot0": "dgemm"})
        assert pol.splits_for("shmap0/scan0/dot1") == 9
        assert pol.splits_for("scan0/dot1") == 9
        assert pol.splits_for("scan0/dot2") == 3
        assert pol.backend_for("shmap1/dot0") == "dgemm"
        assert pol.backend_for("dot1") == pol.backend

    def test_sharded_key_reaches_unsharded_site(self):
        # A key copied from a *sharded* site_report must drive the
        # unsharded program too (and count as matched, not warn).
        pol = PrecisionPolicy(default_splits=3,
                              site_splits={"shmap0/dot1": 8})
        assert pol.splits_for("dot1") == 8
        a, b = _operands(192)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            sites = offload(_two_site_fn,
                            PrecisionPolicy(
                                min_dim=64,
                                site_splits={"shmap0/dot1": 8})
                            ).sites(a, b)
        assert sites[1].splits == 8


class TestCalibrator:
    def test_records_stats_and_returns_native(self):
        a, b = _operands()
        pol = PrecisionPolicy(default_splits=6, min_dim=128)
        cal = Calibrator(_two_site_fn, pol)
        out = cal.run(a, b)
        assert float(out) == pytest.approx(float(_two_site_fn(a, b)),
                                           rel=1e-12)
        res = cal.result()
        assert [r.site for r in res.records] == ["dot0", "dot1"]
        for r in res.records:
            assert r.k == 192
            assert r.dtype == "float64"
            assert r.flops == 2 * 192**3
            # Gaussian operands at probe s=6 measure well below the
            # a-priori model but above the f64 reference floor.
            assert r.measured_rel is not None
            assert 1e-14 < r.measured_rel < 1e-8
            assert r.rhs_exp is not None and r.rhs_exp >= 1
        # dot0's lhs is the raw Gaussian (max |x| ~ 4 -> exp 2-3);
        # dot1's lhs is tanh-squashed (max |x| <= 1 -> exp <= 0).
        assert res.records[0].lhs_exp >= 1
        assert res.records[1].lhs_exp <= 0

    def test_scan_multiplicity_scales_flops(self):
        w = jnp.eye(160)

        def f(x):
            def body(c, _):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, None, length=3)
            return y

        x = jnp.ones((160, 160))
        cal = Calibrator(f, PrecisionPolicy(min_dim=64))
        cal.run(x)
        (rec,) = cal.result().records
        assert rec.site == "scan0/dot0"
        assert rec.flops == 3 * 2 * 160**3  # trip multiplicity

    def test_zero_operand_leaves_model_curve(self):
        a, _ = _operands()
        zero = jnp.zeros((192, 192))
        cal = Calibrator(lambda a, b: a @ b, PrecisionPolicy(min_dim=64))
        cal.run(a, zero)
        (rec,) = cal.result().records
        assert rec.measured_rel is None  # degenerate anchor rejected
        assert rec.rhs_exp == 0

    def test_demoted_sites_are_still_measured(self):
        # Re-calibrating under a from_plan policy: a site the old plan
        # demoted to dgemm must still be instrumented, or it would be
        # re-promoted with no measurement to catch the pathology.
        a, b = _operands(192)
        pol = PrecisionPolicy(min_dim=64,
                              site_backends={"dot0": "dgemm"},
                              on_unmatched_site="ignore")
        cal = Calibrator(_two_site_fn, pol)
        out = cal.run(a, b)
        assert float(out) == pytest.approx(float(_two_site_fn(a, b)),
                                           rel=1e-12)
        recs = {r.site: r for r in cal.result().records}
        assert recs["dot0"].measured_rel is not None
        assert recs["dot1"].measured_rel is not None

    def test_signature_drift_raises(self):
        cal = Calibrator(lambda a, b: a @ b, PrecisionPolicy(min_dim=64))
        a, b = _operands(192)
        cal.run(a, b)
        big = jnp.ones((256, 256))
        with pytest.raises(ValueError, match="site set"):
            # Different k -> different eligible site set fingerprint.
            cal.run(big, big)


class TestSolver:
    def test_budget_monotone(self):
        recs = [_record("dot0", k=256), _record("dot1", k=1024)]
        loose = solve_plan(_result(recs), budget=1e-4)
        tight = solve_plan(_result(recs), budget=1e-12)
        for s_loose, s_tight in zip(loose.sites, tight.sites):
            assert s_loose.splits <= s_tight.splits
        assert loose.budget_met and tight.budget_met

    def test_measured_anchor_needs_fewer_splits(self):
        # A site measured 1000x better than the model gets fewer
        # splits than the same site on the model curve.
        modeled = solve_plan(_result([_record(measured=None)]),
                             budget=1e-10)
        anchored = solve_plan(
            _result([_record(measured=1e-13, probe=6)]), budget=1e-10)
        assert anchored.sites[0].splits < modeled.sites[0].splits

    def test_pathological_site_demoted_to_dgemm(self):
        recs = [_record("dot0", measured=1e-3, probe=6),  # >> model
                _record("dot1", measured=1e-11, probe=6)]
        plan = solve_plan(_result(recs), budget=1e-9)
        by = {s.site: s for s in plan.sites}
        assert by["dot0"].backend == "dgemm"
        assert by["dot0"].splits == 0
        assert by["dot1"].backend == "fp64_int8"
        assert plan.demoted_sites() == ["dot0"]

    def test_cost_weighting_prefers_cheap_sites(self):
        # Same error curves, 100x different cost: the expensive site
        # must never end up with more splits than the cheap one.
        recs = [_record("cheap", flops=10**6),
                _record("costly", flops=10**8)]
        plan = solve_plan(_result(recs), budget=1e-9)
        by = {s.site: s.splits for s in plan.sites}
        assert by["costly"] <= by["cheap"]

    def test_unreachable_budget_flagged(self):
        plan = solve_plan(_result([_record()]), budget=1e-300)
        assert not plan.budget_met
        assert all(s.splits == 14 for s in plan.sites)  # MAX_SPLITS

    def test_deterministic(self):
        recs = [_record(f"dot{i}", k=128 * (i + 1)) for i in range(5)]
        a = solve_plan(_result(recs), budget=1e-9)
        b = solve_plan(_result(list(reversed(recs))), budget=1e-9)
        assert a.to_json() == b.to_json()

    def test_default_budget_tracks_loosest_dtype(self):
        f32 = default_budget([_record(dtype="float32")])
        f64 = default_budget([_record(dtype="float64")])
        assert f32 == pytest.approx(32 * np.finfo(np.float32).eps)
        assert f64 == pytest.approx(32 * np.finfo(np.float64).eps)
        assert default_budget([_record(dtype="float32"),
                               _record(dtype="float64")]) == f32
        # ml_dtypes types resolve too (np.finfo would raise here).
        bf16 = default_budget([_record(dtype="bfloat16")])
        assert bf16 == pytest.approx(32 * 2.0 ** -7)
        assert solve_plan(_result([_record(dtype="bfloat16")])
                          ).budget == pytest.approx(bf16)

    def test_unpinned_family(self):
        assert unpinned_family("fp64_int8_6") == "fp64_int8"
        assert unpinned_family("fp64_int8") == "fp64_int8"
        assert unpinned_family("adaptive:1e-9") == "adaptive:1e-9"


class TestPlanArtifact:
    def _plan(self):
        return solve_plan(_result([_record("dot0", k=256),
                                   _record("scan0/dot1", k=512)]),
                          budget=1e-9)

    def test_roundtrip_byte_identical(self, tmp_path):
        plan = self._plan()
        path = plan.save(tmp_path / "p.json")
        loaded = PrecisionPlan.load(path)
        assert loaded.to_json() == plan.to_json()
        assert path.read_text() == plan.to_json()

    def test_unknown_version_rejected(self):
        bad = self._plan().to_json().replace(
            f'"version": {PLAN_VERSION}', '"version": 99')
        with pytest.raises(PlanError, match="version"):
            PrecisionPlan.from_json(bad)

    def test_malformed_rejected(self, tmp_path):
        with pytest.raises(PlanError, match="JSON"):
            PrecisionPlan.from_json("{nope")
        with pytest.raises(PlanError, match="missing"):
            PrecisionPlan.from_json(f'{{"version": {PLAN_VERSION}}}')
        with pytest.raises(PlanError, match="no precision plan"):
            PrecisionPlan.load(tmp_path / "absent.json")

    def test_fingerprint_ignores_free_extents_and_spmd(self):
        a, b = _operands(192)
        pol = PrecisionPolicy(min_dim=64)
        wide = site_report(lambda a, b: a @ b, pol)(
            jnp.ones((640, 192)), b)
        narrow = site_report(lambda a, b: a @ b, pol)(a, b)
        assert site_set_fingerprint(wide) == site_set_fingerprint(narrow)

    def test_validate_sites_stale_names_drift(self):
        plan = self._plan()
        a, b = _operands(192)
        sites = site_report(_two_site_fn,
                            PrecisionPolicy(min_dim=64))(a, b)
        with pytest.raises(PlanStaleError, match="dot1"):
            plan.validate_sites(sites)

    def test_from_plan_policy(self):
        recs = [_record("dot0", k=256, measured=1e-3, probe=6),
                _record("scan0/dot1", k=512)]
        plan = solve_plan(_result(recs), budget=1e-9)
        pol = PrecisionPolicy.from_plan(plan)
        assert pol.backend == "fp64_int8"
        assert pol.backend_for("dot0") == "dgemm"  # demoted
        s = plan.site_splits()["scan0/dot1"]
        assert pol.splits_for("shmap0/scan0/dot1") == s
        assert pol.min_dim == plan.min_dim


class TestPlanTiles:
    """The tile model's canonical block picks in the plan artifact."""

    def _pallas_plan(self):
        recs = [_record("dot0", k=256, dtype="float32"),
                _record("dot1", k=512, dtype="float32",
                        measured=1e-1, probe=6)]  # demoted
        pol = PrecisionPolicy(backend="pallas_int8")
        return solve_plan(_result(recs, policy=pol), budget=1e-6)

    def test_pallas_plan_records_canonical_tiles(self):
        from repro.kernels.tile_model import select_tiles

        plan = self._pallas_plan()
        by_name = {s.site: s for s in plan.sites}
        solved = by_name["dot0"]
        d = select_tiles(None, solved.k, None, solved.splits,
                         dtype=solved.dtype)
        assert solved.tiles == (d.block_m, d.block_n, d.block_k)
        assert "tiles=" in plan.describe()
        # Demoted sites run native: no tile pick.
        assert by_name["dot1"].tiles is None

    def test_jnp_plan_has_no_tiles(self):
        plan = solve_plan(_result([_record("dot0")]), budget=1e-9)
        assert all(s.tiles is None for s in plan.sites)

    def test_tiles_survive_roundtrip_byte_identical(self, tmp_path):
        plan = self._pallas_plan()
        path = plan.save(tmp_path / "p.json")
        loaded = PrecisionPlan.load(path)
        assert loaded.to_json() == plan.to_json()
        assert {s.site: s.tiles for s in loaded.sites} == \
            {s.site: s.tiles for s in plan.sites}

    def test_plan_without_tiles_field_still_loads(self):
        # Plans written before the tile model existed: additive field,
        # same PLAN_VERSION, default None.
        import json as _json

        doc = _json.loads(self._pallas_plan().to_json())
        for s in doc["sites"]:
            s.pop("tiles")
        plan = PrecisionPlan.from_json(_json.dumps(doc))
        assert all(s.tiles is None for s in plan.sites)

    def test_tiles_table_written_next_to_plan(self, tmp_path):
        from repro.tune.plan import tiles_table, write_tiles_table

        plan = self._pallas_plan()
        path = plan.save(tmp_path / "p.json")
        tpath = write_tiles_table(plan, path)
        assert tpath == tmp_path / "p.tiles.json"
        doc = tiles_table(plan)
        assert doc["fingerprint"] == plan.fingerprint
        (row,) = doc["sites"]  # demoted dot1 carries no tiles row
        assert row["site"] == "dot0"
        assert set(row) >= {"tiles", "pairs", "schedule", "vmem_bytes",
                            "mxu_cycles_step", "hbm_bytes_step"}
        import json as _json

        assert _json.loads(tpath.read_text()) == _json.loads(
            _json.dumps(doc, sort_keys=True))

    def test_calibrator_probes_tiles_for_pallas_backend(self):
        a, b = _operands(192)
        pol = PrecisionPolicy(backend="pallas_int8", default_splits=4,
                              min_dim=64)
        cal = Calibrator(_two_site_fn, pol)
        cal.run(a, b)
        result = cal.result()
        assert all(r.tiles is not None for r in result.records)
        assert "tiles=" in result.describe()


class TestUnmatchedSiteOverrides:
    def _run(self, pol):
        a, b = _operands(192)
        return offload(_two_site_fn, pol).sites(a, b)

    def test_typo_warns_by_default(self):
        pol = PrecisionPolicy(min_dim=64,
                              site_splits={"dot7_typo": 9})
        with pytest.warns(UserWarning, match="dot7_typo"):
            self._run(pol)

    def test_strict_mode_raises(self):
        pol = PrecisionPolicy(min_dim=64, site_splits={"nope": 9},
                              on_unmatched_site="raise")
        with pytest.raises(ValueError, match="nope"):
            self._run(pol)

    def test_ignore_mode_is_silent(self):
        pol = PrecisionPolicy(min_dim=64, site_splits={"nope": 9},
                              on_unmatched_site="ignore")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            self._run(pol)

    def test_matching_keys_do_not_warn(self):
        pol = PrecisionPolicy(min_dim=64, site_splits={"dot1": 7})
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            sites = self._run(pol)
        assert sites[1].splits == 7


class TestOffloadWithPlan:
    def _plan_for(self, fn, *args, min_dim=64):
        pol = PrecisionPolicy(min_dim=min_dim)
        cal = Calibrator(fn, pol)
        cal.run(*args)
        return solve_plan(cal.result())

    def test_plan_drives_per_site_splits(self):
        a, b = _operands(192)
        plan = self._plan_for(_two_site_fn, a, b)
        wrapped = offload(_two_site_fn, plan=plan)
        sites = {s.name: s for s in wrapped.sites(a, b)}
        for ps in plan.sites:
            assert sites[ps.site].splits == ps.splits
        assert float(wrapped(a, b)) == pytest.approx(
            float(_two_site_fn(a, b)), rel=1e-9)

    def test_strict_match_raises_on_drift(self):
        a, b = _operands(192)
        plan = self._plan_for(_two_site_fn, a, b)

        def drifted(a, b):  # one extra eligible site
            return jnp.sum(jnp.tanh(a @ b) @ b @ b)

        with pytest.raises(PlanStaleError, match="Re-run calibration"):
            offload(drifted, plan=plan).sites(a, b)

    def test_subset_match_applies_overlap_without_warning(self):
        a, b = _operands(192)
        plan = self._plan_for(_two_site_fn, a, b)

        def forward_only(a, b):  # covers only the plan's dot0
            return a @ b

        # No explicit policy: subset mode derives an ignore-unmatched
        # policy itself — the plan's extra entries must stay silent.
        wrapped = offload(forward_only, plan=plan,
                          plan_match="subset")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            (site,) = wrapped.sites(a, b)
        assert site.splits == plan.site_splits()["dot0"]

    def test_per_site_backend_promotion(self):
        # A single site routed to a distinct engine while the rest
        # stay on the default path — observed through a spy backend,
        # so silent fall-through to the default engine cannot pass.
        from repro.core import register_backend
        from repro.core.backends import _FACTORIES, OzakiBackend

        calls = []

        class SpyBackend(OzakiBackend):
            def matmul(self, a, b, **kw):
                calls.append(kw.get("site"))
                return super().matmul(a, b, **kw)

        register_backend("spy_int8", lambda spec, policy, splits, arg:
                         SpyBackend(spec, policy, splits))
        try:
            a, b = _operands(128, seed=3)
            pol = PrecisionPolicy(default_splits=4, min_dim=64,
                                  site_backends={"dot0": "spy_int8_4"})
            wrapped = offload(_two_site_fn, pol)
            sites = wrapped.sites(a, b)
            assert sites[0].backend == "spy_int8_4"
            assert sites[1].backend == "fp64_int8"
            got = float(wrapped(a, b))
            # dot0 (and only dot0) actually executed on the spy.
            assert set(calls) == {"dot0"} and calls
            # s=4 emulation summed over 128^2 outputs: ~1e-2 headroom.
            assert got == pytest.approx(float(_two_site_fn(a, b)),
                                        abs=5e-2)
        finally:
            _FACTORIES.pop("spy_int8", None)


class TestLMTunedPlanAcceptance:
    """Reduced preset: tuned plan == uniform-6 accuracy, fewer GEMMs."""

    def test_tuned_beats_uniform_cost_at_same_tolerance(self):
        cfg = get_config("reduced")
        model = Model(cfg)
        opt = AdamW(lr=3e-3)
        params = model.init_params(jax.random.PRNGKey(0))
        state = opt.init(params)
        data = SyntheticText(cfg.vocab_size, 32, 2, seed=0)
        batch = jnp.asarray(data.batch(0))
        step = build_train_step(model, opt)

        uniform_pol = PrecisionPolicy(backend="fp64_int8",
                                      default_splits=6, min_dim=64)
        cal = Calibrator(step, uniform_pol)
        cal.run(params, state, batch)
        plan = solve_plan(cal.result())
        assert plan.budget_met

        tuned = offload(step, PrecisionPolicy.from_plan(plan),
                        plan=plan)
        uniform = offload(step, uniform_pol)
        n_tuned = count_int8_gemms(tuned.sites(params, state, batch))
        n_uniform = count_int8_gemms(
            uniform.sites(params, state, batch))
        assert n_tuned < n_uniform, (n_tuned, n_uniform)

        _, _, loss_native = jax.jit(step)(params, state, batch)
        _, _, loss_tuned = jax.jit(tuned)(params, state, batch)
        _, _, loss_uniform = jax.jit(uniform)(params, state, batch)
        tol = 1e-4  # the shared end-to-end loss tolerance
        assert abs(float(loss_tuned) - float(loss_native)) <= tol
        assert abs(float(loss_uniform) - float(loss_native)) <= tol


class TestShardedCalibration:
    @needs8
    def test_dp8_plan_byte_identical_to_single_device(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.shard import build_mesh, data_parallel_sharding

        cfg = get_config("tiny")
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        batch = jnp.asarray(
            SyntheticText(cfg.vocab_size, 64, 8, seed=0).batch(0))
        mesh = build_mesh("dp=8")
        replicated, dp = data_parallel_sharding(mesh)

        def sharded_loss(p, b):
            def per_shard(p_s, b_s):
                return jax.lax.pmean(model.loss(p_s, b_s), "dp")

            return shard_map(per_shard, mesh=mesh,
                             in_specs=(P(), P("dp")),
                             out_specs=P())(p, b)

        pol = PrecisionPolicy(default_splits=6, min_dim=64)
        single = Calibrator(model.loss, pol)
        loss1 = single.run(params, batch)
        sharded = Calibrator(sharded_loss, pol)
        loss8 = sharded.run(jax.device_put(params, replicated),
                            jax.device_put(batch, dp))
        assert float(loss8) == pytest.approx(float(loss1), abs=1e-6)

        plan1 = solve_plan(single.result())
        plan8 = solve_plan(sharded.result())
        # The per-shard stats were pmax-shared across the mesh and all
        # plan fields are mesh-invariant: the artifacts match byte for
        # byte (and so do their fingerprints, by construction).
        assert plan8.to_json() == plan1.to_json()
        # Sharded raw names carry the shmap scope; the records do not.
        assert any(n.startswith("shmap0/")
                   for n in sharded.result().site_names)
        assert {r.site for r in sharded.result().records} == \
            {r.site for r in single.result().records}

    @needs8
    def test_step_plan_is_mesh_specific_under_tp(self):
        """The documented caveat, asserted: a ``--target step`` plan
        calibrated single-device does NOT transfer to a tp mesh.

        Tensor parallelism changes the per-shard contraction extents
        (``d_ff/tp``, per-shard head counts), so the traced site set
        disagrees with the plan fingerprint and plan-strict offload
        raises :class:`PlanStaleError` instead of silently running a
        split schedule tuned for different GEMM shapes.  Re-calibrate
        with the same ``--mesh`` (the tune CLI goes through the
        identical 2-D bring-up) to get a plan for the tp program.
        """
        from repro.launch.train import (build_sharded_train_step,
                                        build_train_step)
        from repro.shard import train_mesh_setup
        from repro.train import AdamW

        cfg = get_config("tiny")
        model = Model(cfg)
        opt = AdamW(lr=3e-3)
        params = model.init_params(jax.random.PRNGKey(0))
        state = opt.init(params)
        batch = jnp.asarray(
            SyntheticText(cfg.vocab_size, 64, 8, seed=0).batch(0))

        pol = PrecisionPolicy(default_splits=6, min_dim=64)
        cal = Calibrator(build_train_step(model, opt), pol)
        cal.run(params, state, batch)
        plan = solve_plan(cal.result())

        mesh, bsh, (p2, o2), _ = train_mesh_setup(
            "dp=4,tp=2", 8, cfg, (params, state))
        sharded = build_sharded_train_step(model, opt, mesh)
        with pytest.raises(PlanStaleError):
            offload(sharded, plan=plan).sites(
                p2, o2, jax.device_put(batch, bsh))
