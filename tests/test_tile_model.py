"""Analytic tile-cost model: hand-computed figures + properties.

The model is closed-form (no autotuning), so the unit tests pin its
numbers against figures computed by hand from the documented formulas,
and a property sweep checks every pick is admissible (fits the VMEM
budget, MXU-aligned).  No Pallas import anywhere — the model must work
on hosts without a Pallas build.
"""

import numpy as np
import pytest

from repro.core.ozaki import num_pair_gemms, pair_indices
from repro.kernels import tile_model as tm


class TestHandComputedFigures:
    def test_vmem_bytes_presliced(self):
        # 2 * (bm*bk + bk*bn) int8 double-buffered inputs
        # + 40 * bm*bn: double-buffered f32 hi/lo outputs, int32 k-sum
        # and tile product, the fold's f32 temporaries.
        assert tm.vmem_bytes(128, 128, 128) == \
            2 * (128 * 128 + 128 * 128) + 40 * 128 * 128 == 720896
        assert tm.vmem_bytes(32, 128, 256) == \
            2 * (32 * 256 + 256 * 128) + 40 * 32 * 128

    def test_vmem_bytes_fused(self):
        # Fused streams f32 hi+lo halves (8 B/elem, double-buffered)
        # and holds a 20 B/elem f32 working set for the quantization.
        e = 128 * 128 + 128 * 128
        assert tm.vmem_bytes(128, 128, 128, fused=True) == \
            (2 * 8 + 20) * e + 40 * 128 * 128 == 1835008

    def test_mxu_tile_cycles(self):
        # One 128^3 MAC block per 128 cycles on the 128x128 array.
        assert tm.mxu_tile_cycles(128, 128, 128) == 128
        assert tm.mxu_tile_cycles(256, 512, 128) == 2 * 4 * 1 * 128
        # Sub-array blocks still occupy a full pass.
        assert tm.mxu_tile_cycles(32, 128, 128) == 128

    def test_hbm_bytes_per_step(self):
        assert tm.hbm_bytes_per_step(128, 128, 128) == 32768
        assert tm.hbm_bytes_per_step(128, 128, 128, fused=True) == \
            8 * 32768

    def test_select_128_cube_s6(self):
        # The worked example in the module docstring: at 128^3 the only
        # aligned candidates are bm in {32, 64, 128} x bn=bk=128, and
        # the full 128^3 block wins on cycles-per-flop.
        d = tm.select_tiles(128, 128, 128, 6, dtype="float32")
        assert (d.block_m, d.block_n, d.block_k) == (128, 128, 128)
        assert d.vmem_bytes == 720896
        assert d.mxu_cycles_step == 128
        assert d.pairs == 21
        assert d.kernel_invocations == 21  # 1 * 1 * 21 pairs * 1
        assert d.schedule == "ordered"

    def test_traffic_figures_128_cube_s6(self):
        # elems = 128*128 + 128*128 = 32768 per slice layer (A + B).
        t = tm.traffic(128, 128, 128, 6, 128, 128, 128)
        assert t.slice_read_bytes_v1 == 21 * 32768 == 688128
        assert t.slice_read_bytes_v2 == 6 * 32768 == 196608
        assert t.read_reduction == pytest.approx(3.5)
        assert t.stream_bytes == 21 * 32768  # 21 grid steps
        assert t.out_bytes == 2 * 4 * 128 * 128
        assert t.total_v1 > t.total_v2

    def test_read_reduction_is_s_plus_1_over_2(self):
        for s in range(3, 10):
            t = tm.traffic(256, 256, 256, s, 128, 128, 128)
            assert t.read_reduction == pytest.approx((s + 1) / 2)

    def test_split_cost_figures(self):
        # pairs(s) + s * tax, tax = macs_per_cycle * (2/1024) / B-per-cyc.
        p = tm.DEFAULT_PARAMS
        tax = p.macs_per_cycle * (2.0 / 1024) / p.bytes_per_cycle
        assert tm.split_cost(6) == pytest.approx(21 + 6 * tax)
        assert tm.split_cost(1) == pytest.approx(1 + tax)

    def test_canonical_selection_has_no_shape_totals(self):
        # Canonical picks (m/n unknown) must not carry shape-dependent
        # totals — they'd leak per-shard geometry into plans.
        d = tm.select_tiles(None, 96, None, 4, dtype="float32")
        assert d.kernel_invocations is None
        assert d.traffic_model is None
        # k=96 caps block_k at align_up(96, 128) = 128.
        assert d.block_k == 128


class TestSelectionProperties:
    @pytest.mark.parametrize("fused", [False, True])
    def test_every_pick_fits_vmem_and_alignment(self, fused):
        rng = np.random.default_rng(0)
        for _ in range(40):
            m, k, n = (int(rng.integers(1, 2048)) for _ in range(3))
            s = int(rng.integers(1, 10))
            d = tm.select_tiles(m, k, n, s, fused=fused)
            assert d.vmem_bytes <= tm.DEFAULT_PARAMS.vmem_budget
            assert d.vmem_bytes == tm.vmem_bytes(
                d.block_m, d.block_n, d.block_k, fused=fused)
            assert d.block_m % tm.SUBLANE_INT8 == 0
            assert d.block_n % tm.LANE == 0
            assert d.block_k % tm.LANE == 0
            assert d.kernel_invocations >= d.pairs == num_pair_gemms(s)
            assert d.traffic_model.read_reduction == \
                pytest.approx((s + 1) / 2)

    def test_deterministic(self):
        a = tm.select_tiles(300, 700, 500, 6)
        b = tm.select_tiles(300, 700, 500, 6)
        assert a == b

    def test_explicit_none_dims_ignore_geometry(self):
        # The canonical pick depends on (k, splits, fused) only.
        d1 = tm.select_tiles(None, 4096, None, 6)
        d2 = tm.select_tiles(None, 4096, None, 6, dtype="float64")
        assert (d1.block_m, d1.block_n, d1.block_k) == \
            (d2.block_m, d2.block_n, d2.block_k)


class TestPairSchedule:
    def test_ordered_matches_reference(self):
        for s in (1, 3, 6, 9):
            ii, jj = tm.pair_schedule(s, "ordered")
            ri, rj = pair_indices(s)
            assert list(ii) == list(ri) and list(jj) == list(rj)

    def test_grouped_is_a_permutation(self):
        ii, jj = tm.pair_schedule(6, "grouped")
        ri, rj = pair_indices(6)
        assert sorted(zip(ii, jj)) == sorted(zip(ri, rj))
        # Grouped sorts by A-slice index for block reuse accounting.
        assert list(ii) == sorted(ii)

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            tm.pair_schedule(6, "random")


class TestSplitCost:
    def test_strictly_monotone(self):
        costs = [tm.split_cost(s) for s in range(1, 12)]
        assert all(b > a for a, b in zip(costs, costs[1:]))

    def test_marginal_cost_grows(self):
        # Each extra split adds s+1 more pairs plus one slice tax, so
        # the marginal cost is itself increasing — the property the
        # tuner's greedy marginal analysis relies on.
        marg = [tm.split_cost(s + 1) - tm.split_cost(s)
                for s in range(1, 10)]
        assert all(b > a for a, b in zip(marg, marg[1:]))

    def test_dominated_by_pair_count(self):
        # The slice tax is a small correction, not the driver: v2 is
        # compute-bound (the paper's roofline argument).
        for s in range(1, 10):
            assert 0 < tm.split_cost(s) - num_pair_gemms(s) < 1.0
