"""Pallas split-GEMM kernel vs the jnp reference path (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ozaki_matmul as ozaki_ref

pytest.importorskip("jax.experimental.pallas")

from repro.core.ozaki import slice_matrix  # noqa: E402
from repro.kernels import ops, slicing  # noqa: E402


def _pair(m, k, n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((m, k)), dtype),
            jnp.asarray(rng.standard_normal((k, n)), dtype))


class TestPallasEquivalence:
    @pytest.mark.parametrize("num_splits", [3, 6])
    def test_matches_df32_reference_bitwise(self, num_splits):
        # Same slicing, same weights, same compensated accumulation:
        # the kernel must agree with the jnp df32 path to the last bit.
        a, b = _pair(128, 128, 128, 0)
        c_pal = ops.ozaki_matmul(a, b, num_splits=num_splits,
                                 interpret=True, out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=num_splits,
                          accumulator="df32", out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c_pal - c_ref))) == 0.0

    def test_padded_rectangular(self):
        # Shapes that don't divide the tile exercise the zero-padding
        # path (zero slices contribute exactly nothing).
        a, b = _pair(100, 200, 60, 1)
        c_pal = ops.ozaki_matmul(a, b, num_splits=5, interpret=True,
                                 block_m=64, block_n=64, block_k=64,
                                 out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=5, accumulator="df32",
                          out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c_pal - c_ref))) == 0.0

    def test_accuracy_vs_native(self):
        a, b = _pair(128, 128, 128, 2)
        ref = a.astype(jnp.float64) @ b.astype(jnp.float64)
        denom = (jnp.abs(a).astype(jnp.float64)
                 @ jnp.abs(b).astype(jnp.float64))
        c = ops.ozaki_matmul(a, b, num_splits=6, interpret=True,
                             out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c - ref) / denom)) < 1e-9

    def test_rejects_complex(self):
        a = jnp.ones((32, 32), jnp.complex64)
        with pytest.raises(NotImplementedError):
            ops.ozaki_matmul(a, a, num_splits=3, interpret=True)


class TestV2BitIdentity:
    """v2 == jnp df32 reference to the last bit, everywhere it claims."""

    @pytest.mark.parametrize("m,k,n", [(37, 130, 51), (100, 200, 60),
                                       (64, 96, 64), (1, 129, 1)])
    @pytest.mark.parametrize("num_splits", [3, 4, 5, 9])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_odd_shapes_all_splits(self, m, k, n, num_splits, dtype):
        a, b = _pair(m, k, n, 7, dtype)
        c_pal = ops.ozaki_matmul(a, b, num_splits=num_splits,
                                 interpret=True, out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=num_splits,
                          accumulator="df32", out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c_pal - c_ref))) == 0.0

    @pytest.mark.parametrize("k,edges", [
        # One run per shift: a run's first pair opens (1), its last
        # folds (2), a lone pair does both (3).
        (960, [3, 1, 2, 1, 0, 2, 1, 0, 0, 2]),
        # Runs cut at two pairs (fold_runs' int32 cap).
        (2**19 + 2**18, [3, 1, 2, 1, 2, 3, 1, 2, 1, 2]),
        (2**20, [3] * 10),
    ])
    def test_schedule_marks_the_fold_runs(self, k, edges):
        from repro.core.ozaki import fold_runs

        *_, edge = ops._pair_schedule_arrays(4, 6, k)
        assert edge.tolist() == edges
        assert [p for p, e in enumerate(edges) if e & 1] == \
            [start for start, _ in fold_runs(4, k)]

    def test_v1_matches_v2_bitwise(self):
        # Same slices, same schedule, same TwoSum stream: the legacy
        # pair-materializing kernel and the pair-indexing one must
        # agree exactly (the refactor changed data movement only).
        a, b = _pair(100, 200, 60, 8)
        a_sl, _ = slice_matrix(a, 5, axis=1)
        b_sl, _ = slice_matrix(b, 5, axis=0)
        hi2, lo2 = ops.split_gemm_pallas(a_sl, b_sl, 5, interpret=True)
        hi1, lo1 = ops.split_gemm_pallas_v1(a_sl, b_sl, 5,
                                            interpret=True)
        assert float(jnp.max(jnp.abs(hi1 - hi2))) == 0.0
        assert float(jnp.max(jnp.abs(lo1 - lo2))) == 0.0

    def test_tiny_shapes_round_up_to_aligned_tiles(self):
        # Shapes below one MXU tile must pad up to (32, 128), never
        # shrink the block below alignment (the old min() clamp bug).
        a, b = _pair(20, 20, 20, 9)
        c_pal = ops.ozaki_matmul(a, b, num_splits=4, interpret=True,
                                 out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=4, accumulator="df32",
                          out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c_pal - c_ref))) == 0.0

    def test_model_picked_blocks_match_explicit(self):
        # Letting the tile model choose must not change the numerics.
        a, b = _pair(64, 96, 64, 10)
        auto = ops.ozaki_matmul(a, b, num_splits=4, interpret=True)
        manual = ops.ozaki_matmul(a, b, num_splits=4, interpret=True,
                                  block_m=32, block_n=128, block_k=128)
        assert float(jnp.max(jnp.abs(auto - manual))) == 0.0

    def test_grad_through_offload_bit_identical(self):
        # The pallas_int8 backend inside the offload transform, through
        # jax.grad, must match the jnp fp64_int8 path exactly.
        from repro.core import PrecisionPolicy, offload

        a, b = _pair(64, 96, 48, 11)

        def f(a, b):
            return (a @ b).sum()

        g_pal = jax.grad(offload(f, PrecisionPolicy(
            backend="pallas_int8", default_splits=4, min_dim=16)))(a, b)
        g_ref = jax.grad(offload(f, PrecisionPolicy(
            backend="fp64_int8", default_splits=4, min_dim=16)))(a, b)
        assert bool(jnp.all(g_pal == g_ref))


class TestAccumulatorValidation:
    """Satellite fix: unknown accumulators raise, never silently drop."""

    @pytest.mark.parametrize("fuse", [False, True])
    def test_unsupported_accumulator_raises(self, fuse):
        a, b = _pair(32, 32, 32, 12)
        with pytest.raises(ValueError, match="accumulator"):
            ops.ozaki_matmul(a, b, num_splits=3, accumulator="f64",
                             interpret=True, fuse_slicing=fuse)

    def test_none_means_backend_default(self):
        a, b = _pair(32, 32, 32, 12)
        got = ops.ozaki_matmul(a, b, num_splits=3, accumulator=None,
                               interpret=True)
        want = ops.ozaki_matmul(a, b, num_splits=3, accumulator="df32",
                                interpret=True)
        assert float(jnp.max(jnp.abs(got - want))) == 0.0


class TestFusedSlicing:
    """In-kernel quantization vs the shared slicing spec."""

    @pytest.mark.parametrize("m,k,n", [(37, 130, 51), (64, 96, 64)])
    @pytest.mark.parametrize("num_splits", [3, 4, 6, 9])
    def test_fused_f32_bitwise_vs_reference(self, m, k, n, num_splits):
        # For f32 sources lo == 0, the pair recurrence collapses to the
        # core slicing recurrence, and the fused path must equal the
        # jnp df32 reference exactly.
        a, b = _pair(m, k, n, 13)
        c_fus = ops.ozaki_matmul(a, b, num_splits=num_splits,
                                 interpret=True, fuse_slicing=True,
                                 out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=num_splits,
                          accumulator="df32", out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(c_fus - c_ref))) == 0.0

    @pytest.mark.parametrize("num_splits", [4, 8])
    def test_fused_f64_accuracy_vs_core(self, num_splits):
        # For f64 sources the f32-pair recurrence may pick a different
        # (value-preserving) slice decomposition than the core f64
        # recurrence, so the core comparison is an accuracy bound at
        # the pair's ~48-bit budget, not bit-identity.
        a, b = _pair(37, 130, 51, 14, jnp.float64)
        c_fus = ops.ozaki_matmul(a, b, num_splits=num_splits,
                                 interpret=True, fuse_slicing=True,
                                 out_dtype=jnp.float64)
        c_ref = ozaki_ref(a, b, num_splits=num_splits,
                          accumulator="df32", out_dtype=jnp.float64)
        denom = jnp.abs(a) @ jnp.abs(b)
        assert float(jnp.max(jnp.abs(c_fus - c_ref) / denom)) < 1e-12

    @pytest.mark.parametrize("num_splits", [4, 9])
    def test_fused_f64_bitwise_vs_its_jnp_spec(self, num_splits):
        # The fused kernel's spec for f64 sources is slice_matrix_fused:
        # feeding its slices through the pre-sliced v2 kernel at the
        # same blocks must reproduce the fused output exactly.
        from repro.kernels import tile_model

        s = num_splits
        a, b = _pair(37, 130, 51, 15, jnp.float64)
        d = tile_model.select_tiles(37, 130, 51, s, fused=True)
        c_fus = ops.ozaki_matmul(a, b, num_splits=s, interpret=True,
                                 fuse_slicing=True,
                                 out_dtype=jnp.float64)
        a_sl, sig_a = slicing.slice_matrix_fused(a, s, axis=1)
        b_sl, sig_b = slicing.slice_matrix_fused(b, s, axis=0)
        hi, lo = ops.split_gemm_pallas(
            a_sl, b_sl, s, interpret=True, block_m=d.block_m,
            block_n=d.block_n, block_k=d.block_k)
        deferred = 2.0 ** (-slicing.SLICE_BITS * (s + 1))
        c_spec = ((hi.astype(jnp.float64) + lo.astype(jnp.float64))
                  * deferred * sig_a[:, None] * sig_b[None, :])
        assert float(jnp.max(jnp.abs(c_fus - c_spec))) == 0.0
        # And it still lands within the split count's emulation
        # accuracy (~2**(-slice_bits*(s-1)) relative).
        ref = a @ b
        denom = jnp.abs(a) @ jnp.abs(b)
        bound = 1e-5 if s == 4 else 1e-11
        assert float(jnp.max(jnp.abs(c_fus - ref) / denom)) < bound

    def test_fused_backend_spec_resolves_and_computes(self):
        from repro.core import get_backend

        a, b = _pair(64, 96, 48, 16)
        fused = get_backend("pallas_int8_4:fused")
        plain = get_backend("pallas_int8_4")
        got = fused(a, b, out_dtype=jnp.float64)
        want = plain(a, b, out_dtype=jnp.float64)
        assert float(jnp.max(jnp.abs(got - want))) == 0.0

    def test_slice_matrix_fused_f32_equals_core(self):
        x = jnp.asarray(
            np.random.default_rng(17).standard_normal((40, 70)),
            jnp.float32)
        sl_f, sig_f = slicing.slice_matrix_fused(x, 5, axis=1)
        sl_c, sig_c = slice_matrix(x, 5, axis=1)
        assert bool(jnp.all(sl_f == sl_c))
        assert bool(jnp.all(sig_f == sig_c))
