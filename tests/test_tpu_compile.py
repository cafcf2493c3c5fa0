"""Compile the main path's programs for a described TPU v5e (no chip).

Each test compiles at a real width with the TPU compiler, for a chip
that is described and not attached, so a kernel Mosaic refuses, an f64
op XLA:TPU cannot lower, or a program that does not fit fails here
instead of on the chip.  Nothing runs: results and times come only from
a chip run (``chip_smoke.py``).

The topology is described inside a module fixture, never at import
time: only one process at a time may load the TPU library, and every
test worker imports this file.  Keep these tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PrecisionPolicy, get_backend, ozaki_matmul
from repro.core.ozaki import real_pair_matmul
from repro.core.ozaki import slice_matrix
from repro.kernels import ops, tile_model

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES
    return compiled.as_text()


@pytest.mark.parametrize("fused", [False, True], ids=["v2", "fused"])
@pytest.mark.parametrize("m,k,n,s", [
    (4096, 4096, 4096, 6),
    (512, 960, 2560, 4),  # the smollm_360m MLP up-projection site
], ids=["4096sq", "smollm_mlp"])
def test_kernel_compiles_to_mosaic(one_chip, fused, m, k, n, s):
    text = _compile(
        lambda a, b: ops.ozaki_matmul(a, b, num_splits=s,
                                      fuse_slicing=fused),
        one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused,bm,bn,bk", [
    (False, 64, 512, 512),   # the tightest blocks for the model
    (True, 32, 512, 512),
    (True, 128, 512, 512),   # the largest fused block in the budget
])
def test_tile_model_bounds_mosaic_vmem(one_chip, monkeypatch, fused, bm,
                                       bn, bk):
    # The kernel compiles with the model's footprint as its whole VMEM
    # limit, so select_tiles' budget check holds on the chip.
    from jax.experimental.pallas import tpu as pltpu

    limit = tile_model.vmem_bytes(bm, bn, bk, fused=fused)
    monkeypatch.setattr(ops, "_compiler_params", lambda *a, **kw:
                        pltpu.CompilerParams(vmem_limit_bytes=limit))
    jax.clear_caches()
    m, k, n = bm, 2 * bk, bn
    if fused:
        fn = lambda ah, al, bh, bl: ops.split_gemm_pallas_fused(
            ah, al, bh, bl, 6, block_m=bm, block_n=bn, block_k=bk)
        shapes = [((m, k), jnp.float32)] * 2 + [((k, n), jnp.float32)] * 2
    else:
        fn = lambda a, b: ops.split_gemm_pallas(
            a, b, 6, block_m=bm, block_n=bn, block_k=bk)
        shapes = [((6, m, k), jnp.int8), ((6, k, n), jnp.int8)]
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)
    jax.clear_caches()


def test_v1_kernel_compiles_to_mosaic(one_chip):
    def v1(a, b):
        a_sl, _ = slice_matrix(a, 6, axis=1)
        b_sl, _ = slice_matrix(b, 6, axis=0)
        return ops.split_gemm_pallas_v1(a_sl, b_sl, 6, block_m=256,
                                        block_n=512, block_k=512)

    blk = ((1024, 1024), jnp.float32)
    assert "tpu_custom_call" in _compile(v1, one_chip, blk, blk)


@pytest.mark.parametrize("accumulator", ["df32", "f64"])
def test_jnp_f64_path_compiles(one_chip, accumulator):
    # f64 sigma used to go through ldexp, which XLA:TPU refuses.
    _compile(lambda a, b: ozaki_matmul(a, b, num_splits=7,
                                       accumulator=accumulator),
             one_chip, ((2048, 2048), jnp.float64),
             ((2048, 2048), jnp.float64))


def test_must_real_pair_gemm_compiles(one_chip):
    # MuST's block GEMM as it reaches the device: four real f64 GEMMs
    # on (re, im) pairs, never a complex128 operand.
    backend = get_backend("fp64_int8_9",
                          PrecisionPolicy(accumulator="f64"))

    def gemm(ar, ai, br, bi):
        return real_pair_matmul(
            lambda x, y, dt: backend(x, y, out_dtype=dt), (ar, ai),
            (br, bi), jnp.float64)

    blk = ((1024, 1024), jnp.float64)
    text = _compile(gemm, one_chip, blk, blk, blk, blk)
    assert "c128" not in text


@pytest.mark.parametrize("form", ["rows", "contraction"])
def test_grouped_ozaki_compiles_to_int8_ragged_dots(one_chip, form):
    # Moonlight's expert up-projection on one chip's 8 experts, its rows
    # at their bound (2048 tokens x 6 picks): XLA:TPU takes the int8
    # grouped products into int32 (a ragged-dot kernel, no dense loop).
    from repro.core.ozaki import (RAGGED_CONTRACTION, RAGGED_ROWS,
                                  ozaki_ragged_dot)

    rows, d, f, g = 12288, 2048, 1408, 8
    dims = RAGGED_ROWS if form == "rows" else RAGGED_CONTRACTION
    rhs = (g, d, f) if form == "rows" else (rows, f)

    def grouped(a, b, sizes):
        return ozaki_ragged_dot(a, b, sizes, dims, num_splits=4)

    shapes = [((rows, d), jnp.float32), (rhs, jnp.float32),
              ((g,), jnp.int32)]
    text = _compile(grouped, one_chip, *shapes)
    assert "ragged-dot-metadata" in text
    assert text.count("s8[") > 0
    # Under the cell's `highest`, the int8 grouped products keep their
    # own precision: Mosaic refuses the kernel at f32 contract precision
    # on the chip, after this compile has passed.
    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(grouped).lower(*[
            jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]).as_text()
    ragged = [line for line in lowered.splitlines()
              if "chlo.ragged_dot" in line]
    assert len(ragged) == 4
    assert not any("HIGHEST" in line for line in ragged)
