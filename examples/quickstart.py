"""Quickstart: tunable-precision INT8 GEMM emulation + automatic offload.

Runs in ~a minute on CPU:
  1. accuracy-vs-splits sweep of the emulated DGEMM (paper Table 1 trend);
  2. the PEAK-profiler analogue: enumerate BLAS-3 sites of an *unmodified*
     JAX function and offload them at a chosen precision, no code changes,
     then tune a single site through its stable structural name;
  3. adaptive split selection (the paper's proposed dynamic tuning);
  4. the backend registry: every engine behind one spec-string dispatch.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import (AdaptiveGemm, PrecisionPolicy, get_backend,
                        measure_splits, offload, ozaki_matmul,
                        predict_splits, site_report)


def accuracy_sweep():
    print("=== 1. DGEMM emulation accuracy vs split count ===")
    rng = np.random.default_rng(0)
    m = k = n = 512
    a = jnp.asarray(rng.standard_normal((m, k)))
    b = jnp.asarray(rng.standard_normal((k, n)))
    ref = a @ b
    denom = jnp.abs(a) @ jnp.abs(b)
    print(f"{'mode':>14s} {'max rel err':>12s}")
    for s in range(3, 10):
        c = ozaki_matmul(a, b, num_splits=s, accumulator="df32",
                         out_dtype=jnp.float64)
        err = float(jnp.max(jnp.abs(c - ref) / denom))
        print(f"  fp64_int8_{s:<2d} {err:12.3e}")


def automatic_offload():
    print("\n=== 2. Automatic BLAS offload (no code changes) ===")

    def legacy_solver(a, b):  # pretend this is someone else's code
        x = jnp.tanh(a @ b)
        for _ in range(2):
            x = x @ b / jnp.linalg.norm(x)
        return jnp.sum(x)

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((384, 384)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((384, 384)), jnp.float32)

    policy = PrecisionPolicy(default_splits=6, min_dim=256)
    print("BLAS-3 sites found by the interceptor:")
    for site in site_report(legacy_solver, policy)(a, b):
        print("  ", site)
    ref = legacy_solver(a, b)
    got = offload(legacy_solver, policy)(a, b)
    print(f"native={float(ref):.8f}  emulated={float(got):.8f}  "
          f"rel err={abs(float(got - ref)) / abs(float(ref)):.2e}")

    # The names printed above are stable policy keys: tune one site.
    tuned = PrecisionPolicy(default_splits=6, min_dim=256,
                            site_splits={"dot0": 9})
    print("per-site override (dot0 -> 9 splits):")
    for site in offload(legacy_solver, tuned).sites(a, b):
        print("  ", site)


def backend_registry():
    print("\n=== 4. One registry, every engine (spec strings) ===")
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((256, 256)))
    b = jnp.asarray(rng.standard_normal((256, 256)))
    ref = a @ b
    denom = jnp.abs(a) @ jnp.abs(b)
    for spec in ("dgemm", "fp64_int8_4", "fp64_int8_8", "adaptive:1e-9"):
        gemm = get_backend(spec)
        err = float(jnp.max(jnp.abs(gemm(a, b, out_dtype=jnp.float64)
                                    - ref) / denom))
        print(f"  {spec:>14s}: max rel err {err:.2e}")


def adaptive():
    print("\n=== 3. Tunable precision: adaptive split selection ===")
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((256, 256)))
    b = jnp.asarray(rng.standard_normal((256, 256)))
    for tol in (1e-4, 1e-8, 1e-12):
        s_pred = predict_splits(a, b, tol)
        s_meas, est = measure_splits(a, b, tol)
        print(f"  target {tol:.0e}: predicted s={s_pred}, "
              f"measured s={s_meas} (err est {est:.2e})")
    gemm = AdaptiveGemm(target_rel=1e-9)
    gemm(a, b, site="tau")
    print(f"  AdaptiveGemm chose s={gemm.sites['tau'].splits} for site 'tau'")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    accuracy_sweep()
    automatic_offload()
    adaptive()
    backend_registry()
