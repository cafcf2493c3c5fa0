"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Wall-clock numbers are CPU
(this container); TPU-side performance is reported through the roofline
model over the dry-run artifacts (bench_roofline), since the paper's own
performance table (§4: 20.35 vs 62.52 TFLOPS at split 6) is a hardware
measurement we map to the v5e peak model.

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np


def _skip_reason(e) -> str:
    """One CSV-safe clause explaining a degraded row.

    The ``derived`` column is ``;``-separated ``key=value`` pairs on a
    ``,``-separated CSV line, so the reason must not contain either —
    collapse them (and newlines) to spaces and bound the length.
    """
    msg = " ".join(str(e).replace(",", " ").replace(";", " ").split())
    return (msg[:77] + "...") if len(msg) > 80 else (msg or "unknown")


def _timeit(fn, *args, reps=5) -> float:
    jax.block_until_ready(fn(*args))  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        # Block every rep: JAX dispatch is async, so timing only the
        # final block would measure dispatch cost, not compute.
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def bench_table1_must(quick: bool) -> list:
    """Paper Table 1: G(z) accuracy vs split count on the MuST workload."""
    from repro.apps import must as MU

    n = 192 if quick else 384
    cfg = MU.MustConfig(n=n, block=n // 4, n_energies=8 if quick else 16)
    system = MU.build_system(cfg)
    t0 = time.perf_counter()
    ref = MU.run_contour(cfg, "dgemm", system)
    t_ref = (time.perf_counter() - t0) * 1e6 / cfg.n_energies
    rows = [f"must_dgemm_contour_point,{t_ref:.0f},etot={ref['etot']:.6f}"]
    for s in ([3, 5, 7] if quick else [3, 4, 5, 6, 7, 8, 9]):
        t0 = time.perf_counter()
        test = MU.run_contour(cfg, f"fp64_int8_{s}", system)
        dt = (time.perf_counter() - t0) * 1e6 / cfg.n_energies
        err = MU.relative_errors(ref, test)
        rows.append(
            f"must_int8_{s}_contour_point,{dt:.0f},"
            f"max_real={err['max_real']:.3e};max_imag={err['max_imag']:.3e};"
            f"d_etot={err['d_etot']:.3e}")
    return rows


def bench_gemm_accuracy(quick: bool) -> list:
    """Emulation accuracy ladder on a plain DGEMM (Table 1 trend).

    Engines are resolved through the backend registry by spec string —
    the same dispatch path the interceptor and the MuST app use.
    """
    from repro.core import get_backend

    rng = np.random.default_rng(0)
    n = 256 if quick else 512
    a = jnp.asarray(rng.standard_normal((n, n)))
    b = jnp.asarray(rng.standard_normal((n, n)))
    ref = a @ b
    denom = jnp.abs(a) @ jnp.abs(b)
    rows = []
    for spec in [f"fp64_int8_{s}" for s in (3, 5, 7, 9)] + ["dgemm"]:
        backend = get_backend(spec)
        fn = lambda a, b: backend(a, b, out_dtype=jnp.float64)  # noqa: E731
        us = _timeit(jax.jit(fn), a, b)
        err = float(jnp.max(jnp.abs(fn(a, b) - ref) / denom))
        rows.append(f"gemm_{spec}_{n},{us:.0f},maxrel={err:.3e}")
    return rows


def bench_gemm_throughput_model(quick: bool) -> list:
    """Paper §4 analogue: emulated-vs-native throughput at 2048^2.

    GH200 measured: split-6 = 20.35 TFLOPS vs native FP64 = 62.52.
    v5e modeled: native FP64 = 0 (no hardware); emulated split-s
    effective FP64-equivalent TFLOPS = int8_peak / (s(s+1)/2).
    """
    rows = []
    int8_peak = 394e12
    for s in range(3, 10):
        n_gemms = s * (s + 1) / 2
        eff = int8_peak / n_gemms
        gh = "20.35" if s == 6 else "n/a"
        rows.append(f"v5e_fp64eq_tflops_int8_{s},0,"
                    f"modeled={eff/1e12:.2f}TFLOPS;gh200_paper={gh}")
    rows.append("v5e_fp64_native,0,modeled=0TFLOPS(no FP64 unit);"
                "gh200_paper=62.52")
    return rows


def bench_kernel_pallas(quick: bool) -> list:
    """Pallas kernel (interpret) vs pure-jnp path, same split count."""
    from repro.core import ozaki_matmul
    from repro.kernels.tile_model import select_tiles

    rng = np.random.default_rng(1)
    n = 128 if quick else 256
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    us_jnp = _timeit(
        jax.jit(lambda a, b: ozaki_matmul(a, b, num_splits=6)), a, b)
    rows = [f"ozaki6_jnp_{n},{us_jnp:.0f},backend=xla_cpu"]
    # The tile shapes the v2 kernel actually runs with come from the
    # analytic model, not a hard-coded default — report them so a
    # model regression shows up in the row payload, not just timing.
    d = select_tiles(n, n, n, 6, dtype="float32")
    tiles = f"tiles={d.block_m}x{d.block_n}x{d.block_k}"
    try:
        # Pallas interpret mode has no hardware requirements but can be
        # unavailable (no pallas in the jaxlib build, Mosaic-only
        # wheels): skip the row with a reason instead of failing the
        # whole bench.  The registry backend picks interpret mode
        # automatically off-TPU.
        from repro.core import get_backend

        pallas6 = get_backend("pallas_int8_6")
        us_pal = _timeit(lambda a, b: pallas6(a, b), a, b, reps=2)
        rows.append(f"ozaki6_pallas_interpret_{n},{us_pal:.0f},"
                    f"backend=interpret(correctness-only);{tiles}")
    except Exception as e:  # noqa: BLE001 - degrade, don't fail
        rows.append(f"ozaki6_pallas_interpret_{n},0,"
                    f"skipped={type(e).__name__};{tiles};"
                    f"skip_reason={_skip_reason(e)}")
    return rows


def bench_kernel_v2(quick: bool) -> list:
    """v2 split-GEMM data movement: modeled HBM traffic + invocations.

    The v2 kernel's O(s) slice-read claim, made gateable: the analytic
    traffic model (``repro.kernels.tile_model.traffic``) computes the
    slice-array bytes the v1 pair-materializing kernel reads
    (``hbm_bytes_moved_v1``, O(s^2) in the pair count) against what v2
    reads indexing the un-materialized ``(s,m,k)``/``(s,k,n)`` stacks
    (``hbm_bytes_moved``, O(s)), with ``hbm_read_reduction`` their
    ratio — exactly ``(s+1)/2``, i.e. 3.5 at s=6 — and
    ``kernel_invocations`` the pair-schedule length ``s(s+1)/2``.
    Model deriveds are computed even when the kernel itself cannot run
    (no Pallas in the build): compare_baseline's derived checks gate
    the data-movement claim regardless of the timing row's skip state.
    """
    from repro.kernels.tile_model import select_tiles, traffic

    s, n = 6, 128
    d = select_tiles(n, n, n, s, dtype="float32")
    t = traffic(n, n, n, s, d.block_m, d.block_n, d.block_k)
    deriveds = (f"hbm_bytes_moved={t.slice_read_bytes_v2};"
                f"hbm_bytes_moved_v1={t.slice_read_bytes_v1};"
                f"hbm_read_reduction={t.read_reduction:.2f};"
                f"kernel_invocations={d.kernel_invocations};"
                f"pairs={d.pairs};"
                f"tiles={d.block_m}x{d.block_n}x{d.block_k}")
    try:
        from repro.core import ozaki_matmul
        from repro.kernels import ops

        rng = np.random.default_rng(5)
        a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)

        def v2(a, b):
            return ops.ozaki_matmul(a, b, num_splits=s, interpret=True)

        us = _timeit(v2, a, b, reps=2)
        ref = ozaki_matmul(a, b, num_splits=s)
        bitwise = int(bool(jnp.all(v2(a, b) == ref)))
        rows = [f"kernel_v2_s{s}_{n},{us:.0f},"
                f"{deriveds};bitwise_vs_jnp={bitwise}"]
    except Exception as e:  # noqa: BLE001 - degrade, don't fail
        rows = [f"kernel_v2_s{s}_{n},0,skipped={type(e).__name__};"
                f"{deriveds};skip_reason={_skip_reason(e)}"]
    return rows


def bench_intercept(quick: bool) -> list:
    """Automatic-offload interception cost (trace+rewrite, amortized)."""
    from repro.core import PrecisionPolicy, offload

    rng = np.random.default_rng(2)
    n = 256
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)

    def f(a, b):
        return jnp.sum(jnp.tanh(a @ b) @ b)

    pol = PrecisionPolicy(default_splits=4, min_dim=128)
    t0 = time.perf_counter()
    wrapped = jax.jit(offload(f, pol))
    jax.block_until_ready(wrapped(a, b))
    trace_us = (time.perf_counter() - t0) * 1e6
    us = _timeit(wrapped, a, b)
    return [f"offload_first_call,{trace_us:.0f},includes_trace_and_compile",
            f"offload_steady_state,{us:.0f},per_call"]


def bench_offload_batched(quick: bool) -> list:
    """Batched (rank-3) offload: vmapped contour-point GEMMs.

    A MuST-shaped batch — one GEMM per energy point ``z_k`` of the
    contour, all issued as a single batched ``dot_general`` — exercises
    the transform's reshape/vmap batched path end to end.
    """
    from repro.core import PrecisionPolicy, offload

    rng = np.random.default_rng(3)
    n = 128 if quick else 192
    n_energies = 8 if quick else 16
    h = jnp.asarray(rng.standard_normal((n, n)))
    h = 0.5 * (h + h.T)
    z = jnp.linspace(0.1, 1.3, n_energies) + 0.03j
    mats = z[:, None, None] * jnp.eye(n) - h.astype(jnp.complex128)
    blocks = jnp.asarray(rng.standard_normal((n_energies, n, n)),
                         jnp.complex128)

    def contour_gemms(mats, blocks):
        return jax.vmap(jnp.matmul)(mats, blocks)

    pol = PrecisionPolicy(default_splits=6, min_dim=64,
                          accumulator="f64")
    wrapped = jax.jit(offload(contour_gemms, pol))
    native = jax.jit(contour_gemms)
    ref = native(mats, blocks)
    got = wrapped(mats, blocks)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    us_emul = _timeit(wrapped, mats, blocks)
    us_nat = _timeit(native, mats, blocks)
    return [
        f"offload_batched_int8_6,{us_emul:.0f},"
        f"batch={n_energies};n={n};maxrel={err:.3e}",
        f"offload_batched_native,{us_nat:.0f},batch={n_energies};n={n}",
    ]


def bench_offload_sharded(quick: bool) -> list:
    """Sharded (shard_map) offload: the multi-device dispatch path.

    A data-parallel GEMM chain under ``shard_map`` over every visible
    device (1 on a plain runner, 8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), offloaded
    through the registry.  The derived column carries the offloaded-
    site count so sharded sites silently falling back to native fail
    the bench-regression gate, not just the timing.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import PrecisionPolicy, offload
    from repro.shard import build_mesh

    ndev = jax.device_count()
    mesh = build_mesh(f"dp={ndev}")
    n = 192 if quick else 384
    rows_per_shard = 128
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((ndev * rows_per_shard, n)))
    b = jnp.asarray(rng.standard_normal((n, n)))

    def fn(a, b):
        def per_shard(a_s, b_s):
            return jnp.tanh(a_s @ b_s) @ b_s

        return shard_map(per_shard, mesh=mesh,
                         in_specs=(P("dp"), P(None)),
                         out_specs=P("dp"))(a, b)

    pol = PrecisionPolicy(default_splits=6, min_dim=64,
                          accumulator="f64")
    wrapped = offload(fn, pol)
    n_on = sum(s.offloaded for s in wrapped.sites(a, b))
    emul = jax.jit(wrapped)
    native = jax.jit(fn)
    ref = native(a, b)
    err = float(jnp.max(jnp.abs(emul(a, b) - ref))
                / jnp.max(jnp.abs(ref)))
    us_emul = _timeit(emul, a, b)
    us_nat = _timeit(native, a, b)
    return [
        f"offload_sharded_int8_6,{us_emul:.0f},"
        f"devices={ndev};n={n};offloaded_sites={n_on};maxrel={err:.3e}",
        f"offload_sharded_native,{us_nat:.0f},devices={ndev};n={n}",
    ]


def bench_train_2d(quick: bool) -> list:
    """2-D (dp x tp) train step: overlapped vs blocking grad reduce.

    The same sharded train step twice over the largest canonical
    ``dp=N,tp=M`` mesh the visible devices allow (tp=2 when the tiny
    config's head counts divide and >= 2 devices are up, dp = the
    rest): once with the default bucketed all-reduce that XLA can
    overlap with backward GEMMs, once with the ``optimization_barrier``
    reference that forces every gradient to exist before one full-tree
    psum.  The gate ratios overlapped/blocking — overlap must never
    make the step *slower*.  The derived column records the bucket
    count and bytes per psum so a bucketing regression (everything
    collapsing into one bucket, or per-leaf fragmentation) fails the
    gate even when the timing noise hides it.
    """
    from repro.configs import get_config
    from repro.launch.train import build_sharded_train_step
    from repro.models import Model
    from repro.shard import bucket_stats, train_mesh_setup
    from repro.train import AdamW, SyntheticText

    cfg = get_config("tiny")
    ndev = jax.device_count()
    tp = 2 if ndev % 2 == 0 else 1
    dp = max(ndev // tp, 1)
    batch = max(4, dp)
    model, opt = Model(cfg), AdamW(lr=3e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    mesh, bsh, (params, state), _ = train_mesh_setup(
        f"dp={dp},tp={tp}", batch, cfg, (params, state))
    data = jax.device_put(
        jnp.asarray(SyntheticText(cfg.vocab_size, 64, batch,
                                  seed=0).batch(0)), bsh)

    # A small bucket so even the tiny tree splits into several psums —
    # the quick bench must exercise the multi-bucket path, not degrade
    # to one all-encompassing psum.
    bucket_bytes = 256 << 10
    n_buckets, sizes = bucket_stats(params, bucket_bytes)
    bpp = int(sum(sizes) / max(n_buckets, 1))

    rows = []
    for mode in ("bucketed", "blocking"):
        step = jax.jit(build_sharded_train_step(
            model, opt, mesh, grad_reduce=mode,
            bucket_bytes=bucket_bytes))
        us = _timeit(step, params, state, data, reps=3)
        tag = "overlapped" if mode == "bucketed" else mode
        rows.append(
            f"train_2d_{tag},{us:.0f},devices={ndev};dp={dp};tp={tp};"
            f"n_buckets={n_buckets};bytes_per_psum={bpp}")
    return rows


def bench_roofline(quick: bool) -> list:
    """§Roofline summary from the dry-run artifacts (if present)."""
    try:
        from repro.analysis.roofline import analyze_cell
    except Exception as e:  # noqa: BLE001 - degrade, don't fail
        return [f"roofline_skipped,0,skipped={type(e).__name__};"
                f"skip_reason=analysis unavailable: {_skip_reason(e)}"]

    rows = []
    outdir = Path("runs/dryrun")
    if not outdir.exists():
        return ["roofline_skipped,0,skipped=1;"
                "skip_reason=no runs/dryrun artifacts"]
    sel = sorted(outdir.glob("*pod16x16.json"))
    if not sel:
        return ["roofline_skipped,0,skipped=1;"
                "skip_reason=no *pod16x16.json artifacts in "
                "runs/dryrun"]
    for j in sel[: 6 if quick else 1000]:
        try:
            r = analyze_cell(j)
            rows.append(
                f"roofline_{r.cell},0,"
                f"compute={r.compute_s:.3f}s;memory={r.memory_s:.3f}s;"
                f"collective={r.collective_s:.3f}s;bound={r.dominant}")
        except Exception as e:
            rows.append(f"roofline_{j.stem},0,parse_error={e!r}")
    return rows


def bench_lm_step(quick: bool) -> list:
    """LM train-step wall time per backend (tiny config, CPU).

    The transformer workload through the same registry dispatch the
    examples use: one full train step (loss forward + backward + AdamW)
    native vs. offloaded at split 4/6.  The derived column carries the
    offloaded-site count so a silent routing regression (sites falling
    back to native) fails the bench-regression gate, not just the
    timing.
    """
    from repro.configs import get_config
    from repro.core import PrecisionPolicy, offload
    from repro.launch.train import build_train_step
    from repro.models import Model
    from repro.train import AdamW, SyntheticText

    cfg = get_config("tiny")
    model = Model(cfg)
    opt = AdamW(lr=3e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    batch = jnp.asarray(
        SyntheticText(cfg.vocab_size, 64, 4, seed=0).batch(0))
    step = build_train_step(model, opt)

    us = _timeit(jax.jit(step), params, state, batch, reps=3)
    rows = [f"lm_step_native,{us:.0f},tiny;tokens=256"]
    # The emulated rows run with per-site telemetry ON (the repro.obs
    # site-event hook counting every executed site into a registry) so
    # the existing lm_step_fp64_int8_4/lm_step_native ratio gate also
    # bounds the observability overhead — if the hook ever gets
    # expensive, the bench-regression gate catches it.
    from repro.obs import Registry

    for s in (4,) if quick else (4, 6):
        registry = Registry()
        pol = PrecisionPolicy(backend=f"fp64_int8_{s}",
                              default_splits=s, min_dim=128)
        wrapped = offload(
            step, pol,
            on_site_event=lambda p: registry.counter(
                "site_exec", site=p["site"]).inc())
        n_on = sum(site.offloaded
                   for site in wrapped.sites(params, state, batch))
        us = _timeit(jax.jit(wrapped), params, state, batch, reps=3)
        jax.effects_barrier()  # drain async site-event callbacks
        n_events = int(sum(
            m["value"] for m in registry.snapshot()
            if m["name"] == "site_exec"))
        rows.append(f"lm_step_fp64_int8_{s},{us:.0f},"
                    f"tiny;tokens=256;offloaded_sites={n_on};"
                    f"site_events={n_events}")
    return rows


def bench_tuned_plan(quick: bool) -> list:
    """Tuned precision plan vs uniform splits on the LM train step.

    The paper's pitch, measured: calibrate the train step, solve the
    cost-optimal per-site split assignment, and compare against
    uniform ``fp64_int8_6`` — the tuned plan must issue *fewer* INT8
    GEMMs per step (``saved_int8_gemms`` derived, gated by
    compare_baseline) at equal-or-better end-to-end loss error vs the
    native step (``err_ok`` derived, also gated).
    """
    from repro.configs import get_config
    from repro.core import PrecisionPolicy, offload
    from repro.launch.train import build_train_step
    from repro.models import Model
    from repro.train import AdamW, SyntheticText
    from repro.tune import Calibrator, count_int8_gemms, solve_plan

    cfg = get_config("tiny")
    model = Model(cfg)
    opt = AdamW(lr=3e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    data = SyntheticText(cfg.vocab_size, 64, 4, seed=0)
    batch = jnp.asarray(data.batch(0))
    step = build_train_step(model, opt)

    uniform_pol = PrecisionPolicy(backend="fp64_int8",
                                  default_splits=6, min_dim=128)
    cal = Calibrator(step, uniform_pol)
    cal.run(params, state, batch)
    plan = solve_plan(cal.result())
    tuned = offload(step, PrecisionPolicy.from_plan(plan), plan=plan)
    uniform = offload(step, uniform_pol)
    n_tuned = count_int8_gemms(tuned.sites(params, state, batch))
    n_uniform = count_int8_gemms(uniform.sites(params, state, batch))

    def run_steps(fn, n=2):
        p, s = params, state
        for i in range(n):
            p, s, loss = fn(p, s, jnp.asarray(data.batch(i)))
        return float(loss)

    loss_native = run_steps(jax.jit(step))
    d_tuned = abs(run_steps(jax.jit(tuned)) - loss_native)
    d_uniform = abs(run_steps(jax.jit(uniform)) - loss_native)
    # "Equal or better": both emulation errors sit in f32 roundoff
    # noise; the tuned plan passes if it is within noise of uniform.
    err_ok = int(d_tuned <= max(4.0 * d_uniform, 1e-4))
    us = _timeit(jax.jit(tuned), params, state, batch, reps=3)
    return [
        f"tuned_plan_step,{us:.0f},"
        f"int8_gemms_tuned={n_tuned};int8_gemms_uniform={n_uniform};"
        f"saved_int8_gemms={n_uniform - n_tuned};"
        f"loss_delta_tuned={d_tuned:.3e};"
        f"loss_delta_uniform={d_uniform:.3e};err_ok={err_ok}",
    ]


def bench_serve_trace(quick: bool) -> list:
    """Deterministic many-user serve trace: paged vs dense replay.

    The same fixed request trace (seeded ragged prompts, more users
    than slots, per-request max_new) replayed through the paged and
    the dense engine.  ``us_per_call`` is microseconds per *generated
    token* — the gate ratios paged/dense, so the block-table layout
    must sustain the rectangle's tokens/sec.  The paged row's deriveds
    carry the allocation claim (``kv_blocks_hwm`` strictly under
    ``dense_equivalent_blocks``, ``kv_blocks_saved`` >= 1), gated by
    compare_baseline's derived checks.
    """
    from repro.configs import LMConfig
    from repro.models import Model
    from repro.serve import Engine, Request

    cfg = LMConfig(name="bench_serve", vocab_size=128, num_layers=1,
                   d_model=64, num_heads=2, num_kv_heads=1,
                   head_dim=32, d_ff=128)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n_users = 12 if quick else 32
    rng = np.random.default_rng(2024)
    trace = [([int(t) for t in rng.integers(1, cfg.vocab_size, n)],
              int(m))
             for n, m in zip(rng.integers(4, 40, n_users),
                             rng.integers(4, 9, n_users))]

    rows, out_tokens = [], {}
    for layout in ("paged", "dense"):
        eng = Engine(model, params, batch_slots=4, max_len=64,
                     kv_layout=layout, block_size=16)
        reqs = [Request(prompt=p, max_new_tokens=m) for p, m in trace]
        # Warm the compile caches on a throwaway prefix, then time the
        # full replay.
        Engine(model, params, batch_slots=4, max_len=64,
               kv_layout=layout, block_size=16).run(
            [Request(prompt=p, max_new_tokens=m)
             for p, m in trace[:4]])
        t0 = time.perf_counter()
        done = eng.run(reqs)
        dt = time.perf_counter() - t0
        n_tok = sum(len(r.out) for r in done)
        out_tokens[layout] = [r.out for r in done]
        us_per_tok = dt * 1e6 / max(n_tok, 1)
        derived = (f"users={n_users};tokens={n_tok};"
                   f"tokens_per_s={n_tok / dt:.1f}")
        if layout == "paged":
            st = eng.kv.stats()
            saved = st["dense_equivalent_blocks"] - st["allocated_hwm"]
            derived += (f";kv_blocks_hwm={st['allocated_hwm']};"
                        f"dense_equivalent_blocks="
                        f"{st['dense_equivalent_blocks']};"
                        f"kv_blocks_saved={saved}")
        rows.append(f"serve_trace_{layout},{us_per_tok:.0f},{derived}")
    # The replay is only a fair perf comparison if both layouts emit
    # the same tokens; disagreement voids the row.
    identical = int(out_tokens["paged"] == out_tokens["dense"])
    rows[0] += f";tokens_match_dense={identical}"
    return rows


BENCHES = [bench_gemm_accuracy, bench_gemm_throughput_model,
           bench_kernel_pallas, bench_kernel_v2, bench_intercept,
           bench_offload_batched,
           bench_offload_sharded, bench_train_2d,
           bench_lm_step, bench_tuned_plan, bench_serve_trace,
           bench_table1_must, bench_roofline]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--metrics-dir", default="runs/metrics/bench",
                    help="repro.obs run dir mirroring every CSV row "
                         "as a bench_row event; 'none' disables")
    args, _ = ap.parse_known_args()

    metrics = None
    if args.metrics_dir != "none":
        from repro.obs import MetricsRun

        metrics = MetricsRun(args.metrics_dir)

    def emit(row: str) -> None:
        print(row, flush=True)
        if metrics is None:
            return
        parts = row.split(",", 2)
        try:
            us = float(parts[1]) if len(parts) > 1 else None
        except ValueError:
            us = None
        derived = parts[2] if len(parts) > 2 else ""
        # Mirror the numeric view of the derived payload so obs diff
        # compares values without re-parsing the CSV string.
        from repro.obs.diff import parse_derived

        metrics.event("bench_row", name=parts[0], us_per_call=us,
                      derived=derived,
                      derived_num=parse_derived(derived))

    print("name,us_per_call,derived")
    try:
        for bench in BENCHES:
            if args.only and args.only not in bench.__name__:
                continue
            try:
                for row in bench(args.quick):
                    emit(row)
            except Exception as e:
                emit(f"{bench.__name__}_FAILED,0,{e!r}")
    finally:
        if metrics is not None:
            metrics.close()


if __name__ == "__main__":
    main()
