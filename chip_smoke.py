"""Smoke run of the offload path on a TPU: one chip, or four with --chips 4.

  python chip_smoke.py              # one chip: gemm, must, train, serve
  python chip_smoke.py --chips 4    # four chips of one host: dp, dp x tp
  python chip_smoke.py --cpu-rehearsal [--chips 4]   # same phases, tiny,
                                    # on the CPU with interpreted kernels

Every phase drives the program through the entry points a user calls
(the backend registry, ``apps.must.run_contour``,
``repro.launch.train.main``, ``examples/serve_lm.py``,
``build_sharded_train_step``) and prints one JSON line per result.  A
failed check raises, so the script exits nonzero; no phase is skipped.
The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, and without ``--cpu-rehearsal``, it exits nonzero before
any phase runs.  Everything runs in this one process, which holds the
chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Full-size phases on the chip; tiny ones for the CPU rehearsal.
SIZES = {
    False: {"gemm_n": 4096, "must": (4096, 1024), "arch": "smollm_360m",
            "preset": "360m", "seq": 512, "batch": 1,
            "mesh": ("smollm_360m", "reduced_100m", 256, 4)},
    True: {"gemm_n": 128, "must": (128, 32), "arch": "tiny",
           "preset": "tiny", "seq": 64, "batch": 2,
           "mesh": ("tiny", "tiny", 128, 4)},
}
SPLITS = (3, 5, 7, 9)

#: Largest |logit difference| / max |logit| of the served ``--splits 6``
#: run against native f32 at HIGHEST precision.  Set from a v5e run of
#: smollm_360m: 2.1e-7 emulated; 1.2e-3 to 1.6e-3 for native at DEFAULT
#: precision, the control.
SERVE_LOGIT_LIMIT = 1e-5

#: Mesh phase, after the first step: largest |loss difference| and
#: parameter relative L2 distance to the same steps on one chip.  Set
#: from a 2x2 v5e run (smollm_360m dp=4: 8.7e-5 and 0.0040; reduced_100m
#: dp=2,tp=2: 1.1e-4 and 0.0034), below emulation's own distance from
#: native on one chip (1.1e-3 to 1.2e-3 and 0.010 to 0.023).
MESH_LIMITS = {"loss": 3e-4, "param_rel_l2": 8e-3}


def emit(**row) -> None:
    print(json.dumps(row, default=float), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_gemm(jax, n: int, on_tpu: bool) -> None:
    """Registry specs on n x n f64 operands against host numpy f64."""
    import numpy as np

    from repro.core import get_backend

    rng = np.random.default_rng(0)
    a, b = rng.standard_normal((n, n)), rng.standard_normal((n, n))
    ref, denom = a @ b, np.abs(a) @ np.abs(b)
    da, db = jax.device_put(a), jax.device_put(b)
    specs = (["dgemm"] + [f"fp64_int8_{s}" for s in SPLITS]
             + [f"pallas_int8_{s}" for s in SPLITS]
             + ["pallas_int8_6:fused"])
    err = {}
    for spec in specs:
        backend = get_backend(spec)
        t0 = time.perf_counter()
        compiled = jax.jit(lambda x, y, be=backend: be(x, y)).lower(
            da, db).compile()
        compile_s = time.perf_counter() - t0
        compiled(da, db).block_until_ready()
        t0 = time.perf_counter()
        c = compiled(da, db).block_until_ready()
        wall_s = time.perf_counter() - t0
        c = np.asarray(c)
        check(c.shape == (n, n) and c.dtype == np.float64,
              f"{spec}: result {c.shape} {c.dtype}")
        err[spec] = float(np.max(np.abs(c - ref) / denom))
        kernel = "tpu_custom_call" in compiled.as_text()
        if on_tpu and spec.startswith("pallas"):
            check(kernel, f"{spec}: no tpu_custom_call in the program")
        emit(phase="gemm", spec=spec, n=n, err=err[spec],
             compile_s=compile_s, wall_s=wall_s, tpu_custom_call=kernel,
             peak_bytes=peak_bytes(jax))
    for fam in ("fp64_int8", "pallas_int8"):
        ladder = [err[f"{fam}_{s}"] for s in SPLITS]
        check(all(x > y for x, y in zip(ladder, ladder[1:])),
              f"{fam} error does not fall strictly over s={SPLITS}: "
              f"{ladder}")
    for s in SPLITS:
        check(err[f"pallas_int8_{s}"] <= 2 * err[f"fp64_int8_{s}"],
              f"pallas_int8_{s} error {err[f'pallas_int8_{s}']} is over "
              f"2x fp64_int8_{s}'s {err[f'fp64_int8_{s}']}")
    check(err["pallas_int8_6:fused"] < err["pallas_int8_5"],
          "fused s=6 is not more accurate than s=5")


def phase_must(jax, n: int, block: int) -> None:
    """MuST contour in three modes against host LAPACK inverses."""
    from repro.apps import must as MU

    cfg = MU.MustConfig(n=n, block=block, n_energies=4)
    system = MU.build_system(cfg)
    ref = MU.lapack_contour(cfg, system)
    max_real = []
    for s in (5, 7, 9):
        mode = f"fp64_int8_{s}"
        t0 = time.perf_counter()
        out = MU.run_contour(cfg, mode, system)
        wall_s = time.perf_counter() - t0
        e = MU.relative_errors(ref, out)
        max_real.append(e["max_real"])
        emit(phase="must", mode=mode, n=n, block=block, energies=4,
             max_real=e["max_real"], max_imag=e["max_imag"],
             d_etot=e["d_etot"], d_ne=e["d_ne"], wall_s=wall_s,
             peak_bytes=peak_bytes(jax))
    check(all(x > y for x, y in zip(max_real, max_real[1:])),
          f"MuST max_real does not fall with splits: {max_real}")


def _events(metrics_dir: Path) -> list:
    return [json.loads(line)
            for path in sorted(metrics_dir.glob("events-*.jsonl"))
            for line in path.read_text().splitlines() if line]


def phase_train(jax, size: dict, work: Path) -> Path:
    """Three steps through ``launch.train.main``, emulated and native."""
    from repro.launch.train import main as train_main

    def run(tag, extra, *, highest=False):
        ckpt = work / tag
        argv = ["--arch", size["arch"], "--seq-len", str(size["seq"]),
                "--global-batch", str(size["batch"]), "--steps", "3",
                "--ckpt-dir", str(ckpt), "--log-every", "1", *extra]
        t0 = time.perf_counter()
        if highest:
            with jax.default_matmul_precision("highest"):
                losses = train_main(argv)
        else:
            losses = train_main(argv)
        wall_s = time.perf_counter() - t0
        events = _events(ckpt / "metrics")
        step_ms = [e["ms"] for e in events if e.get("type") == "step"]
        check(len(losses) == 3 and len(step_ms) == 3,
              f"train {tag}: {len(losses)} losses, {len(step_ms)} steps")
        return ckpt, losses, step_ms, events, wall_s

    ckpt, emul, emul_ms, events, wall_s = run(
        "emul", ["--backend", "fp64_int8_4"])
    decl = [e for e in events if e.get("type") == "site_decl"]
    on = [e["site"] for e in decl if e["offloaded"]]
    execs = {e["labels"]["site"]: e["value"] for e in events
             if e.get("type") == "metric" and e.get("name") == "site_exec"}
    numerics = [e for e in events if e.get("type") == "numerics"]
    emit(phase="train", mode="fp64_int8_4", arch=size["arch"],
         seq=size["seq"], batch=size["batch"], losses=emul,
         step_ms=emul_ms, wall_s=wall_s, sites_offloaded=len(on),
         sites=len(decl), site_exec_total=sum(execs.values()),
         numerics=[{k: e[k] for k in ("site", "realized_rel", "budget",
                                     "drift")} for e in numerics],
         peak_bytes=peak_bytes(jax))
    for scope in ("scan0/", "scan1/"):
        check(any(s.startswith(scope) for s in on),
              f"no {scope}* site offloaded: {on}")
    check(execs and all(execs.get(s, 0) > 0 for s in on),
          f"offloaded sites without site_exec counts: {execs}")
    check(numerics and not any(e["drift"] for e in numerics),
          f"numerics monitor reports drift: {numerics}")

    native_dir, native, native_ms, _, wall_s = run("native", [],
                                                   highest=True)
    shutil.rmtree(native_dir)
    emit(phase="train", mode="native-highest", arch=size["arch"],
         losses=native, step_ms=native_ms, wall_s=wall_s,
         peak_bytes=peak_bytes(jax))
    check(emul[-1] < emul[0], f"emulated loss does not fall: {emul}")
    for i, (e, r) in enumerate(zip(emul, native)):
        check(abs(e - r) <= 1e-3 * abs(r),
              f"step {i + 1}: emulated loss {e} vs native {r}")
    return ckpt


def phase_serve(jax, preset: str, ckpt: Path) -> None:
    """examples/serve_lm.py on the trained checkpoint, three ways.

    ``--splits 6 --min-dim 1`` offloads every prefill and decode GEMM
    (the default size gate keeps all of them native at 4 requests x 16
    prompt tokens).  It and native f32, both at ``Precision.HIGHEST``,
    must emit the same greedy tokens and logits within
    ``SERVE_LOGIT_LIMIT`` of each other, relative to the largest native
    logit.  Native at DEFAULT precision (one bf16 pass on a TPU) is
    printed beside them as a control: it shows what a coarse GEMM does
    to the same logits.
    """
    import contextlib

    import numpy as np
    import serve_lm

    outs, logits = {}, {}
    for tag, extra, highest in (
            ("splits6", ["--splits", "6", "--min-dim", "1"], True),
            ("native", [], True),
            ("native-default", [], False)):
        metrics = ckpt.parent / f"serve-{tag}"
        argv = ["--preset", preset, "--ckpt-dir", str(ckpt),
                "--max-new-tokens", "24", "--keep-logits",
                "--metrics-dir", str(metrics), *extra]
        precision = (jax.default_matmul_precision("highest") if highest
                     else contextlib.nullcontext())
        t0 = time.perf_counter()
        with precision:
            done = serve_lm.main(argv)
        wall_s = time.perf_counter() - t0
        outs[tag] = [list(r.out) for r in done]
        check(len(done) == 4 and all(len(o) == 24 for o in outs[tag]),
              f"serve {tag}: {[len(o) for o in outs[tag]]} tokens")
        logits[tag] = np.stack([np.stack(r.logits) for r in done])
        events = _events(metrics)
        offloaded = sum(e["offloaded"] for e in events
                        if e.get("type") == "site_decl")
        execs = sum(e["value"] for e in events
                    if e.get("type") == "metric"
                    and e.get("name") == "site_exec")
        emit(phase="serve", mode=tag, preset=preset, requests=len(done),
             new_tokens=24, wall_s=wall_s, tokens=outs[tag],
             prefill_sites_offloaded=offloaded, site_exec_total=execs,
             peak_bytes=peak_bytes(jax))
        check(tag != "splits6" or (offloaded > 0 and execs > 0),
              f"serve {tag}: {offloaded} prefill sites offloaded, "
              f"{execs} site executions")
    ref = logits["native"]
    scale = float(np.max(np.abs(ref)))

    def gap(tag):
        d = np.abs(logits[tag] - ref) / scale
        return {"prefill": float(d[:, 0].max()),
                "decode": float(d[:, 1:].max())}

    emul = gap("splits6")
    emit(phase="serve", logit_scale=scale, splits6_vs_native=emul,
         default_vs_native=gap("native-default"),
         limit=SERVE_LOGIT_LIMIT)
    check(outs["splits6"] == outs["native"],
          "greedy tokens differ between --splits 6 and native")
    check(max(emul.values()) <= SERVE_LOGIT_LIMIT,
          f"--splits 6 logits {emul} off native, limit "
          f"{SERVE_LOGIT_LIMIT}")


def phase_mesh(jax, size: dict) -> None:
    """dp=4 and dp=2 x tp=2 steps against one chip of the same host.

    The first loss, a forward pass from identical parameters, is held
    to ``tests/test_shard.py``'s 2e-6.  After it, Adam turns the
    per-shard rounding of the gradient psum and of the Ozaki slicing
    into differences far above that test's tolerances (set for a tiny
    f64 model), so the later losses and the parameters are held to
    ``MESH_LIMITS``.  The one-chip native run is printed beside them.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import PrecisionPolicy, offload
    from repro.launch.train import (build_sharded_train_step,
                                    build_train_step)
    from repro.models import Model
    from repro.shard import train_mesh_setup
    from repro.train import AdamW, SyntheticText

    big, tp_arch, seq, batch = size["mesh"]
    pol = PrecisionPolicy(backend="fp64_int8_4", default_splits=4)
    for arch, spec in ((big, "dp=4"), (tp_arch, "dp=2,tp=2")):
        # remat: the one-chip reference of the whole global batch must
        # fit one chip at full width.
        cfg = get_config(arch).replace(remat=True)
        model, opt = Model(cfg), AdamW(lr=3e-3)
        data = SyntheticText(cfg.vocab_size, seq, batch, seed=0)
        runs = {}
        for tag in ("native", "one_chip", spec):
            params = model.init_params(jax.random.PRNGKey(0))
            state = opt.init(params)
            put = jnp.asarray
            step = build_train_step(model, opt)
            if tag == spec:
                mesh, bsh, (params, state), _ = train_mesh_setup(
                    spec, batch, cfg, (params, state))
                step = build_sharded_train_step(model, opt, mesh)
                put = lambda x, s=bsh: jax.device_put(jnp.asarray(x), s)
            offloaded = 0
            if tag != "native":
                step = offload(step, pol)
                offloaded = sum(s.offloaded for s in step.sites(
                    params, state, put(data.batch(0))))
            fn = jax.jit(step, donate_argnums=(0, 1))
            losses, step_ms = [], []
            with jax.default_matmul_precision("highest"):
                for i in range(3):
                    t0 = time.perf_counter()
                    params, state, loss = fn(params, state,
                                             put(data.batch(i)))
                    losses.append(float(loss))
                    step_ms.append((time.perf_counter() - t0) * 1e3)
            runs[tag] = (losses, [np.asarray(x, np.float64) for x in
                                  jax.tree_util.tree_leaves(
                                      jax.device_get(params))], offloaded)
            del params, state
            emit(phase="mesh", arch=arch, mesh=tag, seq=seq, batch=batch,
                 losses=losses, step_ms=step_ms, sites_offloaded=offloaded,
                 peak_bytes=peak_bytes(jax))

        def gap(a, b):
            (la, pa, _), (lb, pb, _) = runs[a], runs[b]
            num = sum(float(np.sum((x - y) ** 2)) for x, y in zip(pa, pb))
            den = sum(float(np.sum(y ** 2)) for y in pb)
            return {"loss": [abs(x - y) for x, y in zip(la, lb)],
                    "param_rel_l2": (num / den) ** 0.5,
                    "param_max_abs": max(float(np.max(np.abs(x - y)))
                                         for x, y in zip(pa, pb))}

        shard, emul = gap(spec, "one_chip"), gap("one_chip", "native")
        emit(phase="mesh", arch=arch, mesh=spec, vs_one_chip=shard,
             one_chip_vs_native=emul)
        n1, nn = runs["one_chip"][2], runs[spec][2]
        # tp shrinks the per-shard extents, and with them the sites
        # that pass the size gate; dp alone must offload every site.
        check(nn > 0 and ("tp" in spec or nn == n1),
              f"{spec}: {nn} sites offloaded, one chip {n1}")
        check(shard["loss"][0] <= 2e-6,
              f"{spec}: first loss off by {shard['loss'][0]}")
        check(max(shard["loss"]) <= MESH_LIMITS["loss"],
              f"{spec}: losses {shard['loss']} off one chip, limit "
              f"{MESH_LIMITS['loss']}")
        check(shard["param_rel_l2"] <= MESH_LIMITS["param_rel_l2"],
              f"{spec}: params {shard['param_rel_l2']} off one chip, "
              f"limit {MESH_LIMITS['param_rel_l2']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases at tiny sizes on the CPU")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {device}); pass "
              "--cpu-rehearsal to rehearse on the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {device}",
              file=sys.stderr)
        return 1
    if not args.cpu_rehearsal:
        enable_compile_cache()
    size = SIZES[args.cpu_rehearsal]
    emit(phase="start", device=device, chips=args.chips,
         rehearsal=args.cpu_rehearsal)
    if args.chips == 4:
        phase_mesh(jax, size)
    else:
        jax.config.update("jax_enable_x64", True)
        phase_gemm(jax, size["gemm_n"], device["platform"] == "tpu")
        phase_must(jax, *size["must"])
        # The LM runs in pure f32, as its examples do.
        jax.config.update("jax_enable_x64", False)
        (ROOT / ".smoke").mkdir(exist_ok=True)
        work = Path(tempfile.mkdtemp(dir=ROOT / ".smoke"))
        try:
            ckpt = phase_train(jax, size, work)
            phase_serve(jax, size["preset"], ckpt)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
