"""Training entry point: the LM step loop, optionally fully emulated.

``main(argv)`` trains the configured LM on the deterministic synthetic
stream up to ``--steps`` *global* steps, checkpointing as it goes and
resuming from the newest checkpoint in ``--ckpt-dir`` — kill it and
re-invoke with the same arguments and it continues bit-exactly.

``--backend`` is where this loop meets the paper: the *entire* jitted
train step (loss forward, backward, AdamW update) is wrapped in the
automatic offload transform (:func:`repro.core.intercept.offload`)
with a :class:`~repro.core.precision.PrecisionPolicy` pointing at that
registry spec — so ``--backend fp64_int8_4`` runs every projection,
MLP, and LM-head GEMM of the forward *and* backward pass through the
Ozaki INT8 emulation, while sub-``--min-dim`` contractions (notably
attention, k = head_dim) stay native, exactly like the paper's size
cutoff.  The discovered sites are printed once per run.

``--mesh dp=N`` runs the same step data-parallel over N devices
(:func:`build_sharded_train_step`): parameters replicated, batch split
over the ``dp`` axis, gradients mean-reduced with a *bucketed* psum
(grouped by byte size, issued as buckets complete so XLA overlaps
them with the remaining backward GEMMs; ``--grad-reduce`` selects the
blocking reference or a ``ppermute`` ring instead).  ``--mesh
dp=N,tp=M`` adds Megatron-style tensor parallelism: attention heads
and the SwiGLU hidden dim split over ``tp`` per the axis rules in
:mod:`repro.shard.rules`, each sublayer closed by a ``psum`` on the
``tp`` axis inside the shard_map body, and checkpoints written as
per-shard npz files plus a layout manifest.  Both compose with
``--backend``, whose offload transform descends into the ``shard_map``
body (sites named ``shmap0/...``), so every shard runs the per-shard
Ozaki split schedule its local extents call for.  On CPU, export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.

Precision plans (:mod:`repro.tune`) close the loop:

* ``--tune N --plan path`` calibrates the exact train step this loop
  would run (N batches, starting from the resume state), solves the
  cost-optimal per-site split assignment, writes the plan JSON, and
  exits — no training happens;
* ``--plan path`` (without ``--tune``) trains under the plan: the
  step is wrapped in ``offload(step, plan=...)``, the traced site set
  is validated against the plan fingerprint (a drifted program
  raises), and every checkpoint records the fingerprint so a later
  resume under a different precision configuration errors instead of
  silently continuing at different numerics —
  ``--allow-plan-change`` turns that error into a loud warning, the
  explicit path for adopting a freshly tuned plan on an existing
  lineage.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import PrecisionPolicy, get_backend, offload
from repro.models import Model
from repro.obs import MetricsRun, NumericsMonitor, get_logger
from repro.shard import (DEFAULT_BUCKET_BYTES, bucket_stats,
                         reduce_gradients, train_mesh_setup)
from repro.train import AdamW, SyntheticText, checkpoint
from repro.tune.solve import count_int8_gemms

__all__ = ["main", "build_train_step", "build_sharded_train_step"]

log = get_logger("train")
offload_log = get_logger("offload")


def build_train_step(model: Model, opt: AdamW):
    """The pure ``(params, opt_state, batch) -> (params, opt_state,
    loss)`` step.  Kept separate so tests and benchmarks can wrap the
    exact function the trainer runs."""

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state = opt.update(grads, params, opt_state)
        return params, opt_state, loss

    return train_step


def build_sharded_train_step(model: Model, opt: AdamW, mesh,
                             axis: str | None = None, *,
                             grad_reduce: str = "bucketed",
                             bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """dp(×tp)-parallel version of :func:`build_train_step` over ``mesh``.

    Each data-parallel shard runs value_and_grad on its batch slice;
    losses are ``pmean``-ed and gradients mean-reduced across the dp
    axis with :func:`repro.shard.reduce_gradients` — bucketed by byte
    size so XLA can overlap early buckets with the remaining backward
    GEMMs (``grad_reduce="bucketed"``, bit-identical to the per-leaf
    ``pmean`` it replaced; ``"blocking"`` and ``"ppermute"`` are the
    reference and the ring-pipelined alternative).  Every shard then
    applies the identical AdamW update, so the global step equals the
    single-device step on the full batch, which the dp=N equivalence
    tests pin to 1e-10.

    When ``mesh`` carries a ``tp`` axis of size > 1, the step runs
    Megatron-style tensor parallelism on top: parameters enter the
    body per the LM axis rules (attention heads and the SwiGLU hidden
    dim column/row-sharded on ``tp``, the rest replicated), the model
    is rebuilt with ``tp_axis="tp"`` so each sublayer closes with a
    ``psum`` over ``tp`` inside the shard_map body, and the AdamW
    update runs elementwise on the local parameter blocks.

    Wrapping the returned function in ``offload(...)`` routes the
    per-shard forward AND backward GEMMs through the registry backend
    (sites named ``shmap0/...``) — the per-shard contraction extents
    (``q_dim/tp``, ``d_ff/tp``, per-shard batch rows for ``dW``)
    drive the size gate and plan lookup, exactly as a single device
    of that shard size would.
    """
    from jax.sharding import PartitionSpec as P

    from repro.shard import (TP_AXIS, train_state_specs, validate_tp)

    dp = axis or mesh.axis_names[0]
    if dp == TP_AXIS and len(mesh.axis_names) > 1:
        dp = next(a for a in mesh.axis_names if a != TP_AXIS)
    tp = dict(mesh.shape).get(TP_AXIS, 1)
    dp_size = dict(mesh.shape)[dp]

    if tp > 1:
        validate_tp(model.cfg, tp)
        model = Model(model.cfg, tp_axis=TP_AXIS)
        param_specs, opt_specs = train_state_specs(model.cfg)
    else:
        param_specs, opt_specs = P(), P()

    def per_shard_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        loss = jax.lax.pmean(loss, dp)
        grads = reduce_gradients(grads, dp, dp_size,
                                 mode=grad_reduce,
                                 bucket_bytes=bucket_bytes)
        params, opt_state = opt.update(grads, params, opt_state)
        return params, opt_state, loss

    # check_vma=False: the tp model's custom_vjp collective wrappers
    # have no varying-axis tracking rules, and all cross-shard sums
    # here are explicit psums anyway.
    return jax.shard_map(per_shard_step, mesh=mesh,
                         in_specs=(param_specs, opt_specs, P(dp)),
                         out_specs=(param_specs, opt_specs, P()),
                         check_vma=False)


def _describe_sites(sites) -> None:
    on = [s for s in sites if s.offloaded]
    off = [s for s in sites if not s.offloaded]
    offload_log.info(f"{len(on)} of {len(sites)} dot_general sites "
                     "routed through the registry backend:")
    for s in on:
        offload_log.info(f"  {s}")
    if off:
        offload_log.info(f"{len(off)} sites stay native "
                         "(size/dtype gate), e.g. "
                         + "; ".join(repr(s) for s in off[:3]))
    native = getattr(sites, "native", ())
    if native:
        offload_log.warning(
            f"{len(native)} contractions left native besides the gated "
            "dot_general sites: " + "; ".join(
                f"{n.primitive} {n.name} ({n.reason})" for n in native))


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--overrides", default="",
                    help="JSON dict of LMConfig overrides")
    ap.add_argument("--steps", type=int, default=300,
                    help="train until this GLOBAL step (resume-aware)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="",
                    help="GEMM registry spec (e.g. fp64_int8_4); empty "
                         "= native XLA matmuls")
    ap.add_argument("--plan", default="",
                    help="precision-plan JSON: with --tune, where the "
                         "calibrated plan is written; without, the "
                         "plan the train step runs under")
    ap.add_argument("--tune", type=int, default=0,
                    help="calibrate the train step over this many "
                         "batches, solve, write --plan, and exit "
                         "(no training)")
    ap.add_argument("--budget", type=float, default=0.0,
                    help="end-to-end relative error budget for "
                         "--tune; 0 = derive from the model dtype")
    ap.add_argument("--allow-plan-change", action="store_true",
                    help="resume a lineage under a DIFFERENT "
                         "precision configuration (loud warning "
                         "instead of an error); the intended path for "
                         "adopting a plan tuned at the resume state")
    ap.add_argument("--mesh", default="",
                    help="mesh spec: 'dp=8' (data parallel) or "
                         "'dp=4,tp=2' (2-D: tp splits attention heads "
                         "and the MLP hidden dim); empty = single "
                         "device.  On CPU export XLA_FLAGS=--xla_"
                         "force_host_platform_device_count=N first")
    ap.add_argument("--grad-reduce", default="bucketed",
                    choices=["bucketed", "blocking", "ppermute"],
                    help="gradient all-reduce strategy on the dp axis "
                         "(bucketed = overlapped with the remaining "
                         "backward, bit-identical to pmean; ppermute "
                         "= ring pipeline, replicas agree to rounding "
                         "only)")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="gradient bucket size in MiB for "
                         "--grad-reduce bucketed; 0 = default (4)")
    ap.add_argument("--min-dim", type=int, default=128,
                    help="offload size gate: min(m,k,n) for emulation")
    ap.add_argument("--ckpt-dir", default="",
                    help="default: runs/ckpt/<arch>")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-dir", default="",
                    help="telemetry directory (repro.obs JSONL runs); "
                         "default: <ckpt-dir>/metrics; 'none' disables")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the run's registry live at "
                         "http://127.0.0.1:PORT/metrics (Prometheus "
                         "text format; 0 = ephemeral port) while "
                         "training; requires telemetry on")
    ap.add_argument("--metrics-push-url", default="",
                    help="push this process's registry snapshot to an "
                         "aggregating metrics server (http://host:port"
                         "/push) every --log-every steps — how a "
                         "multi-process mesh job publishes into one "
                         "scrapeable /metrics endpoint")
    ap.add_argument("--numerics-every", type=int, default=25,
                    help="NumericsMonitor period: every Nth step "
                         "re-measure the probe site's realized error "
                         "against dgemm (emulated runs with telemetry "
                         "on); 0 disables")
    return ap.parse_args(argv)


def _run_tune(args, train_step, params, opt_state, data, start,
              batch_sharding) -> None:
    """``--tune N --plan path``: calibrate, solve, save, report."""
    from repro.tune import Calibrator, solve_plan
    from repro.tune.cli import log_report, report_plan, tune_policy
    from repro.tune.plan import write_tiles_table

    policy = tune_policy(args.backend or "fp64_int8", args.min_dim)
    log.info(f"tuning: {args.tune} calibration batch(es) from "
             f"step {start}, probe s={policy.default_splits}, "
             f"backend family {policy.backend}")
    cal = Calibrator(train_step, policy)
    for i in range(args.tune):
        batch = jnp.asarray(data.batch(start + i))
        if batch_sharding is not None:
            batch = jax.device_put(batch, batch_sharding)
        cal.run(params, opt_state, batch)
    plan = solve_plan(cal.result(), budget=args.budget or None)
    path = plan.save(args.plan)
    tiles_path = write_tiles_table(plan, path)
    log_report(get_logger("tune"), report_plan(plan, cal.sites))
    log.info(f"plan written to {path} (tile decisions: "
             f"{tiles_path}); train with --plan {path}")


def _check_resume_plan(ckpt_dir, start: int, plan,
                       allow_change: bool) -> None:
    """Refuse to resume across a precision-configuration change.

    The checkpoint metadata carries the plan fingerprint the run was
    training under; resuming with a different plan (or none, or from
    a pre-plan checkpoint with a plan now active) would silently
    continue the loss curve at different numerics — error unless the
    change is explicit (``--allow-plan-change``, the intended way to
    adopt a freshly tuned plan on an existing lineage: train
    plan-less, ``--tune`` at the resume state, resume with ``--plan
    ... --allow-plan-change`` once).
    """
    ckpt_fp = checkpoint.load_meta(ckpt_dir, start).get(
        "plan_fingerprint")
    active_fp = plan.fingerprint if plan is not None else None
    if ckpt_fp == active_fp:
        return
    if allow_change:
        log.warning(f"precision configuration changes at "
                    f"step {start}: {ckpt_fp or '<none>'} -> "
                    f"{active_fp or '<none>'} (--allow-plan-change); "
                    "later checkpoints record the new fingerprint")
        return
    raise SystemExit(
        f"[train] checkpoint step {start} in {ckpt_dir} was written "
        f"under precision plan {ckpt_fp or '<none>'} but this run is "
        f"configured with {active_fp or '<none>'}: resuming would "
        "silently change training numerics mid-lineage. Pass the "
        "matching --plan; or, to adopt this configuration on purpose "
        "(e.g. a plan just tuned at this resume state), re-run with "
        "--allow-plan-change.")


def main(argv: Optional[Sequence[str]] = None) -> List[float]:
    """Run the loop; returns the per-step losses of THIS invocation."""
    args = _parse(argv)
    if args.tune and not args.plan:
        raise SystemExit("[train] --tune needs --plan (where to write "
                         "the calibrated plan)")
    if args.plan and args.backend and not args.tune:
        raise SystemExit("[train] --plan and --backend are both "
                         "precision configurations; pass one (with "
                         "--tune, --backend sets the probe family)")
    cfg = get_config(args.arch)
    if args.overrides:
        cfg = cfg.replace(**json.loads(args.overrides))
    model = Model(cfg)
    opt = AdamW(lr=args.lr)
    data = SyntheticText(cfg.vocab_size, args.seq_len,
                         args.global_batch, seed=args.seed)
    ckpt_dir = args.ckpt_dir or f"runs/ckpt/{args.arch}"

    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    start = checkpoint.latest_step(ckpt_dir) or 0
    if start:
        log.info(f"resuming from step {start} in {ckpt_dir}")
        params, opt_state = checkpoint.restore(ckpt_dir, start,
                                               (params, opt_state))
    if start >= args.steps and not args.tune:
        log.info(f"checkpoint step {start} >= --steps "
                 f"{args.steps}; nothing to do")
        return []

    mesh = batch_sharding = state_specs = None
    bucket_bytes = (int(args.bucket_mb * (1 << 20)) if args.bucket_mb
                    else DEFAULT_BUCKET_BYTES)
    if args.mesh:
        mesh, batch_sharding, (params, opt_state), state_specs = \
            train_mesh_setup(args.mesh, args.global_batch, cfg,
                             (params, opt_state))
        shape = dict(mesh.shape)
        log.info(f"mesh {args.mesh}: {mesh.size} devices "
                 f"(dp={shape.get('dp', 1)} tp={shape.get('tp', 1)}), "
                 f"per-shard batch "
                 f"{args.global_batch // shape.get('dp', 1)}, "
                 f"grad-reduce {args.grad_reduce}")
        if args.grad_reduce == "bucketed":
            n_buckets, per_psum = bucket_stats(params, bucket_bytes)
            log.info(f"gradient buckets: {n_buckets} psum(s), "
                     f"{[round(b / 1024) for b in per_psum]} KiB")
        train_step = build_sharded_train_step(
            model, opt, mesh, grad_reduce=args.grad_reduce,
            bucket_bytes=bucket_bytes)
    else:
        train_step = build_train_step(model, opt)

    if args.tune:
        _run_tune(args, train_step, params, opt_state, data, start,
                  batch_sharding)
        return []

    plan = None
    if args.plan:
        from repro.tune import PrecisionPlan

        plan = PrecisionPlan.load(args.plan)
    if start:
        _check_resume_plan(ckpt_dir, start, plan,
                           args.allow_plan_change)
    ckpt_meta = {
        "plan_fingerprint": plan.fingerprint if plan is not None
        else None,
        # Informational (resume enforcement keys on the fingerprint).
        "backend": args.backend or None,
        "plan_path": args.plan or None,
    }
    # A tp mesh writes the per-shard layout (one npz per tp shard +
    # manifest); restore reassembles the global tree, so a later
    # resume may use any mesh shape — or none.
    tp_sharded = (state_specs is not None and mesh is not None
                  and dict(mesh.shape).get("tp", 1) > 1)

    def save_ckpt(step_no, state):
        if tp_sharded:
            checkpoint.save_sharded(ckpt_dir, step_no, state,
                                    state_specs, mesh, meta=ckpt_meta)
        else:
            checkpoint.save(ckpt_dir, step_no, state, meta=ckpt_meta)

    # Telemetry (repro.obs): one MetricsRun per invocation, scoped to
    # the checkpoint lineage by default so test/tmp runs stay in tmp.
    metrics = None
    if args.metrics_dir != "none":
        metrics = MetricsRun(args.metrics_dir
                             or f"{ckpt_dir}/metrics")
        metrics.event("config", arch=args.arch, steps=args.steps,
                      start=start, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      backend=args.backend or None,
                      plan=args.plan or None, mesh=args.mesh or None)

    # Live observability: a pull endpoint over this run's registry
    # and/or periodic pushes into another process's aggregator.
    mserver = None
    push_url = args.metrics_push_url if metrics is not None else ""
    push_source = f"train-proc{jax.process_index()}"
    if metrics is not None and args.metrics_port is not None:
        from repro.obs import MetricsServer

        mserver = MetricsServer(metrics.registry,
                                port=args.metrics_port,
                                runs_dir=metrics.directory).start()
        log.info(f"live metrics: {mserver.url}/metrics")

    def push_metrics() -> None:
        if not push_url:
            return
        from repro.obs import push_snapshot

        try:
            push_snapshot(push_url, push_source, metrics.registry)
        except OSError as e:
            log.warning(f"metrics push to {push_url} failed: {e}")

    on_site_event = metrics.site_event_handler() if metrics else None
    monitor = None
    policy = None
    if plan is not None:
        policy = PrecisionPolicy.from_plan(plan)
        wrapped = offload(train_step, policy, plan=plan,
                          plan_match="strict",
                          on_site_event=on_site_event)
        log.info(f"precision plan {args.plan} "
                 f"({plan.fingerprint}, backend={plan.backend}, "
                 f"{len(plan.sites)} sites"
                 + (f", {len(plan.demoted_sites())} demoted" if
                    plan.demoted_sites() else "") + ")")
    elif args.backend:
        # A pinned spec ("fp64_int8_4") is authoritative at execution;
        # mirror it into the policy so the printed site report shows
        # the split count that actually runs.
        pinned = getattr(get_backend(args.backend), "pinned_splits",
                         None)
        policy = PrecisionPolicy(backend=args.backend,
                                 min_dim=args.min_dim,
                                 **({"default_splits": pinned}
                                    if pinned else {}))
        wrapped = offload(train_step, policy,
                          on_site_event=on_site_event)
        log.info(f"backend={args.backend} min_dim={args.min_dim} "
                 f"({cfg.num_params()/1e6:.1f}M params)")
    if policy is not None:
        sites = wrapped.sites(params, opt_state, data.batch(start))
        _describe_sites(sites)
        # Donating the state lets the update write in place: at full
        # width a second copy of params + AdamW moments does not fit.
        step_fn = jax.jit(wrapped, donate_argnums=(0, 1))
        int8_per_step = count_int8_gemms(sites)
        if metrics is not None:
            metrics.declare_sites(sites)
            if args.numerics_every > 0:
                monitor = NumericsMonitor(
                    train_step, plan=plan,
                    policy=None if plan is not None else policy,
                    every=args.numerics_every,
                    registry=metrics.registry, sink=metrics.sink,
                    log=log)
    else:
        step_fn = jax.jit(train_step, donate_argnums=(0, 1))
        int8_per_step = 0

    def span(name: str, **kw):
        # Host spans of the loop (one TraceAnnotation each, so a device
        # trace of the launcher names its idle gaps by them).
        return (metrics.tracer.span(name, **kw) if metrics is not None
                else contextlib.nullcontext())

    losses: List[float] = []
    t_last = time.perf_counter()
    try:
        for step in range(start, args.steps):
            with span("train.data", step=step + 1):
                batch = jnp.asarray(data.batch(step))
                if batch_sharding is not None:
                    batch = jax.device_put(batch, batch_sharding)
            if monitor is not None and monitor.due(step):
                with span("train.numerics", step=step + 1):
                    monitor.check(step, params, opt_state, batch)
            t_step = time.perf_counter()
            with span("train.step", step=step + 1):
                params, opt_state, loss = step_fn(params, opt_state, batch)
            with span("train.loss", step=step + 1):
                # Blocks on the device step: train.step covers the
                # dispatch, train.loss the wait for the step's end.
                losses.append(float(loss))
            step_ms = (time.perf_counter() - t_step) * 1e3
            if metrics is not None:
                metrics.event("step", step=step + 1, loss=losses[-1],
                              ms=step_ms, int8_gemms=int8_per_step)
            if step == start or (step + 1) % args.log_every == 0 \
                    or step + 1 == args.steps:
                now = time.perf_counter()
                log.info(f"step {step + 1}/{args.steps} "
                         f"loss={losses[-1]:.4f} "
                         f"({(now - t_last) * 1e3:.0f} ms)")
                t_last = now
                push_metrics()
            if (step + 1) % args.ckpt_every == 0:
                with span("train.checkpoint", step=step + 1):
                    save_ckpt(step + 1, (params, opt_state))
        with span("train.checkpoint", step=args.steps):
            save_ckpt(args.steps, (params, opt_state))
    finally:
        if metrics is not None:
            # Drain async site-event callbacks before the final
            # registry snapshot, so execution counts are complete.
            jax.effects_barrier()
            push_metrics()
            metrics.close()
        if mserver is not None:
            mserver.close()
    log.info(f"done at step {args.steps}; checkpoint in {ckpt_dir}")
    if metrics is not None:
        log.info(f"telemetry: {metrics.sink.path} (inspect with "
                 f"python -m repro.obs report {metrics.directory})")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
