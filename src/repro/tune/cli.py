"""``python -m repro.tune`` — calibrate an LM workload, solve, save.

The standalone tune flow over the registered LM configs::

    PYTHONPATH=src python -m repro.tune --arch tiny --batches 2 \\
        --plan runs/plans/tiny.json

calibrates the chosen target program (``--target step``: one full
train step, forward + backward + AdamW, the sites ``launch/train.py``
offloads; ``--target loss``: the forward loss only — its site set is
mesh-portable, so plans calibrated under ``--mesh dp=N`` and on a
single device are byte-identical), solves the cost-optimal per-site
split assignment for the error budget, and writes the plan JSON.

Consume the plan with ``launch/train.py --plan`` (training) and
``examples/serve_lm.py --plan`` (serving); ``launch/train.py --tune N
--plan path`` runs this same calibrate-and-solve flow inline on the
exact training setup.
"""

from __future__ import annotations

import argparse
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import PrecisionPolicy, get_backend
from repro.models import Model
from repro.obs import get_logger
from repro.shard import train_mesh_setup
from repro.train import AdamW, SyntheticText

from .calibrate import Calibrator
from .plan import write_tiles_table
from .solve import count_int8_gemms, solve_plan, unpinned_family

__all__ = ["main", "tune_policy", "report_plan", "log_report"]

log = get_logger("tune")


def tune_policy(backend_spec: str, min_dim: int) -> PrecisionPolicy:
    """The calibration policy for a requested backend spec.

    The family is unpinned (the plan owns per-site splits); a pinned
    spec's count (``fp64_int8_6``) becomes the probe/default split
    count, so ``--backend fp64_int8_4`` means "probe at s=4".
    """
    pinned = getattr(get_backend(backend_spec), "pinned_splits", None)
    return PrecisionPolicy(
        backend=unpinned_family(backend_spec), min_dim=min_dim,
        **({"default_splits": pinned} if pinned else {}))


def report_plan(plan, sites) -> str:
    """Human-readable tuned-vs-uniform cost summary.

    ``sites`` is the calibration pass's (cached) site-decision list —
    offloaded under the uniform probe policy — so both costs come
    from one trace: the recorded splits give the uniform count, the
    plan's assignment (demotions contribute nothing) gives the tuned
    count.
    """
    policy = PrecisionPolicy.from_plan(plan,
                                       on_unmatched_site="ignore")

    def tuned_splits(site):
        if policy.backend_for(site.name) == "dgemm":
            return None
        return policy.splits_for(site.name)

    n_tuned = count_int8_gemms(sites, splits_for=tuned_splits)
    n_uniform = count_int8_gemms(sites)
    lines = [plan.describe(),
             f"INT8 GEMMs per step: tuned={n_tuned} vs "
             f"uniform={n_uniform} "
             f"(saved {n_uniform - n_tuned})"]
    if not plan.sites:
        lines.append("WARNING: no eligible GEMM sites — every "
                     "dot_general fell under the size/dtype gate "
                     "(per-shard shapes vs min_dim?); the plan tunes "
                     "nothing")
    if not plan.budget_met:
        lines.append("WARNING: budget unreachable even at the "
                     "split ceiling; plan uses max splits")
    return "\n".join(lines)


def log_report(logger, report: str) -> None:
    """Render a :func:`report_plan` string line-by-line through a
    :class:`repro.obs.log.Logger` (WARNING lines at warning level, so
    the rendered text matches the pre-obs ad-hoc prints exactly)."""
    for line in report.splitlines():
        if line.startswith("WARNING: "):
            logger.warning(line[len("WARNING: "):])
        else:
            logger.info(line)


def _parse(argv):
    ap = argparse.ArgumentParser(prog="python -m repro.tune",
                                 description=__doc__)
    ap.add_argument("--arch", default="tiny",
                    help="registered LMConfig preset name")
    ap.add_argument("--target", choices=("step", "loss"),
                    default="step",
                    help="program to calibrate: the full train step "
                         "or the forward loss (mesh-portable plans)")
    ap.add_argument("--batches", type=int, default=1,
                    help="calibration passes (distinct data batches)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="fp64_int8",
                    help="backend family; a pinned count sets the "
                         "probe splits")
    ap.add_argument("--min-dim", type=int, default=128)
    ap.add_argument("--budget", type=float, default=0.0,
                    help="end-to-end relative error budget; 0 = "
                         "derive from the model dtype")
    ap.add_argument("--mesh", default="",
                    help="calibrate data-parallel over this mesh "
                         "(e.g. 'dp=8'); stats are pmax-shared so the "
                         "plan matches the single-device one")
    ap.add_argument("--plan", required=True,
                    help="output path for the plan JSON")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> List[str]:
    args = _parse(argv)
    cfg = get_config(args.arch)
    model = Model(cfg)
    opt = AdamW(lr=args.lr)
    data = SyntheticText(cfg.vocab_size, args.seq_len,
                         args.global_batch, seed=args.seed)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)

    mesh = batch_sharding = None
    if args.mesh:
        # Same 2-D bring-up as the train CLI, so a step plan is
        # calibrated against exactly the per-shard extents (and tp
        # psums) the training run will trace.
        mesh, batch_sharding, (params, opt_state), _ = \
            train_mesh_setup(args.mesh, args.global_batch, cfg,
                             (params, opt_state))

    if args.target == "step":
        from repro.launch.train import (build_sharded_train_step,
                                        build_train_step)

        fn = (build_sharded_train_step(model, opt, mesh)
              if mesh is not None else build_train_step(model, opt))

        def call_args(batch):
            return (params, opt_state, batch)
    else:
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            axis = mesh.axis_names[0]

            def fn(p, batch):
                def per_shard(p_s, b_s):
                    return jax.lax.pmean(model.loss(p_s, b_s), axis)

                return jax.shard_map(per_shard, mesh=mesh,
                                     in_specs=(P(), P(axis)),
                                     out_specs=P(),
                                     check_vma=False)(p, batch)
        else:
            fn = model.loss

        def call_args(batch):
            return (params, batch)

    policy = tune_policy(args.backend, args.min_dim)
    cal = Calibrator(fn, policy)
    for i in range(max(args.batches, 1)):
        batch = jnp.asarray(data.batch(i))
        if batch_sharding is not None:
            batch = jax.device_put(batch, batch_sharding)
        cal.run(*call_args(batch))
    result = cal.result()
    plan = solve_plan(result, budget=args.budget or None)
    path = plan.save(args.plan)
    tiles_path = write_tiles_table(plan, path)
    report = report_plan(plan, cal.sites)
    log_report(log, report)
    log.info(f"plan written to {path} "
             f"(tile decisions: {tiles_path})")
    return report.splitlines()
