"""NumericsMonitor: runtime drift detection for tuned precision plans.

A precision plan is calibrated *offline* (``repro.tune``), typically at
step 0 — but the paper's own observation is that emulation accuracy
depends on the operator's values, and values move as training moves.
:class:`PlanStaleError` catches *structural* drift (the program's site
set changed); this module is the runtime complement for *numerical*
drift: every Nth train step the monitor re-runs the program with an
instrumented pass that measures the **realized** relative error of a
probe site — the eligible offloaded site with the largest per-step
FLOP volume, i.e. the site whose error the composed budget is most
exposed to — at its *deployed* split count, against a ``dgemm``
reference.  If the realized error of that single site exceeds the
plan's whole end-to-end budget, the composed bound is certainly
violated and a structured warning fires (plus a ``numerics`` JSONL
event and a registry gauge), telling the operator to re-tune.

The instrumented pass reuses the exact offload/calibration machinery:
a recording :class:`~repro.core.backends.GemmBackend` (authoritative,
``supports_vjp=False``) that returns the *native* product — a monitor
check never perturbs anything — and ships the measured error to the
host via ``jax.debug.callback`` following the Calibrator's
np-asarray-first rule (callbacks must never launch jax ops).  Inside
``shard_map``/``pmap`` bodies the error is ``pmax``-shared across the
mesh axes first, so every device reports the same global value.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import GemmBackend
from repro.core.intercept import Site, offload
from repro.core.ozaki import ozaki_matmul
from repro.core.precision import PrecisionPolicy

from .log import get_logger

__all__ = ["NumericsMonitor", "NumericsReport"]


@dataclasses.dataclass
class NumericsReport:
    """One drift check: the probe site's realized error vs the budget."""

    step: int
    site: str                  #: probe site (structural name)
    splits: int                #: deployed split count it ran at
    realized_rel: float        #: measured max relative error
    budget: float              #: end-to-end budget it is held against
    drift: bool                #: realized_rel > budget


class _ProbeGemm(GemmBackend):
    """Recording backend: native result out, probe-site error to host."""

    supports_vjp = False
    intercepts_all_sites = True

    def __init__(self, policy: PrecisionPolicy):
        super().__init__("numerics_probe", policy)
        self._meta: Dict[str, Site] = {}
        self.probe_site: Optional[str] = None
        self._lock = threading.Lock()
        self._realized = 0.0
        self._seen = False

    def observe_sites(self, decisions: Dict[str, Site]) -> None:
        self._meta.update(decisions)
        offloaded = [s for s in decisions.values() if s.offloaded]
        if offloaded and self.probe_site is None:
            # Deterministic probe choice: the costliest offloaded site
            # (most FLOPs per step), name as the tie-break.
            self.probe_site = max(offloaded,
                                  key=lambda s: (s.flops, s.name)).name

    def reset(self) -> None:
        with self._lock:
            self._realized = 0.0
            self._seen = False

    def realized(self) -> Optional[float]:
        with self._lock:
            return self._realized if self._seen else None

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        del num_splits  # the deployed (plan) split count is measured
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        native = a @ b
        if site == self.probe_site:
            is_cplx = (jnp.issubdtype(a.dtype, jnp.complexfloating)
                       or jnp.issubdtype(b.dtype, jnp.complexfloating))
            ref_dtype = jnp.complex128 if is_cplx else jnp.float64
            if not jax.config.jax_enable_x64:
                ref_dtype = jnp.complex64 if is_cplx else jnp.float32
            # HIGHEST: a TPU's DEFAULT f32 matmul is one bf16 pass.
            hi = jax.lax.Precision.HIGHEST
            ref = jnp.matmul(a.astype(ref_dtype), b.astype(ref_dtype),
                             precision=hi)
            emul = ozaki_matmul(
                a, b, num_splits=self.policy.splits_for(site),
                accumulator=self.policy.accumulator,
                out_dtype=ref_dtype,
                slice_bits=self.policy.slice_bits)
            denom = jnp.matmul(jnp.abs(a).astype(jnp.abs(ref).dtype),
                               jnp.abs(b).astype(jnp.abs(ref).dtype),
                               precision=hi)
            denom = jnp.where(denom == 0, 1.0, denom)
            err = jnp.max(jnp.abs(emul - ref) / denom)
            meta = self._meta.get(site)
            for axis, _ in (meta.spmd_axes if meta is not None else ()):
                err = jax.lax.pmax(err, axis)

            def tap(e):
                # np-asarray-first: the callback runs on the runtime's
                # callback thread; launching a jax op here deadlocks
                # the single-threaded CPU runtime.
                val = float(np.max(np.asarray(e)))
                with self._lock:
                    self._realized = max(self._realized, val)
                    self._seen = True

            jax.debug.callback(tap, err)
        return (native if out_dtype is None
                else native.astype(out_dtype))


class NumericsMonitor:
    """Sample a training program every Nth step for realized GEMM error.

    Args:
      fn: the program to probe — the exact train step (or loss) the
        run executes, *unwrapped* (the monitor builds its own
        instrumented offload around it).
      plan: the active :class:`repro.tune.PrecisionPlan`; supplies the
        per-site split counts and the error budget.  Applied in
        ignore-unmatched mode so the monitor also works on a site
        subset (e.g. the forward-only loss).
      policy: alternative to ``plan`` — the active
        :class:`~repro.core.PrecisionPolicy` (a ``--backend`` run with
        uniform splits); the budget then defaults to 32 ulps of the
        probed dtype unless given.
      budget: override the end-to-end relative-error budget.
      every: check period in steps (``maybe_check``); 0 disables.
      registry/sink/log: optional telemetry destinations — a
        ``numerics_realized_rel`` gauge, a ``numerics`` JSONL event
        per check, and a structured WARNING on drift.
    """

    def __init__(self, fn, *, plan=None,
                 policy: Optional[PrecisionPolicy] = None,
                 budget: Optional[float] = None, every: int = 25,
                 registry=None, sink=None, log=None):
        if plan is None and policy is None:
            raise ValueError("NumericsMonitor needs a plan or a policy")
        if policy is None:
            policy = PrecisionPolicy.from_plan(
                plan, on_unmatched_site="ignore")
        self.plan = plan
        self.policy = policy
        self.every = int(every)
        self._budget = budget if budget is None else float(budget)
        self.registry = registry
        self.sink = sink
        self.log = log or get_logger("numerics")
        self._probe = _ProbeGemm(policy)
        # Jitted: one compiled pass, not an op-by-op evaluation of the
        # whole step, which on a chip compiles every op on its own.
        self._wrapped = jax.jit(offload(fn, policy, backend=self._probe))
        self.last_report: Optional[NumericsReport] = None

    def _resolve_budget(self) -> float:
        if self._budget is not None:
            return self._budget
        if self.plan is not None:
            return float(self.plan.budget)
        name = self._probe.probe_site
        meta = self._probe._meta.get(name) if name else None
        dtype = meta.dtype if meta is not None else jnp.float32
        return 32.0 * float(jnp.finfo(jnp.dtype(dtype)).eps)

    def due(self, step: int) -> bool:
        """Whether ``step`` lands on the check period."""
        return self.every > 0 and step % self.every == 0

    def maybe_check(self, step: int, *args,
                    **kwargs) -> Optional[NumericsReport]:
        """Run :meth:`check` when ``step`` lands on the period."""
        if not self.due(step):
            return None
        return self.check(step, *args, **kwargs)

    def check(self, step: int, *args, **kwargs) -> NumericsReport:
        """One instrumented pass; returns (and records) the report.

        The pass computes ``fn`` natively (outputs are discarded — the
        caller's training state is never touched) while the probe site
        additionally runs the deployed emulation against a ``dgemm``
        reference.
        """
        self._probe.reset()
        self._wrapped(*args, **kwargs)
        # Debug callbacks are asynchronous: drain before reading.
        jax.effects_barrier()
        realized = self._probe.realized()
        site = self._probe.probe_site or "<none>"
        splits = (self.policy.splits_for(site)
                  if self._probe.probe_site else 0)
        budget = self._resolve_budget()
        report = NumericsReport(
            step=int(step), site=site, splits=splits,
            realized_rel=float(realized or 0.0), budget=budget,
            drift=bool(realized is not None and realized > budget))
        self.last_report = report
        if self.registry is not None:
            self.registry.gauge("numerics_realized_rel",
                                site=site).set(report.realized_rel)
            if report.drift:
                self.registry.counter("numerics_drift",
                                      site=site).inc()
        if self.sink is not None:
            self.sink.emit("numerics", step=report.step, site=site,
                           splits=splits,
                           realized_rel=report.realized_rel,
                           budget=budget, drift=report.drift)
        if report.drift:
            self.log.warning(
                f"numerics drift at step {step}: site {site} realized "
                f"rel error {report.realized_rel:.3e} exceeds the "
                f"plan budget {budget:.3e} at s={splits} — the "
                "operands have moved since calibration; re-tune "
                "(launch/train.py --tune / python -m repro.tune)")
        else:
            self.log.debug(
                f"numerics ok at step {step}: site {site} realized "
                f"{report.realized_rel:.3e} <= budget {budget:.3e}")
        return report
