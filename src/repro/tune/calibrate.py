"""Calibration: an instrumented pass that measures every GEMM site.

``Calibrator`` wraps a function exactly the way
:func:`repro.core.intercept.offload` does — same jaxpr walk, same
structural site names, same size/dtype gates — but routes every
eligible site through a *recording* backend instead of an execution
engine.  For each site call the backend:

* computes the native (``dgemm``) product — calibration output is the
  reference result, so a calibration step never perturbs training
  state;
* measures the relative error of the Ozaki emulation at a probe split
  count against that reference (normalized by ``|A| @ |B|``, the same
  convention as :func:`repro.core.precision.measure_splits`);
* records per-operand max-abs statistics.

Inside ``shard_map``/``pmap`` bodies the statistics are ``pmax``-shared
across the enclosing mesh axes *before* they leave the device, so
every shard records the same global numbers and a sharded calibration
run agrees with a single-device run on one plan.  The values reach the
host through ``jax.debug.callback`` — which fires inside ``scan`` /
``while`` / ``cond`` bodies too, so deeply nested sites are measured
per iteration and max-aggregated.

The result (:class:`CalibrationResult`) carries one
:class:`SiteRecord` per eligible site, keyed by the *canonical* site
name (SPMD scopes stripped), with the dp-invariant solver inputs:
contraction extent, dtype, per-step FLOPs (summed over shards and
scan trips), operand max-abs exponents, and the measured probe error
(quantized to two significant digits so mesh-layout ulp noise cannot
leak into solver decisions).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import GemmBackend
from repro.core.intercept import Site, offload
from repro.core.ozaki import ozaki_matmul
from repro.core.precision import PrecisionPolicy, canonical_site

from .plan import site_set_fingerprint

__all__ = ["Calibrator", "CalibrationResult", "SiteRecord"]


def _quantize(x: float, digits: int = 2) -> float:
    """Round to ``digits`` significant decimal digits.

    Calibration statistics cross mesh layouts: per-shard partial
    products can differ from the single-device computation in final
    ulps (different GEMM tilings), and solver inputs must not.  Two
    significant digits keeps the error magnitude (all the solver
    needs) while burying ulp noise ~14 orders of magnitude below the
    quantization step.
    """
    if x == 0.0 or not np.isfinite(x):
        return float(x)
    from math import floor, log10
    scale = 10.0 ** (digits - 1 - floor(log10(abs(x))))
    return round(x * scale) / scale


@dataclasses.dataclass
class SiteRecord:
    """Calibrated statistics for one eligible GEMM site."""

    site: str            #: canonical site name (SPMD scopes stripped)
    k: int               #: contraction extent (merged)
    dtype: str           #: result dtype name
    flops: int           #: per-step FLOPs across shards & scan trips
    probe_splits: int    #: split count the error probe ran at
    lhs_exp: Optional[int] = None   #: ceil(log2(max|A|)), None if unseen
    rhs_exp: Optional[int] = None   #: ceil(log2(max|B|))
    measured_rel: Optional[float] = None  #: probe error, 2 sig. digits
    calls: int = 0       #: host callback invocations (diagnostic only)
    #: canonical (k-only) tile-model pick at the probe split count for
    #: Pallas-family policies, ``(block_m, block_n, block_k)``;
    #: diagnostic — the solver re-derives tiles at the *solved* count.
    tiles: Optional[Tuple[int, int, int]] = None


class _Recorder:
    """Thread-safe max-aggregating sink for the device callbacks."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, Dict[str, float]] = {}

    def record(self, site: str, err, amax_l, amax_r) -> None:
        # The callback may run on the runtime's callback thread while
        # the device is blocked inside the calling computation:
        # launching any jax op here (np.max on a jax.Array dispatches
        # jnp.max!) deadlocks the single-threaded CPU runtime.  Pure
        # host transfers first, numpy-only reductions after.
        #
        # Under vmap the callback may deliver batched arrays; under a
        # mesh it fires once per device with identical (pmax-shared)
        # values — max + max-merge handles both, idempotently.
        err = float(np.max(np.asarray(err)))
        amax_l = float(np.max(np.asarray(amax_l)))
        amax_r = float(np.max(np.asarray(amax_r)))
        with self._lock:
            st = self._stats.setdefault(
                site, {"err": 0.0, "al": 0.0, "ar": 0.0, "calls": 0})
            st["err"] = max(st["err"], err)
            st["al"] = max(st["al"], amax_l)
            st["ar"] = max(st["ar"], amax_r)
            st["calls"] += 1

    def get(self, site: str) -> Optional[Dict[str, float]]:
        with self._lock:
            st = self._stats.get(site)
            return dict(st) if st is not None else None


class _CalibrationGemm(GemmBackend):
    """Recording backend: native result out, statistics to the host."""

    #: The offload transform skips the custom_vjp wrapper for this
    #: backend: debug-callback effects cannot be staged through
    #: custom_vjp, and calibration output is never differentiated.
    supports_vjp = False
    #: Every eligible site routes through this backend, overriding any
    #: per-site ``site_backends`` spec — calibration instruments the
    #: whole program.
    intercepts_all_sites = True

    def __init__(self, policy: PrecisionPolicy, probe_splits: int,
                 recorder: _Recorder):
        super().__init__("calibrate", policy)
        self.probe_splits = int(probe_splits)
        self.recorder = recorder
        self._meta: Dict[str, Site] = {}
        #: per-site measurement floor: below ~64 ulps of the reference
        #: dtype a probe error is reference noise, not signal (set at
        #: trace time — the floor is static per site).
        self.floors: Dict[str, float] = {}

    def observe_sites(self, decisions: Dict[str, Site]) -> None:
        # transform_jaxpr hands over the full Site records before the
        # trace starts; matmul() only receives the site *name* and
        # needs the enclosing SPMD axes to pmax the statistics.
        self._meta.update(decisions)

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        del num_splits  # the probe split count is fixed per pass
        meta = self._meta.get(site)
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        native = a @ b

        is_cplx = (jnp.issubdtype(a.dtype, jnp.complexfloating)
                   or jnp.issubdtype(b.dtype, jnp.complexfloating))
        ref_dtype = jnp.complex128 if is_cplx else jnp.float64
        if not jax.config.jax_enable_x64:
            ref_dtype = jnp.complex64 if is_cplx else jnp.float32
        floor = 64.0 * float(np.finfo(np.dtype(ref_dtype)).eps)
        self.floors[site] = max(self.floors.get(site, 0.0), floor)
        # HIGHEST: a TPU's DEFAULT f32 matmul is one bf16 pass.
        hi = jax.lax.Precision.HIGHEST
        ref = jnp.matmul(a.astype(ref_dtype), b.astype(ref_dtype),
                         precision=hi)
        emul = ozaki_matmul(a, b, num_splits=self.probe_splits,
                            accumulator=self.policy.accumulator,
                            out_dtype=ref_dtype,
                            slice_bits=self.policy.slice_bits)
        denom = jnp.matmul(jnp.abs(a).astype(jnp.abs(ref).dtype),
                           jnp.abs(b).astype(jnp.abs(ref).dtype),
                           precision=hi)
        denom = jnp.where(denom == 0, 1.0, denom)
        err = jnp.max(jnp.abs(emul - ref) / denom)
        amax_l = jnp.max(jnp.abs(a))
        amax_r = jnp.max(jnp.abs(b))
        # Share the statistics across the mesh *inside* the SPMD scope
        # so every device reports identical global values — this is
        # what makes a dp=N calibration agree with a single-device one.
        for axis, _ in (meta.spmd_axes if meta is not None else ()):
            err = jax.lax.pmax(err, axis)
            amax_l = jax.lax.pmax(amax_l, axis)
            amax_r = jax.lax.pmax(amax_r, axis)

        def tap(e, al, ar, _site=site):
            self.recorder.record(_site, e, al, ar)

        jax.debug.callback(tap, err, amax_l, amax_r)
        return (native if out_dtype is None
                else native.astype(out_dtype))


def _exp_of(amax: float) -> Optional[int]:
    if amax <= 0:
        return 0
    return int(np.ceil(np.log2(amax)))


@dataclasses.dataclass
class CalibrationResult:
    """Everything the plan solver consumes."""

    records: List[SiteRecord]
    fingerprint: str
    policy: PrecisionPolicy
    probe_splits: int
    #: raw (non-canonical) site names that were eligible, for reports
    site_names: Tuple[str, ...] = ()

    def describe(self) -> str:
        lines = [f"Calibration: {len(self.records)} eligible sites, "
                 f"probe s={self.probe_splits}, "
                 f"fingerprint {self.fingerprint}"]
        for r in sorted(self.records, key=lambda r: r.site):
            err = ("unmeasured" if r.measured_rel is None
                   else f"err~{r.measured_rel:.1e}")
            tiles = (" tiles={}x{}x{}".format(*r.tiles)
                     if r.tiles else "")
            lines.append(
                f"  {r.site}: k={r.k} {r.dtype} flops={r.flops:.3g} "
                f"exp=({r.lhs_exp},{r.rhs_exp}) {err}{tiles}")
        return "\n".join(lines)


class Calibrator:
    """Run instrumented passes over ``fn`` and collect site statistics.

    Usage::

        cal = Calibrator(train_step, policy)
        for batch in batches:
            cal.run(params, opt_state, batch)   # returns native output
        result = cal.result()
        plan = solve_plan(result)

    ``run`` executes ``fn`` with every eligible GEMM site instrumented
    (native results, so the pass is side-effect-free for the caller);
    repeated calls aggregate statistics by max.  The site set is fixed
    by the first signature; a later signature with a *different*
    eligible site set raises — one plan covers one program.
    """

    def __init__(self, fn, policy: Optional[PrecisionPolicy] = None,
                 *, probe_splits: Optional[int] = None):
        self.fn = fn
        self.policy = policy or PrecisionPolicy()
        self.probe_splits = int(probe_splits
                                if probe_splits is not None
                                else self.policy.default_splits)
        self._recorder = _Recorder()
        self._gemm = _CalibrationGemm(self.policy, self.probe_splits,
                                      self._recorder)
        # The exact offload wrapper/cache machinery, with the
        # recording backend injected as the (authoritative) engine.
        self._wrapped = offload(fn, self.policy, backend=self._gemm)
        self._sites: Optional[List[Site]] = None
        self._fingerprint: Optional[str] = None

    def run(self, *args, **kwargs):
        """One instrumented pass; returns ``fn``'s (native) output."""
        out = self._wrapped(*args, **kwargs)
        # Debug callbacks are asynchronous: drain them before the
        # recorder is read (or the next pass starts).
        jax.effects_barrier()
        sites = self._wrapped.sites(*args, **kwargs)  # cached
        fp = site_set_fingerprint(sites)
        if self._fingerprint is None:
            self._fingerprint = fp
            self._sites = sites
        elif fp != self._fingerprint:
            raise ValueError(
                "calibration signatures disagree on the eligible "
                f"site set ({fp} vs {self._fingerprint}); "
                "calibrate one program shape per plan")
        return out

    @property
    def sites(self) -> Optional[List[Site]]:
        """Site decisions of the calibrated program (after first run).

        The same (cached) records ``offload(...).sites`` would return
        for the calibration policy — consumers cost alternative split
        assignments against them (:func:`~repro.tune.count_int8_gemms`
        with ``splits_for``) without re-tracing.
        """
        return self._sites

    def _probe_tiles(self, k: int, dtype: str):
        """Canonical tile pick at the probe split count (Pallas only)."""
        spec = self.policy.backend
        if not spec.startswith("pallas_int8"):
            return None
        from repro.kernels import tile_model  # no Pallas dependency

        d = tile_model.select_tiles(None, k, None, self.probe_splits,
                                    dtype=dtype,
                                    fused=spec.endswith(":fused"))
        return (d.block_m, d.block_n, d.block_k)

    def result(self) -> CalibrationResult:
        """Aggregate the recorded statistics into solver inputs.

        Sites are merged by canonical name: the ``shmap0/scan0/dot1``
        of a sharded run and the ``scan0/dot1`` of a single-device run
        produce the same record.  A canonical collision between sites
        with *different* contraction extents or dtypes is ambiguous
        and raises.
        """
        if self._sites is None:
            raise ValueError("no calibration pass has run yet")
        by_canon: Dict[str, SiteRecord] = {}
        names = []
        for site in self._sites:
            if not site.eligible:
                continue
            names.append(site.name)
            canon = canonical_site(site.name)
            rec = by_canon.get(canon)
            if rec is None:
                rec = by_canon[canon] = SiteRecord(
                    site=canon, k=site.k, dtype=site.dtype.name,
                    flops=0, probe_splits=self.probe_splits,
                    tiles=self._probe_tiles(site.k, site.dtype.name))
            elif (rec.k, rec.dtype) != (site.k, site.dtype.name):
                raise ValueError(
                    f"sites {site.name!r} and an earlier one share "
                    f"the canonical name {canon!r} but disagree on "
                    f"k/dtype ({site.k}/{site.dtype.name} vs "
                    f"{rec.k}/{rec.dtype}); cannot key one plan "
                    "entry on both")
            rec.flops += site.flops
            st = self._recorder.get(site.name)
            if st is not None:
                floor = self._gemm.floors.get(site.name, 0.0)
                if st["al"] > 0 and st["ar"] > 0 and st["err"] > floor:
                    # Two degenerate measurements stay on the a-priori
                    # model curve instead of anchoring it: a zero
                    # operand (the zero-initialized LM head at step 0)
                    # measures error 0 and would under-split the site
                    # once it trains away from zero; and a probe at or
                    # below the reference dtype's noise floor (~64
                    # ulps — f32 references when x64 is off) measures
                    # the reference, not the emulation, and would
                    # both mis-anchor and fake a pathological site.
                    rec.measured_rel = _quantize(max(
                        st["err"], rec.measured_rel or 0.0))
                rec.lhs_exp = max(_exp_of(st["al"]), rec.lhs_exp
                                  if rec.lhs_exp is not None else -(2**30))
                rec.rhs_exp = max(_exp_of(st["ar"]), rec.rhs_exp
                                  if rec.rhs_exp is not None else -(2**30))
                rec.calls += int(st["calls"])
        return CalibrationResult(
            records=sorted(by_canon.values(), key=lambda r: r.site),
            fingerprint=self._fingerprint,
            policy=self.policy,
            probe_splits=self.probe_splits,
            site_names=tuple(names))
