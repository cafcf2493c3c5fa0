"""GEMM backend registry: one dispatch point for every execution engine.

The paper's offloading tool has a single place where an intercepted
BLAS call is redirected to an execution engine; this module is the JAX
analogue.  Every way the repo can run a matmul — native, jnp Ozaki
emulation, the Pallas fused kernel, adaptive per-site tuning — is a
:class:`GemmBackend` obtained from a *spec string*, and it is here (and
only here) that a :class:`~repro.core.precision.PrecisionPolicy` binds
to execution.  The interceptor (:mod:`repro.core.intercept`), the MuST
app, and the benchmarks all resolve their engines through
:func:`get_backend`.

Spec-string grammar
-------------------

::

    spec    := family [ "_" splits ] [ ":" arg ]
    family  := registered name ("dgemm", "fp64_int8", "pallas_int8",
               "adaptive", ...)
    splits  := integer split count, pinning the precision (e.g.
               "fp64_int8_6"); without it the policy's per-site split
               count applies
    arg     := family-specific argument (e.g. the target relative
               error of "adaptive:1e-9")

Examples: ``"dgemm"``, ``"fp64_int8_6"``, ``"fp64_int8"``,
``"pallas_int8_6"``, ``"adaptive:1e-9"``.

New engines register with :func:`register_backend`; a factory receives
the parsed spec plus the binding policy and returns the backend.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from .ozaki import complex_matmul_via_real, ozaki_matmul, ozaki_ragged_dot
from .precision import (AdaptiveGemm, PrecisionPolicy,
                        splits_for_tolerance)

__all__ = [
    "GemmBackend",
    "register_backend",
    "get_backend",
    "registered_families",
    "example_specs",
]

_SPLITS_RE = re.compile(r"(?P<family>.+)_(?P<splits>\d+)")


class GemmBackend:
    """A 2-D matmul engine bound to a precision policy.

    Subclasses implement :meth:`matmul`; callers use the instance as a
    function.  The call contract is deliberately small so backends stay
    interchangeable inside ``vmap``/``jit`` traces:

    ``backend(a, b, out_dtype=None, num_splits=None, site="default")``

    * ``a``/``b`` — 2-D operands (real or complex floating);
    * ``out_dtype`` — result dtype (defaults to the promoted input
      dtype);
    * ``num_splits`` — call-site split request; honored unless the spec
      pinned a count (``"fp64_int8_6"`` is authoritative) and ignored
      by split-free engines (``"dgemm"``) and by ``"adaptive"``;
    * ``site`` — stable site name, used by stateful backends for
      per-site caching and by policies for per-site overrides.

    A backend with a grouped form also defines
    ``ragged_dot(lhs, rhs, group_sizes, dims, out_dtype=None,
    num_splits=None, site="default")``, the product of
    ``jax.lax.ragged_dot_general`` in the two forms of
    :func:`repro.core.ozaki.ragged_form`; where it is None (the Pallas
    kernels) the offload transform leaves grouped sites native and says
    so in the site's ``reason``.
    """

    #: The grouped product (see above), or None.
    ragged_dot = None

    #: The spec string this backend was built from (round-trips through
    #: :func:`get_backend`).
    spec: str = ""
    #: Whether the offload transform may wrap this backend's sites in
    #: the emulated-backward ``custom_vjp``.  Instrumentation backends
    #: (the tuner's calibration recorder) opt out: their side effects
    #: cannot stage through custom_vjp and their output is never
    #: differentiated.
    supports_vjp: bool = True
    #: When True, every eligible site routes through this instance,
    #: overriding per-site ``PrecisionPolicy.site_backends`` specs
    #: (again the calibration recorder: it must see the whole program).
    intercepts_all_sites: bool = False

    def __init__(self, spec: str, policy: PrecisionPolicy):
        self.spec = spec
        self.policy = policy

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        raise NotImplementedError

    def __call__(self, a, b, *, out_dtype=None, num_splits=None,
                 site: str = "default"):
        return self.matmul(a, b, out_dtype=out_dtype,
                           num_splits=num_splits, site=site)

    def __repr__(self):
        return f"{type(self).__name__}({self.spec!r})"


class DgemmBackend(GemmBackend):
    """Native XLA matmul — the reference engine (and the A/B control).

    Runs at ``Precision.HIGHEST``: a TPU's DEFAULT f32 matmul is one
    bf16 pass, too coarse for the reference every emulation error
    (calibration, the numerics monitor) is measured against.
    """

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        del num_splits, site
        c = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        return c.astype(out_dtype) if out_dtype is not None else c


class OzakiBackend(GemmBackend):
    """jnp Ozaki INT8 split-GEMM (:func:`repro.core.ozaki.ozaki_matmul`).

    A pinned spec (``"fp64_int8_6"``) is authoritative; an unpinned one
    (``"fp64_int8"``) resolves the split count per call, falling back
    to ``policy.splits_for(site)``.
    """

    def __init__(self, spec, policy, splits: Optional[int] = None):
        super().__init__(spec, policy)
        self.pinned_splits = splits

    def resolve_splits(self, num_splits, site) -> int:
        if self.pinned_splits is not None:
            return self.pinned_splits
        if num_splits is not None:
            return num_splits
        return self.policy.splits_for(site)

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        return ozaki_matmul(a, b,
                            num_splits=self.resolve_splits(num_splits, site),
                            accumulator=self.policy.accumulator,
                            out_dtype=out_dtype,
                            slice_bits=self.policy.slice_bits)

    def ragged_dot(self, lhs, rhs, group_sizes, dims, *, out_dtype=None,
                   num_splits=None, site: str = "default"):
        return ozaki_ragged_dot(
            lhs, rhs, group_sizes, dims,
            num_splits=self.resolve_splits(num_splits, site),
            accumulator=self.policy.accumulator, out_dtype=out_dtype,
            slice_bits=self.policy.slice_bits)


class PallasBackend(OzakiBackend):
    """Fused Pallas split-GEMM kernel (:mod:`repro.kernels.ops`).

    The kernel compiles through Mosaic on a TPU and runs in the Pallas
    interpreter on the CPU (the test platform); any other platform is
    refused rather than silently interpreted.  Complex operands
    decompose into four real kernel launches (same scheme as the jnp
    reference path).

    Block sizes come from the analytic model in
    :mod:`repro.kernels.tile_model` — consulted per (m, k, n, s), no
    autotuning sweep.  ``"pallas_int8*:fused"`` enables in-kernel
    slicing (operands enter as f32 hi/lo pairs and are quantized
    tile-by-tile in VMEM; slices never round-trip through HBM).
    The kernels have no grouped form (``ragged_dot`` is None).
    """

    ragged_dot = None

    def __init__(self, spec, policy, splits: Optional[int] = None,
                 fused: bool = False):
        super().__init__(spec, policy, splits)
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"{spec!r} needs a TPU (or the CPU, interpreted); the "
                f"default backend is {platform!r}")
        self.interpret = platform == "cpu"
        self.fused = fused

    def tile_decision(self, m, k, n, num_splits, dtype=None):
        """The model's block/schedule pick for one (m, k, n, s) site."""
        from repro.kernels import tile_model  # no Pallas dependency

        return tile_model.select_tiles(m, k, n, num_splits, dtype=dtype,
                                       fused=self.fused)

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        from repro.kernels import ops  # deferred: pallas may be absent

        s = self.resolve_splits(num_splits, site)
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        if out_dtype is None:
            out_dtype = jnp.result_type(a.dtype, b.dtype)
        out_dtype = jnp.dtype(out_dtype)

        def kernel(x, y, real_out):
            tiles = self.tile_decision(x.shape[0], x.shape[1],
                                       y.shape[1], s, dtype=real_out)
            return ops.ozaki_matmul(x, y, num_splits=s,
                                    out_dtype=real_out,
                                    slice_bits=self.policy.slice_bits,
                                    interpret=self.interpret,
                                    fuse_slicing=self.fused,
                                    tiles=tiles)

        # Same complex gate as the jnp reference path (inputs OR output
        # complex), same shared four-real-GEMM decomposition.
        if jnp.issubdtype(a.dtype, jnp.complexfloating) or \
           jnp.issubdtype(b.dtype, jnp.complexfloating) or \
           jnp.issubdtype(out_dtype, jnp.complexfloating):
            return complex_matmul_via_real(kernel, a, b, out_dtype)
        return kernel(a, b, out_dtype)


class AdaptiveBackend(GemmBackend):
    """Per-site tuned emulation (:class:`repro.core.precision.AdaptiveGemm`).

    On concrete operands the first call per site probes the split count
    empirically; inside a trace (``jit``/``vmap``/the offload
    transform, where operands are abstract) it falls back to the
    a-priori model :func:`~repro.core.precision.splits_for_tolerance`,
    which only needs the static contraction extent.
    """

    def __init__(self, spec, policy, target_rel: float):
        super().__init__(spec, policy)
        self.target_rel = float(target_rel)
        self.gemm = AdaptiveGemm(target_rel=self.target_rel,
                                 accumulator=policy.accumulator,
                                 slice_bits=policy.slice_bits)

    def matmul(self, a, b, *, out_dtype=None, num_splits=None,
               site: str = "default"):
        del num_splits  # adaptivity owns the split count
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
            s = splits_for_tolerance(self.target_rel, k=a.shape[-1],
                                     slice_bits=self.policy.slice_bits)
            return ozaki_matmul(a, b, num_splits=s,
                                accumulator=self.policy.accumulator,
                                out_dtype=out_dtype,
                                slice_bits=self.policy.slice_bits)
        return self.gemm(a, b, site=site, out_dtype=out_dtype)

    def ragged_dot(self, lhs, rhs, group_sizes, dims, *, out_dtype=None,
                   num_splits=None, site: str = "default"):
        """At the a-priori split count of the contraction extent, as
        inside a trace: grouped operands are never probed."""
        del num_splits, site
        (contract, _), _ = dims.dot_dimension_numbers
        s = splits_for_tolerance(self.target_rel,
                                 k=jnp.shape(lhs)[contract[0]],
                                 slice_bits=self.policy.slice_bits)
        return ozaki_ragged_dot(lhs, rhs, group_sizes, dims, num_splits=s,
                                accumulator=self.policy.accumulator,
                                out_dtype=out_dtype,
                                slice_bits=self.policy.slice_bits)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

#: family -> factory(spec, policy, splits, arg) -> GemmBackend
_FACTORIES: Dict[str, Callable[..., GemmBackend]] = {}


def register_backend(family: str,
                     factory: Callable[..., GemmBackend]) -> None:
    """Register a backend family under ``family``.

    ``factory(spec, policy, splits, arg)`` receives the full spec
    string, the binding policy, the optional ``_<splits>`` suffix (as
    int) and the optional ``:<arg>`` suffix (as str), and returns the
    backend instance.
    """
    _FACTORIES[family] = factory


def registered_families() -> List[str]:
    """Sorted registered family names."""
    return sorted(_FACTORIES)


def example_specs() -> List[str]:
    """One representative, resolvable spec per registered shape.

    Used by the registry round-trip tests and the README grammar table.
    """
    return ["dgemm", "fp64_int8", "fp64_int8_6", "pallas_int8_6",
            "pallas_int8_6:fused", "adaptive:1e-9"]


def get_backend(spec: str,
                policy: PrecisionPolicy | None = None) -> GemmBackend:
    """Resolve a spec string to a :class:`GemmBackend`.

    The returned backend carries ``spec`` verbatim (round-trip:
    ``get_backend(s).spec == s``) and binds ``policy`` (accumulator,
    slice bits, per-site splits) to execution.
    """
    policy = policy or PrecisionPolicy()
    head, sep, arg = (spec or "").partition(":")
    arg = arg if sep else None
    family, splits = head, None
    if family not in _FACTORIES:
        # Longest family wins: "fp64_int8_6" is family "fp64_int8"
        # with splits 6 (the greedy match peels one digit suffix).
        m = _SPLITS_RE.fullmatch(head)
        if m and m.group("family") in _FACTORIES:
            family, splits = m.group("family"), int(m.group("splits"))
        else:
            raise ValueError(
                f"unknown backend spec {spec!r}; registered families: "
                f"{', '.join(registered_families())} "
                "(grammar: family[_<splits>][:<arg>])")
    return _FACTORIES[family](spec=spec, policy=policy, splits=splits,
                              arg=arg)


def _dgemm_factory(spec, policy, splits, arg):
    if splits is not None or arg is not None:
        raise ValueError(f"'dgemm' takes no parameters, got {spec!r}")
    return DgemmBackend(spec, policy)


def _ozaki_factory(spec, policy, splits, arg):
    if arg is not None:
        raise ValueError(f"'fp64_int8' takes no ':<arg>', got {spec!r}")
    return OzakiBackend(spec, policy, splits)


def _pallas_factory(spec, policy, splits, arg):
    if arg not in (None, "fused"):
        raise ValueError(f"'pallas_int8' accepts only ':fused' as an "
                         f"argument, got {spec!r}")
    return PallasBackend(spec, policy, splits, fused=arg == "fused")


def _adaptive_factory(spec, policy, splits, arg):
    if splits is not None:
        raise ValueError(
            f"'adaptive' tunes its own split count, got {spec!r}")
    return AdaptiveBackend(spec, policy,
                           target_rel=float(arg) if arg else 1e-9)


register_backend("dgemm", _dgemm_factory)
register_backend("fp64_int8", _ozaki_factory)
register_backend("pallas_int8", _pallas_factory)
register_backend("adaptive", _adaptive_factory)
