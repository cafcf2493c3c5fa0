"""Automatic BLAS offload: a jaxpr->jaxpr transform over matrix products.

The paper intercepts BLAS calls of an *unmodified* application at the
linker level and redirects large GEMMs to the INT8 emulation engine.
The JAX analogue implemented here is a program transformation: trace
the user function once per input signature, rewrite every qualifying
``dot_general`` in the resulting :class:`ClosedJaxpr` to run through
the policy's GEMM backend (:mod:`repro.core.backends`), and evaluate
the *transformed* jaxpr on subsequent calls — so ``jax.jit(offload(fn))``
compiles the rewritten program with no per-call re-tracing.

What the transform covers:

* plain 2-D ``dot_general`` (any transposition of the contraction);
* batched and rank-N ``dot_general`` — batch/free/contraction axes are
  normalized to ``(B, M, K) @ (B, K, N)`` by transpose+reshape and the
  2-D backend is ``vmap``-ped over the merged batch axis (loop-free);
* sites inside nested ``jit`` / ``remat2`` (``jax.checkpoint``) bodies,
  which are inlined transparently;
* sites inside ``scan`` / ``while`` / ``cond`` bodies, which are
  rebuilt with transformed bodies;
* sites inside ``shard_map`` bodies (multi-device SPMD; ``jax.pmap``
  stages as a ``shard_map`` too, so its sites are ``shmap`` sites):
  the body is rebuilt around the rewriter under the same mesh and
  partition specs (``check_vma=False``); collective-adjacent equations
  are canonicalized — plain collectives re-bind as-is, while the
  varying-axis artifacts are undone (``pvary`` dropped,
  ``psum_invariant`` -> ``lax.psum``; replaying them verbatim corrupts
  the transpose rule) — and the size gate sees the *per-shard* operand
  shapes, so every device runs the same Ozaki split schedule a
  single-device run would;
* ``jit``-ted inner functions with ``NamedSharding``-annotated
  arguments: the ``jit`` body is inlined for site discovery and its
  in/out shardings are re-applied as ``with_sharding_constraint``, so
  the transformed program still partitions the same way under
  ``jax.jit``;
* reverse-mode AD: each offloaded site carries a ``custom_vjp`` whose
  backward pass runs the *same* backend on the transposed operands
  ("emulated backward"), so ``jax.grad`` works through offloaded code;
* grouped products, ``ragged_dot_general`` (what ``jax.lax.ragged_dot``
  and its VJP bind), in the two forms
  :func:`repro.core.ozaki.ragged_form` names: rows ragged against a
  stack of group matrices (the forward and dX), and a ragged contraction
  (dW).  They run through the backend's ``ragged_dot``, with a
  ``custom_vjp`` whose backward products are grouped too; a backend
  without a grouped form (the Pallas kernels) leaves them native.

Functions wrapped in ``jax.custom_jvp``/``jax.custom_vjp`` are left
opaque — rewriting their primal would silently discard the user's
derivative rule — so their internal matmuls stay native.  Every
contraction left native that is not a ``dot_general`` the gates kept
(contractions inside such calls, ``conv_general_dilated``, grouped
products in another form or under a backend without a grouped form)
is listed by primitive and name in the site list's ``native``
(:class:`SiteList`).

Site naming is structural and **shared verbatim** between
:func:`site_report` and :func:`offload`: ``dot{i}`` numbers the
``dot_general`` sites of a scope in program order (call-like primitives
are inlined into the enclosing scope), ``ragged{i}`` its grouped sites
on a count of their own, and control-flow/SPMD bodies
extend the path — ``scan0/dot1``, ``scan0/ragged1``,
``while2/cond/dot0``, ``cond1/br0/dot0``, ``shmap0/dot1``,
``shmap0/scan0/dot0``.
``PrecisionPolicy.site_splits`` keys against exactly these names, which
is the paper's "enumerate first, then tune per site" workflow.

Public API
----------

``offload(fn, policy)``
    Drop-in replacement for ``fn`` whose large matmuls run emulated.
    ``offload(fn, policy).sites(*args)`` returns the Site decisions for
    a given input signature without computing.

``site_report(fn, policy)``
    Same-signature function that lists the BLAS-3 sites the transform
    would touch (name, shapes, dtype, decision) instead of computing.

``transform_jaxpr(closed_jaxpr, policy)``
    The raw jaxpr->jaxpr transform: returns ``(transformed, sites)``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import warnings
from collections import OrderedDict, namedtuple
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import export as _jax_export  # not auto-imported by `import jax`
from jax._src import source_info_util
from jax.extend import core as jex_core

from .backends import GemmBackend, get_backend
from .ozaki import (RAGGED_CONTRACTION, RAGGED_ROWS, fold_runs,
                    ragged_form)
from .precision import PrecisionPolicy

__all__ = ["offload", "site_report", "transform_jaxpr", "Site",
           "SiteList", "Native", "CacheInfo", "PersistInfo",
           "OFFLOAD_CACHE_SIZE"]

#: How the walker and the evaluator treat a primitive: the one table of
#: primitive names both read (any other primitive is re-bound as is).
#:
#: * ``dot`` / ``ragged``: a site (``dot_general``, ``ragged_dot_general``);
#: * ``inline``: call-like primitives whose body is inlined into the
#:   enclosing scope — they neither change shapes nor iterate, so their
#:   sites share the enclosing scope's numbering ("jit" is a nested
#:   jax.jit; "remat2" is the primitive behind jax.checkpoint, and
#:   inlining it only trades the rematerialization schedule, not values
#:   or derivatives);
#: * ``scan`` / ``while`` / ``cond`` / ``shmap``: bodies with their own
#:   scope path and rebuild handlers;
#: * ``pvary`` / ``psum_invariant``: shard_map's varying-axis artifacts,
#:   undone in a rebuilt shard_map body;
#: * ``contraction``: a contraction with no Ozaki path, left native and
#:   listed in :attr:`SiteList.native`;
#: * ``opaque``: custom-derivative calls, deliberately NOT inlined: their
#:   bodies define their own differentiation semantics (stop-gradients,
#:   stabilized rules), so inlining the primal would silently replace
#:   the user's rule under jax.grad.  They take the default native
#:   re-bind; the contractions inside are listed as native.  Wrap the
#:   function's *caller* if those sites matter.
_PRIMITIVES = {
    "dot_general": "dot", "ragged_dot_general": "ragged",
    "jit": "inline", "closed_call": "inline", "remat2": "inline",
    "scan": "scan", "while": "while", "cond": "cond", "shard_map": "shmap",
    "pvary": "pvary", "psum_invariant": "psum_invariant",
    "conv_general_dilated": "contraction",
    "custom_jvp_call": "opaque", "custom_vjp_call": "opaque",
    "custom_vjp_call_jaxpr": "opaque",
}

#: A contraction the transform leaves native that is not a site the
#: gates kept native: its primitive, structural name and why.
Native = namedtuple("Native", ["primitive", "name", "reason"])


class SiteList(list):
    """The :class:`Site` records of one traced program, in discovery
    order, and ``native``: every contraction left native that is not a
    ``dot_general`` the gates kept (:class:`Native` records — grouped
    sites left native, ``conv_general_dilated``, contractions inside
    custom-derivative calls)."""

    def __init__(self, sites=(), skipped=()):
        super().__init__(sites)
        self.skipped = tuple(skipped)

    @property
    def native(self) -> Tuple[Native, ...]:
        grouped = tuple(Native(s.primitive, s.name, s.reason) for s in self
                        if s.primitive != "dot_general" and not s.offloaded)
        return grouped + self.skipped


def _check_overrides(policy: PrecisionPolicy, decisions) -> None:
    """Surface ``site_splits``/``site_backends`` keys that match nothing.

    A typo'd site name would otherwise silently run at the default
    split count — the exact failure mode per-site tuning exists to
    prevent.  ``policy.on_unmatched_site`` picks warn (default),
    raise (strict), or ignore (plans applied to a site subset).
    """
    mode = policy.on_unmatched_site
    if mode == "ignore" or not (policy.site_splits
                                or policy.site_backends):
        return
    if mode not in ("warn", "raise"):
        raise ValueError(
            f"on_unmatched_site must be 'warn', 'raise' or 'ignore', "
            f"got {mode!r}")
    unmatched = policy.unmatched_overrides(decisions)
    if not unmatched:
        return
    msg = (f"per-site override keys {unmatched} match no dot_general "
           f"site in the traced function (sites: "
           f"{sorted(decisions)}); they would silently have no effect")
    if mode == "raise":
        raise ValueError(msg)
    warnings.warn(msg, stacklevel=3)


class Site:
    """One discovered ``dot_general`` site and the decision taken.

    Beyond the decision itself the record carries the static facts the
    tuner (:mod:`repro.tune`) keys on: the normalized extents
    ``m``/``k``/``n``/``batch``, the static trip multiplicity ``mult``
    (how many times one step executes this site — the enclosing
    ``scan`` lengths multiplied out), the enclosing SPMD axes
    ``spmd_axes`` (``(name, size)`` pairs of the ``shard_map`` meshes
    the site runs under), the resolved per-site ``backend``
    spec, ``eligible`` — whether the site passed the dtype and size
    gates (a plan-demoted site is eligible but not offloaded) — and,
    for Pallas-family backends, ``tiles``: the analytic tile model's
    block/schedule pick for this site's geometry
    (:meth:`repro.kernels.tile_model.TileDecision.summary`).
    ``int8_dots`` is the number of INT8 dots one execution of an
    offloaded site's forward product issues: one per
    :func:`repro.core.ozaki.fold_runs` run of its split count and
    contraction, four times that for a complex site (0 when native).
    ``primitive`` is the site's contraction primitive; a grouped site
    (``ragged_dot_general``) has ``group_count`` groups, its contraction
    ``k`` (the rows, for a ragged contraction) and ``m`` at their static
    upper bound: how many rows a call really routes only the running
    program knows (the ``rows`` of its site events).
    """

    def __init__(self, name: str, lhs_shape, rhs_shape, dtype,
                 offloaded: bool, splits: int, reason: str, *,
                 m: int = 0, k: int = 0, n: int = 0, batch: int = 1,
                 mult: int = 1, spmd_axes=(), backend: str = "",
                 eligible: bool = False, tiles: dict | None = None,
                 int8_dots: int = 0, primitive: str = "dot_general",
                 group_count: int = 0):
        self.name = name
        self.lhs_shape = tuple(lhs_shape)
        self.rhs_shape = tuple(rhs_shape)
        self.dtype = jnp.dtype(dtype)
        self.offloaded = offloaded
        self.splits = splits
        self.reason = reason
        self.m, self.k, self.n, self.batch = m, k, n, batch
        self.mult = mult
        self.spmd_axes = tuple(spmd_axes)
        self.backend = backend
        self.eligible = eligible
        self.tiles = dict(tiles) if tiles else None
        self.int8_dots = int8_dots
        self.primitive = primitive
        self.group_count = group_count

    @property
    def flops(self) -> int:
        """Per-step FLOPs of this site, summed over mesh shards.

        ``2*batch*m*k*n`` per execution (a grouped site's rows at their
        upper bound: every row meets one group), times the static trip
        multiplicity, times the enclosing SPMD axis sizes (every shard
        runs the per-shard GEMM once), times 4 for the complex
        four-real-GEMM decomposition.
        """
        spmd = math.prod(s for _, s in self.spmd_axes)
        cplx = 4 if jnp.issubdtype(self.dtype, jnp.complexfloating) else 1
        return (2 * max(self.batch, 1) * self.m * self.k * self.n
                * self.mult * spmd * cplx)

    @property
    def spmd(self) -> str:
        """Mesh context, e.g. ``"dp=4,tp=2"`` (empty off-mesh)."""
        return ",".join(f"{name}={size}"
                        for name, size in self.spmd_axes)

    def __repr__(self):
        action = (f"offload splits={self.splits}" if self.offloaded
                  else f"native ({self.reason})")
        if self.tiles:
            action += (f" tiles={self.tiles['block_m']}x"
                       f"{self.tiles['block_n']}x{self.tiles['block_k']}")
        mesh = f" [{self.spmd}]" if self.spmd_axes else ""
        return (f"{self.name}{mesh}: {self.lhs_shape} @ "
                f"{self.rhs_shape} {self.dtype.name} -> {action}")


def _subjaxprs(eqn):
    """Yield (jaxpr, consts) for the body of a call-like equation."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        if hasattr(sub, "jaxpr"):  # ClosedJaxpr
            yield sub.jaxpr, sub.consts
        else:
            yield sub, []
        return


def _mesh_axes(mesh) -> Tuple[Tuple[str, int], ...]:
    """(name, size) pairs of a shard_map mesh (concrete or abstract)."""
    return tuple((str(name), int(mesh.shape[name]))
                 for name in mesh.axis_names)


def _local_shards(mesh) -> int:
    """Shards of a shard_map mesh that run in this process.

    A concrete mesh knows its local devices; an abstract one is taken
    to spread evenly over the processes.
    """
    if isinstance(mesh, jax.sharding.Mesh):
        return len(mesh.local_devices)
    return max(1, math.prod(s for _, s in _mesh_axes(mesh))
               // jax.process_count())


def _dynamic_trip(prefix: str) -> bool:
    """Whether a scope path lies under ``while`` or ``cond``, whose
    executions per call only the running program knows."""
    return any(part.startswith(("while", "cond"))
               for part in prefix.split("/"))


def site_scope(site: "Site") -> str:
    """The ``jax.named_scope`` a site's subgraph runs under.

    ``ozaki_<site>`` for an offloaded site, ``native_<site>`` for one
    left native, with the path's ``/`` written as ``.`` so that the
    scope stays one component of the op names in the device trace:
    ``ozaki_scan0.dot3``.
    """
    kind = "ozaki" if site.offloaded else "native"
    return f"{kind}_{site.name.replace('/', '.')}"


#: Body-carrying primitives share one count in a scope: ``scan0``,
#: ``cond1``, ``while2``.
_FLOW = ("scan", "while", "cond", "shmap")


def _next_name(prefix: str, kind: str, counters: dict) -> str:
    """``{prefix}{kind}{i}``: sites of each kind, and the bodies of
    :data:`_FLOW` together, numbered in program order in their scope."""
    key = "flow" if kind in _FLOW else kind
    i = counters.get(key, 0)
    counters[key] = i + 1
    return f"{prefix}{kind}{i}"


def _walk_sites(jaxpr, prefix: str = "", counters=None, out=None,
                mult: int = 1, spmd=(),
                native=None) -> List[Tuple[Any, str, int, tuple]]:
    """Enumerate the sites (``dot_general``, ``ragged_dot_general``
    equations) with their structural names.

    This single walker is the naming authority: both :func:`site_report`
    and the offload transform consume its ``(eqn, name, mult, spmd)``
    entries, so the two APIs can never diverge.  ``mult`` is the static
    trip multiplicity of the scope (the product of enclosing ``scan``
    lengths; ``while`` bodies and ``cond`` branches count as one — the
    trip count is dynamic) and ``spmd`` the enclosing SPMD axes as
    ``(name, size)`` pairs, both consumed by the site records the
    tuner calibrates against.  ``native``, where given, collects a
    :class:`Native` record of each contraction that is no site.
    """
    counters = {} if counters is None else counters
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        kind = _PRIMITIVES.get(prim)
        if kind in ("dot", "ragged"):
            out.append((eqn, _next_name(prefix, kind, counters), mult,
                        spmd))
        elif kind == "inline":
            for sub, _ in _subjaxprs(eqn):
                _walk_sites(sub, prefix, counters, out, mult, spmd, native)
        elif kind == "contraction":
            name = _next_name(prefix, "conv", counters)
            if native is not None:
                native.append(Native(prim, name,
                                     f"{prim} has no Ozaki path"))
        elif kind == "opaque":
            pfx = _next_name(prefix, "custom", counters) + "/"
            if native is not None:
                inner, inner_native = [], []
                for sub, _ in _subjaxprs(eqn):
                    _walk_sites(sub, pfx, out=inner, native=inner_native)
                native.extend(Native(e.primitive.name, name,
                                     f"inside {prim}: its derivative rule "
                                     "is kept") for e, name, _, _ in inner)
                native.extend(inner_native)
        elif kind == "shmap":
            # The body sees *per-shard* shapes: sites inside get their
            # offload decision (and size gate) against the local block,
            # so the per-device Ozaki schedule matches a single-device
            # run on one shard.
            _walk_sites(eqn.params["jaxpr"],
                        _next_name(prefix, "shmap", counters) + "/",
                        out=out, mult=mult,
                        spmd=spmd + _mesh_axes(eqn.params["mesh"]),
                        native=native)
        elif kind == "scan":
            body = eqn.params["jaxpr"]
            _walk_sites(body.jaxpr, _next_name(prefix, "scan", counters)
                        + "/", out=out,
                        mult=mult * int(eqn.params["length"]), spmd=spmd,
                        native=native)
        elif kind == "while":
            pfx = _next_name(prefix, "while", counters) + "/"
            _walk_sites(eqn.params["cond_jaxpr"].jaxpr, pfx + "cond/",
                        out=out, mult=mult, spmd=spmd, native=native)
            _walk_sites(eqn.params["body_jaxpr"].jaxpr, pfx, out=out,
                        mult=mult, spmd=spmd, native=native)
        elif kind == "cond":
            pfx = _next_name(prefix, "cond", counters) + "/"
            for bi, br in enumerate(eqn.params["branches"]):
                _walk_sites(br.jaxpr, f"{pfx}br{bi}/", out=out,
                            mult=mult, spmd=spmd, native=native)
    return out


def _has_grouped_form(spec: str, policy: PrecisionPolicy) -> bool:
    """Whether the backend ``spec`` names computes grouped products."""
    try:
        backend = get_backend(spec, policy=policy)
    except (ValueError, RuntimeError):
        return False
    return getattr(backend, "ragged_dot", None) is not None


def _classify(eqn, policy: PrecisionPolicy, name: str, mult: int = 1,
              spmd=(), grouped=None) -> Site:
    """Decide whether one site's equation gets offloaded.

    ``grouped(spec)`` says whether the backend ``spec`` has a grouped
    form (:func:`_has_grouped_form` by default).
    """
    if eqn.primitive.name == "ragged_dot_general":
        return _classify_ragged(eqn, policy, name, mult, spmd,
                                grouped or (lambda spec: _has_grouped_form(
                                    spec, policy)))
    lhs_aval, rhs_aval = (v.aval for v in eqn.invars)
    dtype = eqn.outvars[0].aval.dtype
    # The same normalization that will execute (batch dims excluded,
    # free/contraction extents merged) decides the size gate.
    dims = _DotDims(eqn.params["dimension_numbers"],
                    lhs_aval.shape, rhs_aval.shape)
    m, k, n = dims.M, dims.K, dims.N
    geom = dict(m=m, k=k, n=n, batch=dims.B, mult=mult,
                spmd_axes=spmd)

    def skip(reason, eligible=False, backend=""):
        return Site(name, lhs_aval.shape, rhs_aval.shape, dtype,
                    False, 0, reason, eligible=eligible,
                    backend=backend, **geom)

    if not (jnp.issubdtype(dtype, jnp.floating)
            or jnp.issubdtype(dtype, jnp.complexfloating)):
        return skip(f"dtype {jnp.dtype(dtype).name}")
    if min(m, k, n) < policy.min_dim:
        return skip(f"min(m,k,n)={min(m, k, n)} < min_dim={policy.min_dim}")
    backend = policy.backend_for(name)
    if backend == "dgemm":
        # A per-site demotion (typically from a precision plan that
        # found the site pathological): the site passes the gates —
        # it is *eligible*, and counts toward plan fingerprints — but
        # executes native.
        return skip("demoted to dgemm", eligible=True, backend=backend)
    splits = policy.splits_for(name)
    cplx = 4 if jnp.issubdtype(dtype, jnp.complexfloating) else 1
    return Site(name, lhs_aval.shape, rhs_aval.shape, dtype,
                True, splits, "", eligible=True, backend=backend,
                tiles=_tile_choice(backend, m, k, n, splits, dtype),
                int8_dots=cplx * len(fold_runs(splits, k,
                                               policy.slice_bits)),
                **geom)


def _classify_ragged(eqn, policy, name, mult, spmd, grouped) -> Site:
    """Decide whether one ``ragged_dot_general`` equation gets offloaded.

    ``m``/``k``/``n`` are those of each group's product, with the rows
    at their static upper bound: rows x k x n for ragged rows, and
    k x rows x n for a ragged contraction (the rows are contracted).
    """
    lhs, rhs, sizes = (v.aval for v in eqn.invars)
    dtype = eqn.outvars[0].aval.dtype
    dims = eqn.params["ragged_dot_dimension_numbers"]
    form = ragged_form(dims)
    m = k = n = 0
    if form == "rows" and lhs.ndim == 2 and rhs.ndim == 3:
        m, k, n = lhs.shape[0], lhs.shape[1], rhs.shape[2]
    elif form == "contraction" and lhs.ndim == 2 and rhs.ndim == 2:
        m, k, n = lhs.shape[1], lhs.shape[0], rhs.shape[1]
    else:
        form = None
    geom = dict(m=m, k=k, n=n, batch=1, mult=mult, spmd_axes=spmd,
                primitive="ragged_dot_general", group_count=sizes.shape[0])

    def skip(reason, eligible=False, backend=""):
        return Site(name, lhs.shape, rhs.shape, dtype, False, 0, reason,
                    eligible=eligible, backend=backend, **geom)

    if form is None or eqn.params.get("group_offset") is not None:
        return skip("no grouped Ozaki form for these dimension numbers")
    if not jnp.issubdtype(dtype, jnp.floating):
        return skip(f"dtype {jnp.dtype(dtype).name}")
    if min(m, k, n) < policy.min_dim:
        return skip(f"min(m,k,n)={min(m, k, n)} < min_dim={policy.min_dim}")
    backend = policy.backend_for(name)
    if backend == "dgemm":
        return skip("demoted to dgemm", eligible=True, backend=backend)
    if not grouped(backend):
        return skip(f"{backend} has no grouped form", backend=backend)
    splits = policy.splits_for(name)
    return Site(name, lhs.shape, rhs.shape, dtype, True, splits, "",
                eligible=True, backend=backend,
                int8_dots=len(fold_runs(splits, k, policy.slice_bits)),
                **geom)


def _tile_choice(backend_spec: str, m, k, n, splits, dtype):
    """Analytic tile pick for Pallas-family sites (None otherwise).

    The model itself never imports Pallas, so the decision is available
    (in reports, plans, obs events) even on hosts that cannot run the
    kernel.
    """
    if not backend_spec.startswith("pallas_int8"):
        return None
    from repro.kernels import tile_model  # deferred: core stays light

    decision = tile_model.select_tiles(
        m, k, n, splits, dtype=dtype,
        fused=backend_spec.endswith(":fused"))
    return decision.summary()


class _DotDims:
    """Normalization of a general ``dot_general`` to ``(B, M, K) @ (B, K, N)``.

    Batch axes merge (in batch-dim order) into a leading ``B``, the
    free/contraction axes merge into ``M``/``K``/``N``.  The inverse
    mappings recover operand-shaped cotangents for the backward pass.
    """

    def __init__(self, dimension_numbers, lhs_shape, rhs_shape):
        (lc, rc), (lb, rb) = dimension_numbers
        lfree = [d for d in range(len(lhs_shape))
                 if d not in lc and d not in lb]
        rfree = [d for d in range(len(rhs_shape))
                 if d not in rc and d not in rb]
        self.lperm = (*lb, *lfree, *lc)
        self.rperm = (*rb, *rc, *rfree)
        self.batch_shape = tuple(lhs_shape[d] for d in lb)
        self.m_shape = tuple(lhs_shape[d] for d in lfree)
        self.k_shape = tuple(lhs_shape[d] for d in lc)
        self.n_shape = tuple(rhs_shape[d] for d in rfree)
        self.has_batch = bool(lb)
        self.B = math.prod(self.batch_shape)
        self.M = math.prod(self.m_shape)
        self.K = math.prod(self.k_shape)
        self.N = math.prod(self.n_shape)

    def _lead(self, *tail):
        return (self.B, *tail) if self.has_batch else tail

    def pack_lhs(self, lhs):
        return jnp.transpose(lhs, self.lperm).reshape(
            self._lead(self.M, self.K))

    def pack_rhs(self, rhs):
        return jnp.transpose(rhs, self.rperm).reshape(
            self._lead(self.K, self.N))

    def pack_out(self, out):  # dot_general output is (batch, lfree, rfree)
        return out.reshape(self._lead(self.M, self.N))

    def unpack_out(self, y):
        return y.reshape(self.batch_shape + self.m_shape + self.n_shape)

    def unpack_lhs(self, dl):
        dl = dl.reshape(self.batch_shape + self.m_shape + self.k_shape)
        return jnp.transpose(dl, np.argsort(self.lperm))

    def unpack_rhs(self, dr):
        dr = dr.reshape(self.batch_shape + self.k_shape + self.n_shape)
        return jnp.transpose(dr, np.argsort(self.rperm))


def _site_dot(backend: GemmBackend, site: Site, dims: "_DotDims",
              out_dtype):
    """Build the backend-routed, AD-aware replacement for one site.

    Forward: normalized operands through the backend (``vmap`` over the
    merged batch axis when present).  Backward (``custom_vjp``): the
    standard matmul cotangents ``dA = g @ B^T`` / ``dB = A^T @ g``,
    also executed by the backend — tunable precision end to end.
    """

    def mm(a2, b2, odt):
        return backend(a2, b2, out_dtype=odt, num_splits=site.splits,
                       site=site.name)

    def bmm(a3, b3, odt):
        if dims.has_batch:
            return jax.vmap(lambda x, y: mm(x, y, odt))(a3, b3)
        return mm(a3, b3, odt)

    def fwd_impl(lhs, rhs):
        y = bmm(dims.pack_lhs(lhs), dims.pack_rhs(rhs), out_dtype)
        return dims.unpack_out(y)

    # Instrumentation backends (the tuner's calibration pass) stage
    # side effects the custom_vjp machinery cannot carry — and their
    # output is never differentiated — so they opt out of the wrapper.
    if not getattr(backend, "supports_vjp", True):
        return fwd_impl

    @jax.custom_vjp
    def dot(lhs, rhs):
        return fwd_impl(lhs, rhs)

    def dot_fwd(lhs, rhs):
        return fwd_impl(lhs, rhs), (lhs, rhs)

    def dot_bwd(res, g):
        lhs, rhs = res
        with jax.named_scope(site_scope(site)):
            l3 = dims.pack_lhs(lhs)
            r3 = dims.pack_rhs(rhs)
            g3 = dims.pack_out(g)
            swap = lambda x: jnp.swapaxes(x, -1, -2)  # noqa: E731
            dl = bmm(g3, swap(r3), lhs.dtype)
            dr = bmm(swap(l3), g3, rhs.dtype)
            return dims.unpack_lhs(dl), dims.unpack_rhs(dr)

    dot.defvjp(dot_fwd, dot_bwd)
    return dot


def _site_ragged(backend: GemmBackend, site: Site, dims, out_dtype):
    """The backend-routed, AD-aware replacement for one grouped site.

    Forward: ``backend.ragged_dot`` in the site's form.  Backward
    (``custom_vjp``): both cotangents as grouped products through the
    same backend — of ragged rows ``(m, k) x (g, k, n)``, dX is ragged
    rows against the groups' transposes and dW a ragged contraction; of
    a ragged contraction ``(m, k) x (m, n) -> (g, k, n)``, both are
    ragged rows.
    """

    def rd(lhs, rhs, sizes, form_dims, odt):
        return backend.ragged_dot(lhs, rhs, sizes, form_dims,
                                  out_dtype=odt, num_splits=site.splits,
                                  site=site.name)

    def fwd_impl(lhs, rhs, sizes):
        return rd(lhs, rhs, sizes, dims, out_dtype)

    if not getattr(backend, "supports_vjp", True):
        return fwd_impl

    @jax.custom_vjp
    def grouped(lhs, rhs, sizes):
        return fwd_impl(lhs, rhs, sizes)

    def grouped_fwd(lhs, rhs, sizes):
        return fwd_impl(lhs, rhs, sizes), (lhs, rhs, sizes)

    def grouped_bwd(res, g):
        lhs, rhs, sizes = res
        with jax.named_scope(site_scope(site)):
            if ragged_form(dims) == "rows":
                dl = rd(g, jnp.swapaxes(rhs, 1, 2), sizes, RAGGED_ROWS,
                        lhs.dtype)
                dr = rd(lhs, g, sizes, RAGGED_CONTRACTION, rhs.dtype)
            else:
                dl = rd(rhs, jnp.swapaxes(g, 1, 2), sizes, RAGGED_ROWS,
                        lhs.dtype)
                dr = rd(lhs, g, sizes, RAGGED_ROWS, rhs.dtype)
        return dl, dr, None

    grouped.defvjp(grouped_fwd, grouped_bwd)
    return grouped


def transform_jaxpr(closed, policy: PrecisionPolicy,
                    backend: GemmBackend | None = None,
                    on_site_event=None):
    """Rewrite ``closed`` (a ``ClosedJaxpr``) for emulated execution.

    Returns ``(transformed, sites)``: a new ``ClosedJaxpr`` with every
    offloaded ``dot_general`` replaced by a backend-routed subgraph
    (wrapped in its ``custom_vjp``), and the :class:`Site` decisions in
    discovery order.  The transform runs once; evaluating the result
    (``jax.core.eval_jaxpr``) never re-traces the user function.

    Every ``dot_general`` site runs under one ``jax.named_scope``,
    :func:`site_scope`: ``ozaki_<site>`` (``/`` written as ``.``) over
    an offloaded site's whole backend subgraph and its ``custom_vjp``
    backward, ``native_<site>`` over a site left native.  Scopes change
    only the ops' metadata (``op_name``), never the arithmetic, and
    name each site's ops in a device trace.

    ``on_site_event`` is the telemetry hook: a host callable receiving
    one static payload dict (site name, backend spec, splits, shapes,
    extents, flops, int8 dots) per *execution* of each offloaded site — per
    ``scan`` iteration, per local mesh shard of a ``shard_map``.  Where
    that count is static (a site at top level, under ``scan`` or under
    ``shard_map``: ``Site.mult`` times the local shards), one
    ``jax.debug.callback`` at the top level of the
    transformed program makes all of a call's reports at once, so the
    device waits on the host once a call, not once a site execution.
    A grouped site's payload also carries ``rows``: the rows that
    execution routed (``sum(group_sizes)``), which only the running
    program knows; the rows of the grouped sites under ``scan`` leave
    the loop as extra outputs, and they are the one callback's only
    operands (with no grouped site it takes none).
    A site under ``while`` or ``cond``, whose trip count only execution
    knows, keeps its own callback beside its backend call, outside its
    scope and never inside the ``custom_vjp`` (debug effects cannot
    stage through custom-derivative rules): it fires once per
    iteration or taken branch; so does a grouped site under
    ``shard_map``, whose rows differ by shard.  Those callbacks carry
    no array operand but a grouped site's rows: the payload is
    host-built at transform time, the hook
    adds no device compute, and — load-bearing, not just an
    optimization — an operand-carrying callback inside a loop body is
    *dropped entirely* by JAX's partial-eval when the loop is
    differentiated, whereas the zero-operand form is merely hoisted.
    Consequence: under reverse-mode AD *outside* the transformed
    function, a ``while``/``cond`` site reports once per call, not once
    per iteration (and a grouped one there not at all); every other
    site still reports ``Site.mult`` times
    (forward-only programs count exactly).  Handlers run on the
    runtime's callback threads and must follow the np-asarray-first
    rule: never launch jax ops from the handler.
    """
    backend = backend or get_backend(policy.backend, policy=policy)

    # Per-site backend routing: a site whose resolved spec differs
    # from the policy default (plan promotions, e.g. a single site on
    # the Pallas kernel) gets its own engine; sites on the default
    # spec share the passed-in instance (stateful engines like
    # "adaptive" keep one site cache across signatures).  A backend
    # declaring ``intercepts_all_sites`` (the calibration recorder) is
    # authoritative for every site regardless of per-site specs.
    engines: Dict[str, GemmBackend] = {policy.backend: backend}
    authoritative = getattr(backend, "intercepts_all_sites", False)

    def engine(spec: str) -> GemmBackend:
        if authoritative:
            return backend
        if spec not in engines:
            engines[spec] = get_backend(spec, policy=policy)
        return engines[spec]

    def grouped(spec: str) -> bool:
        try:
            return getattr(engine(spec), "ragged_dot", None) is not None
        except (ValueError, RuntimeError):
            return False

    native: List[Native] = []
    sites: List[Site] = []
    decisions: Dict[str, Site] = {}
    for eqn, name, mult, spmd in _walk_sites(closed.jaxpr, native=native):
        site = _classify(eqn, policy, name, mult, spmd, grouped)
        sites.append(site)
        decisions[name] = site
    sites = SiteList(sites, native)
    _check_overrides(policy, decisions)
    # An instrumentation backend (calibration) sees the full site
    # decisions — shapes, extents, trip multiplicity, SPMD axes —
    # before the first matmul call, which only carries the site name.
    observe = getattr(backend, "observe_sites", None)
    if observe is not None:
        observe(decisions)

    def engine_for(site: Site) -> GemmBackend:
        return engine(site.backend or policy.backend)

    def site_payload(site: Site) -> dict:
        payload = {
            "site": site.name,
            "backend": site.backend or policy.backend,
            "splits": int(site.splits),
            "lhs_shape": list(site.lhs_shape),
            "rhs_shape": list(site.rhs_shape),
            "dtype": site.dtype.name,
            "m": site.m, "k": site.k, "n": site.n,
            "batch": site.batch, "mult": site.mult,
            "spmd_axes": [list(ax) for ax in site.spmd_axes],
            "flops": site.flops,
            "int8_dots": site.int8_dots,
            "tiles": dict(site.tiles) if site.tiles else None,
        }
        if site.group_count:
            payload["group_count"] = site.group_count
        return payload

    def stage_site_events(counted, rows=()) -> None:
        # Static (payload, executions) pairs, built host-side once per
        # staging.  ``rows`` are (site, rows of each execution) of the
        # grouped sites, the callback's only operands; without them it
        # takes none and costs nothing on device.
        counted = tuple(counted)
        names = [name for name, _ in rows]

        def report(*values):
            routed = {name: np.asarray(v).reshape(-1)
                      for name, v in zip(names, values)}
            for payload, execs in counted:
                got = routed.get(payload["site"])
                for i in range(execs):
                    event = dict(payload)
                    if got is not None:
                        event["rows"] = int(got[i])
                    on_site_event(event)

        jax.debug.callback(report, *(v for _, v in rows))

    # Executions per call of each offloaded site with a static count,
    # in program order (reported by the one per-call callback), and the
    # local shards of each shard_map scope, keyed by its path prefix.
    static_execs: Dict[str, int] = {}
    local_shards: Dict[str, int] = {}

    def shards_of(prefix: str) -> int:
        parts = prefix.split("/")
        return math.prod(local_shards["/".join(parts[:i + 1]) + "/"]
                         for i, part in enumerate(parts)
                         if part.startswith("shmap"))

    def read_env(env, v):
        return v.val if isinstance(v, jex_core.Literal) else env[v]

    def run_site(site, eqn, invals, prefix, rows):
        """One site's replacement (or native re-bind) under its scope;
        its execution counted for the site-event hook."""
        ragged = site.primitive == "ragged_dot_general"
        # An authoritative instrumentation backend must see
        # every *eligible* site — including ones a plan
        # demoted to native — or re-calibration under a
        # from_plan policy would re-promote pathological
        # sites unmeasured.
        if on_site_event is not None and site.offloaded:
            routed = ([(site.name, jnp.sum(invals[2]))] if ragged
                      else [])
            if _dynamic_trip(prefix) or (ragged and "shmap" in prefix):
                stage_site_events([(site_payload(site), 1)], routed)
            else:
                static_execs[site.name] = site.mult * shards_of(prefix)
                if rows is not None:
                    rows.extend(routed)
        with jax.named_scope(site_scope(site)):
            if ragged and site.offloaded:
                dims = eqn.params["ragged_dot_dimension_numbers"]
                fn = _site_ragged(engine_for(site), site, dims,
                                  eqn.outvars[0].aval.dtype)
                return [fn(*invals)]
            if site.offloaded or (authoritative and site.eligible
                                  and not ragged):
                dims = _DotDims(eqn.params["dimension_numbers"],
                                site.lhs_shape, site.rhs_shape)
                fn = _site_dot(engine_for(site), site, dims,
                               eqn.outvars[0].aval.dtype)
                return [fn(invals[0], invals[1])]
            return [eqn.primitive.bind(*invals, **eqn.params)]

    # Decisions are keyed by the structural *name*, and the evaluator
    # re-derives names with the exact counter discipline of
    # _walk_sites.  Keying by equation identity would be wrong: JAX's
    # tracing cache reuses one body jaxpr object (hence the same eqn
    # objects) for every call of a jit-ed inner function, so distinct
    # sites can share an eqn.  Each equation is re-bound under the
    # scopes of its own name stack (:func:`_scopes_only`), so that the
    # program's ``jax.named_scope``s survive the rewrite.  ``rows``
    # collects (site, routed rows) of the scope's grouped sites.
    def eval_rewritten(jaxpr, consts: Sequence[Any], args: Sequence[Any],
                       prefix: str = "", counters=None, rows=None):
        counters = {} if counters is None else counters
        env = {}
        for var, const in zip(jaxpr.constvars, consts):
            env[var] = const
        for var, arg in zip(jaxpr.invars, args):
            env[var] = arg

        for eqn in jaxpr.eqns:
            invals = [read_env(env, v) for v in eqn.invars]
            prim = eqn.primitive.name
            kind = _PRIMITIVES.get(prim)
            name_stack = (source_info_util.current_name_stack()
                          + _scopes_only(eqn.source_info.name_stack))
            with source_info_util.user_context(eqn.source_info.traceback,
                                               name_stack=name_stack):
                if kind in ("dot", "ragged"):
                    site = decisions[_next_name(prefix, kind, counters)]
                    outvals = run_site(site, eqn, invals, prefix, rows)
                elif kind == "inline":
                    outvals = _eval_inline(eqn, invals, eval_rewritten,
                                           prefix, counters, rows)
                elif kind == "shmap":
                    pfx = _next_name(prefix, "shmap", counters) + "/"
                    local_shards[pfx] = _local_shards(eqn.params["mesh"])
                    outvals = _eval_shard_map(eqn, invals, eval_rewritten,
                                              pfx)
                elif kind == "pvary":
                    # shard_map's varying-axis tracking (check_vma)
                    # stages pvary markers into the body; they are
                    # physically the identity, and replaying them under
                    # the check_vma=False rebuild corrupts the transpose
                    # rule — drop them.
                    outvals = list(invals)
                elif kind == "psum_invariant":
                    # Same story for psum_invariant (the tracked psum):
                    # replay it as the plain collective so values AND
                    # cotangents come out right under the
                    # check_vma=False rebuild.  One bind over *all*
                    # operands: a bucketed gradient all-reduce stages one
                    # multi-operand psum per bucket, and replaying it per
                    # operand would silently de-fuse the buckets the
                    # overlap path exists to create.
                    outvals = list(jax.lax.psum(
                        tuple(invals), tuple(eqn.params["axes"]),
                        axis_index_groups=eqn.params.get(
                            "axis_index_groups")))
                elif kind == "scan":
                    pfx = _next_name(prefix, "scan", counters) + "/"
                    outvals = _eval_scan(eqn, invals, eval_rewritten, pfx,
                                         rows)
                elif kind == "while":
                    pfx = _next_name(prefix, "while", counters) + "/"
                    outvals = _eval_while(eqn, invals, eval_rewritten, pfx)
                elif kind == "cond":
                    pfx = _next_name(prefix, "cond", counters) + "/"
                    outvals = _eval_cond(eqn, invals, eval_rewritten, pfx)
                else:
                    # Canonical re-bind (same as jax.core.eval_jaxpr):
                    # get_bind_params re-wraps staged params — e.g. the
                    # jvp/fwd/bwd rules of opaque custom-derivative
                    # calls — into bindable form; plain primitives pass
                    # through.
                    subfuns, bind_params = eqn.primitive.get_bind_params(
                        eqn.params)
                    outvals = eqn.primitive.bind(*subfuns, *invals,
                                                 **bind_params)
                    if not eqn.primitive.multiple_results:
                        outvals = [outvals]
            for var, val in zip(eqn.outvars, outvals):
                env[var] = val

        return [read_env(env, v) for v in jaxpr.outvars]

    def interp(*flat_args):
        rows: List[Tuple[str, Any]] = []
        outs = eval_rewritten(closed.jaxpr, closed.consts, flat_args,
                              rows=rows)
        if static_execs:
            stage_site_events(((site_payload(decisions[name]), execs)
                               for name, execs in static_execs.items()),
                              rows)
        return outs

    in_specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                for v in closed.jaxpr.invars]
    transformed = jax.make_jaxpr(interp)(*in_specs)
    return transformed, sites


def _scopes_only(name_stack):
    """``name_stack`` without its transforms.

    A transform (``jvp``, ``transpose``) prints wrapped around the next
    scope: a site scope pushed under it would read
    ``transpose(jvp(ozaki_dot1))`` instead of one ``ozaki_dot1``
    component of the op's name.
    """
    return source_info_util.NameStack(tuple(
        e for e in name_stack.stack
        if isinstance(e, source_info_util.Scope)))


def _eval_inline(eqn, invals, eval_body, prefix, counters, rows):
    """Inline a call-like equation's body into the enclosing scope.

    Inlining a jit discards its partitioning params, so NamedSharding
    annotations on the inner jit are re-applied as sharding constraints
    around the inlined body — offload(jax.jit(fn, in_shardings=...))
    keeps partitioning exactly as the user declared it.
    """
    jit = eqn.primitive.name == "jit"
    if jit:
        invals = _apply_shardings(invals, eqn.params.get("in_shardings"))
    outvals = None
    for sub, sub_consts in _subjaxprs(eqn):
        outvals = eval_body(sub, sub_consts, invals, prefix, counters, rows)
    if outvals is None:  # no body found — bind natively
        outvals = eqn.primitive.bind(*invals, **eqn.params)
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
    elif jit:
        outvals = _apply_shardings(outvals, eqn.params.get("out_shardings"))
    return outvals


def _eval_scan(eqn, invals, eval_body, prefix, rows=None):
    """Rebuild a ``scan`` with its body routed through the rewriter.

    The rows of the body's grouped sites (``rows``, see
    :func:`transform_jaxpr`) leave the loop as extra stacked outputs,
    one per iteration.
    """
    p = eqn.params
    nc, ncar = p["num_consts"], p["num_carry"]
    body = p["jaxpr"]
    consts = invals[:nc]
    init = tuple(invals[nc:nc + ncar])
    xs = tuple(invals[nc + ncar:])
    names: List[str] = []

    def body_fun(carry, x):
        # Fresh counters per trace of the body: scan may re-trace it
        # (carry fixed-point), and names must restart each time.
        routed = None if rows is None else []
        outs = eval_body(body.jaxpr, body.consts, [*consts, *carry, *x],
                         prefix, rows=routed)
        names[:] = [name for name, _ in routed or ()]
        return tuple(outs[:ncar]), (tuple(outs[ncar:]),
                                    tuple(v for _, v in routed or ()))

    carry_out, (ys, routed) = jax.lax.scan(
        body_fun, init, xs, length=p["length"], reverse=p["reverse"],
        unroll=p.get("unroll", 1))
    if rows is not None:
        rows.extend(zip(names, routed))
    return [*carry_out, *ys]


def _eval_while(eqn, invals, eval_body, prefix):
    """Rebuild a ``while`` with cond/body routed through the rewriter."""
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    cond_jaxpr, body_jaxpr = p["cond_jaxpr"], p["body_jaxpr"]
    cconsts = invals[:cn]
    bconsts = invals[cn:cn + bn]
    init = tuple(invals[cn + bn:])

    def cond_fun(val):
        return eval_body(cond_jaxpr.jaxpr, cond_jaxpr.consts,
                         [*cconsts, *val], prefix + "cond/")[0]

    def body_fun(val):
        return tuple(eval_body(body_jaxpr.jaxpr, body_jaxpr.consts,
                               [*bconsts, *val], prefix))

    return list(jax.lax.while_loop(cond_fun, body_fun, init))


def _eval_cond(eqn, invals, eval_body, prefix):
    """Rebuild a ``cond``/``switch`` with rewritten branches."""
    branches = eqn.params["branches"]
    index, *operands = invals

    def branch_fun(bi, br):
        return lambda *ops: tuple(eval_body(br.jaxpr, br.consts,
                                            list(ops),
                                            f"{prefix}br{bi}/"))

    return list(jax.lax.switch(
        index, [branch_fun(bi, br) for bi, br in enumerate(branches)],
        *operands))


def _apply_shardings(vals, shardings):
    """Constrain ``vals`` to the concrete shardings of a jit eqn.

    Entries that are not actual :class:`jax.sharding.Sharding` objects
    (``UnspecifiedValue`` placeholders from a plain ``jax.jit``) leave
    the value untouched.
    """
    if shardings is None:
        return vals
    out = []
    for val, sh in zip(vals, shardings):
        if isinstance(sh, jax.sharding.Sharding):
            val = jax.lax.with_sharding_constraint(val, sh)
        out.append(val)
    return out


def _eval_shard_map(eqn, invals, eval_body, prefix):
    """Rebuild a ``shard_map`` with its body routed through the rewriter.

    The body is re-traced under the original mesh, manual axes and
    partition specs, so per-shard sites run the backend on their local
    block and collectives replay in place.  ``check_vma=False``: the
    recorded body already carries the varying-axis artifacts
    (``pvary``/``psum_invariant``), which the evaluator canonicalizes
    back to plain collectives — running the tracking again on top of
    them would double-apply it (and it has no rules for the offloaded
    sites' ``custom_vjp`` wrappers).
    """
    p = eqn.params
    body = p["jaxpr"]

    def body_fun(*args):
        return tuple(eval_body(body, (), list(args), prefix))

    rebuilt = jax.shard_map(
        body_fun, mesh=p["mesh"], in_specs=p["in_specs"],
        out_specs=p["out_specs"], axis_names=p["manual_axes"],
        check_vma=False)
    return list(rebuilt(*invals))


def _signature(flat_args):
    # Python scalars trace as weakly-typed avals: keep them distinct
    # from same-dtype arrays so a cached transform is never reused
    # across a promotion-semantics boundary.
    return tuple((jnp.shape(x), jnp.result_type(x),
                  isinstance(x, (bool, int, float, complex)))
                 for x in flat_args)


#: ``wrapped.cache_info()`` record, same shape as functools.lru_cache's.
CacheInfo = namedtuple("CacheInfo", ["hits", "misses", "maxsize",
                                     "currsize"])

#: ``wrapped.persist_info()`` record for the on-disk transform cache:
#: ``disk_hits`` — entries restored with a runnable exported program
#: (no re-trace, no re-transform); ``disk_decisions_hits`` — entries
#: whose site decisions were restored and byte-verified but whose
#: program had to be re-traced (no exported artifact on disk);
#: ``disk_misses`` — entries traced fresh and written out.
PersistInfo = namedtuple("PersistInfo", ["disk_hits",
                                         "disk_decisions_hits",
                                         "disk_misses", "directory"])

#: Bumped whenever the persisted payload layout changes; part of the
#: cache key, so stale-format files are simply never looked up.
_PERSIST_FORMAT = 3


def _site_payload(sites: Sequence[Site]) -> dict:
    """Site records and the native contractions (a :class:`SiteList`)
    as plain JSON data (the persisted decision set)."""
    return {"sites": [{"name": s.name, "lhs_shape": list(s.lhs_shape),
             "rhs_shape": list(s.rhs_shape), "dtype": s.dtype.name,
             "offloaded": bool(s.offloaded), "splits": int(s.splits),
             "reason": s.reason, "m": int(s.m), "k": int(s.k),
             "n": int(s.n), "batch": int(s.batch), "mult": int(s.mult),
             "spmd_axes": [[a, int(x)] for a, x in s.spmd_axes],
             "backend": s.backend, "eligible": bool(s.eligible),
             "tiles": s.tiles, "int8_dots": int(s.int8_dots),
             "primitive": s.primitive, "group_count": int(s.group_count)}
            for s in sites],
            "native": [list(n) for n in getattr(sites, "skipped", ())]}


def _sites_from_payload(payload: dict) -> SiteList:
    return SiteList(
        [Site(p["name"], p["lhs_shape"], p["rhs_shape"], p["dtype"],
              p["offloaded"], p["splits"], p["reason"], m=p["m"],
              k=p["k"], n=p["n"], batch=p["batch"], mult=p["mult"],
              spmd_axes=[tuple(a) for a in p["spmd_axes"]],
              backend=p["backend"], eligible=p["eligible"],
              tiles=p["tiles"], int8_dots=p["int8_dots"],
              primitive=p["primitive"], group_count=p["group_count"])
         for p in payload["sites"]],
        [Native(*n) for n in payload["native"]])


def _sites_bytes(sites: Sequence[Site]) -> bytes:
    """Canonical byte encoding of the decision set.  Two processes that
    take the same decisions produce *identical bytes* — the warm-start
    restart test compares these files directly."""
    return json.dumps(_site_payload(sites), sort_keys=True,
                      separators=(",", ":")).encode()


def _persist_key(fn_label, in_tree, sig, policy, plan, hooked) -> str:
    """Content-address one transform-cache entry.

    Keyed the way jax's own ``compilation_cache`` keys executables: a
    hash over everything that determines the transform's output — the
    function identity (label), the input pytree structure and abstract
    signature, the full policy, the plan fingerprint, and the library
    versions — so an entry is reused exactly when re-tracing would have
    reproduced it.
    """
    payload = {
        "format": _PERSIST_FORMAT,
        "fn": fn_label,
        "in_tree": str(in_tree),
        "signature": [[list(shape), str(np.dtype(dt)), bool(weak)]
                      for shape, dt, weak in sig],
        "policy": dataclasses.asdict(policy),
        "plan": getattr(plan, "fingerprint", None),
        "hooked": bool(hooked),
        "jax": jax.__version__,
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


class _DiskCache:
    """Fingerprinted on-disk transform cache (one dir, flat files).

    ``<key>.json`` holds the canonical site-decision bytes;
    ``<key>.bin`` holds the ``jax.export``-serialized program when the
    entry was exportable.  Writes are atomic (tmp + rename), corrupt or
    missing files degrade to a miss — never an error.
    """

    def __init__(self, directory):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)

    def _path(self, key: str, ext: str) -> str:
        return os.path.join(self.directory, f"{key}.{ext}")

    def load(self, key: str):
        """-> (raw decision bytes | None, deserialized Exported | None)."""
        try:
            with open(self._path(key, "json"), "rb") as f:
                raw = f.read()
        except OSError:
            return None, None
        exported = None
        try:
            with open(self._path(key, "bin"), "rb") as f:
                exported = _jax_export.deserialize(bytearray(f.read()))
        except OSError:
            pass
        except Exception as exc:  # corrupt/incompatible artifact
            warnings.warn(f"persisted transform program {key}.bin "
                          f"unusable ({exc!r}); re-tracing")
        return raw, exported

    def store(self, key: str, raw_json: bytes,
              exported_bytes: bytes | None) -> None:
        self._write(self._path(key, "json"), raw_json)
        if exported_bytes is not None:
            self._write(self._path(key, "bin"), exported_bytes)

    def _write(self, path: str, data: bytes) -> None:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)


class _Entry:
    """One transform-cache entry; ``runnable`` is set for disk-restored
    exported programs and for ``jit_entries`` wrappers."""

    __slots__ = ("transformed", "sites", "out_tree", "runnable")

    def __init__(self, transformed, sites, out_tree, runnable=None):
        self.transformed = transformed
        self.sites = sites
        self.out_tree = out_tree
        self.runnable = runnable


def _entry_runner(transformed, out_tree):
    """A jit-compiled callable over the original (args, kwargs)
    signature that evaluates one transformed jaxpr."""

    def run(*args, **kwargs):
        flat, _ = jax.tree_util.tree_flatten((args, kwargs))
        out = jax.core.eval_jaxpr(transformed.jaxpr,
                                  transformed.consts, *flat)
        return jax.tree_util.tree_unflatten(out_tree, out)

    return jax.jit(run)


def _export_entry(transformed, out_tree, args, kwargs):
    """``jax.export``-serialize one entry's program, or None.

    Export legitimately fails for programs the serializer cannot carry
    (debug callbacks, unstable custom calls); the caller then persists
    decisions only.
    """

    def run(*a, **kw):
        flat, _ = jax.tree_util.tree_flatten((a, kw))
        out = jax.core.eval_jaxpr(transformed.jaxpr,
                                  transformed.consts, *flat)
        return jax.tree_util.tree_unflatten(out_tree, out)

    try:
        exp = _jax_export.export(jax.jit(run))(*args, **kwargs)
        return exp.serialize()
    except Exception as exc:
        warnings.warn(f"transform entry not exportable ({exc!r}); "
                      "persisting decisions only")
        return None

#: Default bound on the per-wrapper transform cache.  Serve-style
#: callers present an open-ended stream of signatures (every padded
#: batch/prompt size is a new key), so the cache must evict, not grow.
OFFLOAD_CACHE_SIZE = 64


def offload(fn, policy: PrecisionPolicy | None = None, *,
            plan=None, plan_match: str = "strict",
            backend: GemmBackend | None = None,
            on_site_event=None,
            cache_size: int = OFFLOAD_CACHE_SIZE,
            persist_dir=None, fn_label: str | None = None,
            on_cache_event=None, jit_entries: bool = False):
    """Wrap ``fn`` so its large matmuls run through the policy backend.

    The first call for a given input signature traces ``fn`` once and
    transforms the jaxpr (see :func:`transform_jaxpr`); the transformed
    program is cached and later calls only evaluate it, so
    ``jax.jit(offload(fn, policy))`` compiles with no per-call
    re-tracing.  Batched/rank-N sites, sites inside ``scan``/``while``/
    ``cond``/``shard_map`` bodies, and reverse-mode AD are all
    supported; see the module docstring.

    ``plan`` accepts a :class:`repro.tune.PrecisionPlan`: when no
    explicit ``policy`` is given, the plan's policy
    (:meth:`PrecisionPolicy.from_plan`) drives the transform, and with
    ``plan_match="strict"`` every new signature's traced site set is
    validated against the plan's fingerprint
    (:meth:`~repro.tune.PrecisionPlan.validate_sites`) — a drifted
    program raises instead of silently running mis-tuned.
    ``plan_match="subset"`` skips the fingerprint check and just
    applies the overlapping per-site entries (the serve engine runs a
    train-calibrated plan this way).

    ``backend`` injects the default :class:`GemmBackend` instance
    instead of resolving ``policy.backend`` — the tuner's calibration
    pass rides the exact same wrapper/cache machinery this way, with
    its recording backend swapped in.

    ``on_site_event`` enables per-site execution telemetry: a host
    callable invoked with a static payload dict once per execution of
    each offloaded site — per ``scan`` iteration, per local mesh shard.
    One zero-operand ``jax.debug.callback`` a call makes the reports of
    every site whose count is static; a site under ``while``/``cond``
    keeps a callback of its own; see :func:`transform_jaxpr`.  Pass
    ``MetricsRun.site_event_handler()`` from :mod:`repro.obs` to count
    executions into a metrics run.  Note debug callbacks are
    asynchronous: call ``jax.effects_barrier()`` before reading
    anything the handler accumulates.

    The transform cache is a ``cache_size``-bounded LRU (least recently
    *used* signature evicted first), so signature churn — a serving
    loop padding every admission wave to a fresh (batch, prompt) shape
    — cannot retain unbounded transformed jaxprs.  Inspect it with
    ``wrapped.cache_info()`` and reset it with ``wrapped.cache_clear()``.

    The returned wrapper exposes ``wrapped.sites(*args, **kwargs)``,
    the exact :class:`Site` decisions taken for that signature — the
    same objects :func:`site_report` would produce, same names.

    ``persist_dir`` additionally persists the transform cache to disk,
    content-addressed the way jax's ``compilation_cache.py`` keys
    executables (function label + input signature + policy + plan
    fingerprint + library versions; see :func:`_persist_key`).  Each
    entry is two files: ``<key>.json``, the canonical byte encoding of
    the site decisions (two processes taking the same decisions write
    identical bytes), and ``<key>.bin``, the ``jax.export``-serialized
    program when exportable (it is not when ``on_site_event`` is set —
    debug callbacks cannot be serialized).  A restarted process that
    finds both files reuses the program without re-tracing or
    re-transforming; decisions-only entries are re-traced but
    byte-verified against the persisted decisions.  ``fn_label`` names
    the function in the key (defaults to ``fn.__name__`` — pass an
    explicit stable label, lambdas all share ``"<lambda>"``);
    ``on_cache_event`` is called with ``"miss"`` / ``"disk_hit"`` /
    ``"disk_decisions_hit"`` as entries resolve (in-memory hits are
    silent); ``wrapped.persist_info()`` returns the tallies.

    ``jit_entries=True`` gives every cache entry its own jit-compiled
    runner over the original call signature, so the wrapper is called
    *directly* instead of under an outer ``jax.jit`` — required when
    entries may come from disk as exported programs (which carry their
    own compilation) and fresh trace fallbacks must match.
    """
    if plan_match not in ("strict", "subset"):
        raise ValueError(f"plan_match must be 'strict' or 'subset', "
                         f"got {plan_match!r}")
    if policy is None:
        if plan is not None:
            # Subset mode exists for functions that trace a subset of
            # the calibrated sites (serving a train plan): the plan's
            # unmatched entries are expected there, not typos to warn
            # about.
            policy = PrecisionPolicy.from_plan(
                plan, **({"on_unmatched_site": "ignore"}
                         if plan_match == "subset" else {}))
        else:
            policy = PrecisionPolicy()
    backend = backend or get_backend(policy.backend, policy=policy)
    if cache_size < 1:
        raise ValueError(f"cache_size must be >= 1, got {cache_size}")
    cache: "OrderedDict[Any, _Entry]" = OrderedDict()
    stats = {"hits": 0, "misses": 0}
    pstats = {"disk_hits": 0, "disk_decisions_hits": 0,
              "disk_misses": 0}
    disk = _DiskCache(persist_dir) if persist_dir is not None else None
    label = fn_label or getattr(fn, "__name__", "fn")

    def _event(kind: str) -> None:
        if on_cache_event is not None:
            on_cache_event(kind)

    def build(args, kwargs):
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        sig = _signature(flat)
        key = (in_tree, sig)
        entry = cache.get(key)
        if entry is not None:
            stats["hits"] += 1
            cache.move_to_end(key)
            return flat, entry

        raw = dkey = None
        if disk is not None:
            dkey = _persist_key(label, in_tree, sig, policy, plan,
                                on_site_event is not None)
            raw, exported = disk.load(dkey)
            if raw is not None:
                try:
                    restored = _sites_from_payload(json.loads(raw))
                except Exception as exc:
                    warnings.warn(f"persisted transform decisions "
                                  f"{dkey}.json unreadable ({exc!r}); "
                                  "re-tracing")
                    raw = None
                else:
                    if exported is not None:
                        # Full warm start: restored program, zero
                        # tracing/transform work in this process.
                        pstats["disk_hits"] += 1
                        _event("disk_hit")
                        entry = _Entry(None, restored, None,
                                       jax.jit(exported.call))
                        cache[key] = entry
                        while len(cache) > cache_size:
                            cache.popitem(last=False)
                        return flat, entry

        stats["misses"] += 1
        closed, out_shape = jax.make_jaxpr(
            fn, return_shape=True)(*args, **kwargs)
        transformed, sites = transform_jaxpr(
            closed, policy, backend, on_site_event=on_site_event)
        if plan is not None and plan_match == "strict":
            plan.validate_sites(sites)
        out_tree = jax.tree_util.tree_structure(out_shape)
        entry = _Entry(transformed, sites, out_tree)
        if jit_entries:
            entry.runnable = _entry_runner(transformed, out_tree)
        if disk is not None:
            fresh = _sites_bytes(sites)
            if raw is not None:
                # Decisions were on disk (no runnable program): the
                # re-trace must reproduce them byte-for-byte, or the
                # environment changed under a colliding key.
                if fresh != raw:
                    warnings.warn(
                        f"persisted transform decisions {dkey}.json "
                        "do not match this process's re-trace; "
                        "overwriting with the fresh decisions")
                    disk.store(dkey, fresh, None)
                pstats["disk_decisions_hits"] += 1
                _event("disk_decisions_hit")
            else:
                pstats["disk_misses"] += 1
                _event("miss")
                exported_bytes = None
                if on_site_event is None:
                    exported_bytes = _export_entry(transformed,
                                                   out_tree, args,
                                                   kwargs)
                disk.store(dkey, fresh, exported_bytes)
        cache[key] = entry
        while len(cache) > cache_size:
            cache.popitem(last=False)
        return flat, entry

    def wrapped(*args, **kwargs):
        flat, entry = build(args, kwargs)
        if entry.runnable is not None:
            return entry.runnable(*args, **kwargs)
        out_flat = jax.core.eval_jaxpr(entry.transformed.jaxpr,
                                       entry.transformed.consts, *flat)
        return jax.tree_util.tree_unflatten(entry.out_tree, out_flat)

    def sites(*args, **kwargs) -> SiteList:
        _, entry = build(args, kwargs)
        return entry.sites

    def cache_info() -> CacheInfo:
        return CacheInfo(stats["hits"], stats["misses"], cache_size,
                         len(cache))

    def persist_info() -> PersistInfo:
        return PersistInfo(pstats["disk_hits"],
                           pstats["disk_decisions_hits"],
                           pstats["disk_misses"],
                           disk.directory if disk else None)

    def cache_clear() -> None:
        cache.clear()
        stats["hits"] = stats["misses"] = 0

    wrapped.__name__ = f"offload({getattr(fn, '__name__', 'fn')})"
    wrapped.sites = sites
    wrapped.policy = policy
    wrapped.backend = backend
    wrapped.cache_info = cache_info
    wrapped.persist_info = persist_info
    wrapped.cache_clear = cache_clear
    return wrapped


def site_report(fn, policy: PrecisionPolicy | None = None):
    """Enumerate the BLAS-3 sites ``offload`` would rewrite in ``fn``.

    Returns a function with the same signature as ``fn`` that returns a
    :class:`SiteList` of :class:`Site` records instead of computing
    (its ``native`` lists the contractions left native that are no
    gated ``dot_general``).  The names are
    the same structural names :func:`offload` uses (one shared walker),
    so they are valid ``PrecisionPolicy.site_splits`` keys.
    """
    policy = policy or PrecisionPolicy()

    def reporter(*args, **kwargs) -> SiteList:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
        native: List[Native] = []
        walked = _walk_sites(closed.jaxpr, native=native)
        return SiteList([_classify(eqn, policy, name, mult, spmd)
                         for eqn, name, mult, spmd in walked], native)

    reporter.__name__ = f"site_report({getattr(fn, '__name__', 'fn')})"
    return reporter
