"""repro.core — tunable-precision INT8 GEMM emulation.

Layers:
  * :mod:`repro.core.ozaki`      — the split-GEMM arithmetic engine;
  * :mod:`repro.core.precision`  — the accuracy knob (policies, split
    prediction/measurement, adaptive per-site tuning);
  * :mod:`repro.core.backends`   — the GEMM backend registry, where a
    policy binds to an execution engine (spec strings);
  * :mod:`repro.core.intercept`  — automatic BLAS offload: the
    jaxpr->jaxpr transform for unmodified JAX functions.
"""

from .backends import (GemmBackend, example_specs, get_backend,
                       register_backend, registered_families)
from .intercept import (CacheInfo, Native, PersistInfo, Site, SiteList,
                        offload, site_report, transform_jaxpr)
from .ozaki import (SLICE_BITS, num_pair_gemms, ozaki_matmul,
                    ozaki_ragged_dot, pair_indices, slice_matrix)
from .precision import (AdaptiveGemm, PrecisionPolicy, SiteState,
                        canonical_site, estimate_rel_error,
                        measure_splits, predict_splits,
                        splits_for_tolerance)

__all__ = [
    "SLICE_BITS",
    "AdaptiveGemm",
    "CacheInfo",
    "canonical_site",
    "GemmBackend",
    "PrecisionPolicy",
    "Site",
    "SiteList",
    "SiteState",
    "estimate_rel_error",
    "example_specs",
    "get_backend",
    "measure_splits",
    "Native",
    "num_pair_gemms",
    "offload",
    "ozaki_matmul",
    "ozaki_ragged_dot",
    "pair_indices",
    "PersistInfo",
    "predict_splits",
    "register_backend",
    "registered_families",
    "site_report",
    "slice_matrix",
    "splits_for_tolerance",
    "transform_jaxpr",
]
