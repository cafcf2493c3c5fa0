"""MuST-style Green's-function contour workload (paper §3.2 / §4).

MuST (LSMS family) spends its time inverting the KKR multiple-
scattering matrix at every energy point of a contour around the Fermi
energy; the inversion is a *blocked* LU driver (``zblock_lu``) whose
flops are almost entirely ZGEMM — exactly the calls the paper's
offloading tool redirects to INT8 emulation.

This module reproduces that structure on a synthetic-but-physical
stand-in: a dense Hermitian "Hamiltonian" with an eigenvalue cluster
near the Fermi energy.  For each energy ``z`` on a contour just above
the real axis we form ``M = z I - H`` and compute the resolvent
``G(z) = M^{-1}`` by blocked LU factorization plus blocked triangular
solves, where **every block GEMM goes through a registry backend**
(:mod:`repro.core.backends` — any spec string works as a mode):

* ``"dgemm"``          — native float64 complex matmul (reference);
* ``"fp64_int8_{s}"``  — Ozaki INT8 emulation with ``s`` splits;
* ``"pallas_int8_{s}"``— the fused Pallas kernel (interpret on CPU);
* ``"adaptive:{tol}"`` — per-site split tuning to a target error.

Small per-block factorizations (the LAPACK part MuST keeps on the
host) remain native float64 in all modes, so the accuracy difference
between modes isolates the GEMM emulation — the quantity the paper's
Table 1 reports.  The poles of ``G`` near the Fermi energy amplify the
emulation error locally, reproducing the isolated error peak of the
paper's Figure 1, and contour-integrated observables (electron-count
and band-energy analogues) converge to the FP64 values as the split
count grows.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax.numpy as jnp
import numpy as np

from repro.core.backends import get_backend
from repro.core.ozaki import real_pair_matmul
from repro.core.precision import PrecisionPolicy

__all__ = ["MustConfig", "build_system", "run_contour",
           "lapack_contour", "relative_errors"]


@dataclasses.dataclass
class MustConfig:
    """Synthetic LSMS system + contour discretization."""

    n: int = 384            # scattering-matrix dimension
    block: int = 96         # zblock_lu block size
    n_energies: int = 16    # contour points
    fermi: float = 0.72     # Fermi energy (Ryd), where G has poles
    eta: float = 0.03       # contour height above the real axis
    e_min: float = 0.12     # contour start (Ryd)
    e_max: float = 1.32     # contour end (Ryd)
    cluster_frac: float = 0.25  # fraction of states near the Fermi energy
    cluster_width: float = 0.04
    seed: int = 0

    def __post_init__(self):
        if self.n % self.block != 0:
            raise ValueError(
                f"block {self.block} must divide n {self.n}")


def build_system(cfg: MustConfig) -> Dict[str, np.ndarray]:
    """Random Hermitian Hamiltonian with a state cluster at E_f.

    Eigenvalues are drawn uniformly over the contour window except for
    a ``cluster_frac`` share packed within ``cluster_width`` of the
    Fermi energy — those poles sit right under the contour and make
    ``G(z)`` locally ill-conditioned, which is what gives the paper's
    Figure 1 its isolated error peak.
    """
    rng = np.random.default_rng(cfg.seed)
    n = cfg.n
    n_cluster = int(round(cfg.cluster_frac * n))
    evals = np.concatenate([
        rng.uniform(cfg.e_min - 0.1, cfg.e_max + 0.1, n - n_cluster),
        cfg.fermi + cfg.cluster_width * rng.standard_normal(n_cluster),
    ])
    # Random unitary eigenbasis via QR of a complex Ginibre matrix.
    z = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    q, r = np.linalg.qr(z)
    q = q * (np.diagonal(r) / np.abs(np.diagonal(r)))
    h = (q * evals) @ q.conj().T
    h = 0.5 * (h + h.conj().T)  # exact Hermitian symmetrization
    return {"H": h, "evals": np.sort(evals)}


def _make_gemm(mode: str) -> Callable[[np.ndarray, np.ndarray],
                                      np.ndarray]:
    """Resolve a mode string to a numpy-in/numpy-out block GEMM.

    The mode string is a backend spec (see
    :func:`repro.core.backends.get_backend` for the grammar); the bound
    policy selects the ``"f64"`` accumulator, the historical choice of
    this workload (it mirrors ozIMMU on FP64-capable hardware).

    Complex blocks are split into f64 ``(re, im)`` pairs on the host
    and joined again there: only real f64 arrays reach the device,
    whose compiler (XLA:TPU) has no complex128 matmul.
    """
    backend = get_backend(mode, policy=PrecisionPolicy(accumulator="f64"))

    def real_gemm(x, y, real_out):
        return backend(x, y, out_dtype=real_out, site="zblock_lu")

    def pair(x: np.ndarray):
        return jnp.asarray(x.real), jnp.asarray(x.imag)

    def gemm(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        cr, ci = real_pair_matmul(real_gemm, pair(a), pair(b),
                                  jnp.float64)
        return np.asarray(cr) + 1j * np.asarray(ci)

    return gemm


def _blocked_inverse(m_mat: np.ndarray, block: int, gemm) -> np.ndarray:
    """``m_mat^{-1}`` via blocked LU + blocked triangular solves.

    Mirrors MuST's zblock_lu: the O(n^3) work — Schur updates and the
    substitution products — is all block GEMMs through ``gemm``; only
    the per-diagonal-block inversions are native LAPACK.
    """
    n = m_mat.shape[0]
    nb = n // block
    sl = [slice(i * block, (i + 1) * block) for i in range(nb)]

    # Block Doolittle LU (no pivoting: z I - H with Im z > 0 keeps the
    # diagonal blocks well away from singular).  L has identity
    # diagonal blocks; U is the remaining upper factor.
    a = m_mat.copy()
    lower = np.zeros_like(a)
    for k in range(nb):
        inv_kk = np.linalg.inv(a[sl[k], sl[k]])
        lower[sl[k], sl[k]] = np.eye(block)
        for i in range(k + 1, nb):
            lower[sl[i], sl[k]] = gemm(a[sl[i], sl[k]], inv_kk)
        for i in range(k + 1, nb):
            upd = gemm(lower[sl[i], sl[k]], a[sl[k], k * block:])
            a[sl[i], k * block:] -= upd
    upper = a
    for i in range(1, nb):
        for j in range(i):
            upper[sl[i], sl[j]] = 0.0

    # Forward substitution  L Y = I   (unit block diagonal).
    y = np.zeros_like(a)
    ident = np.eye(n, dtype=a.dtype)
    for i in range(nb):
        acc = ident[sl[i], :].copy()
        for j in range(i):
            acc -= gemm(lower[sl[i], sl[j]], y[sl[j], :])
        y[sl[i], :] = acc

    # Backward substitution  U G = Y.  Applying the diagonal-block
    # inverse is itself a block GEMM — route it through the backend
    # too, so *all* O(n^3) work is emulated (only the O(block^3)
    # LAPACK inversions stay native, as in MuST).
    g = np.zeros_like(a)
    for i in range(nb - 1, -1, -1):
        acc = y[sl[i], :].copy()
        for j in range(i + 1, nb):
            acc -= gemm(upper[sl[i], sl[j]], g[sl[j], :])
        g[sl[i], :] = gemm(np.linalg.inv(upper[sl[i], sl[i]]), acc)
    return g


def contour_points(cfg: MustConfig):
    """Energy contour and trapezoid weights just above the real axis."""
    e = np.linspace(cfg.e_min, cfg.e_max, cfg.n_energies)
    z = e + 1j * cfg.eta
    w = np.gradient(e)
    return z, w


def run_contour(cfg: MustConfig, mode: str,
                system: Dict[str, np.ndarray]) -> Dict:
    """Sweep ``G(z) = (z I - H)^{-1}`` over the contour in one mode.

    Returns per-energy diagonals of G (the site-resolved Green's
    function MuST feeds to its density integrator), the trace, and
    the contour-integrated observables:

    * ``ne``   — electron-count analogue: -1/pi Im sum_k w_k Tr G(z_k);
    * ``etot`` — band-energy analogue:    -1/pi Im sum_k w_k z_k Tr G.
    """
    gemm = _make_gemm(mode)
    return _sweep(cfg, mode, system,
                  lambda m_mat: _blocked_inverse(m_mat, cfg.block, gemm))


def lapack_contour(cfg: MustConfig, system: Dict[str, np.ndarray]) -> Dict:
    """:func:`run_contour`'s result with ``G(z)`` from one host LAPACK
    inverse per energy — a reference that no block GEMM touches."""
    return _sweep(cfg, "lapack", system, np.linalg.inv)


def _sweep(cfg: MustConfig, mode: str, system, inverse) -> Dict:
    h = system["H"]
    z, w = contour_points(cfg)
    n = cfg.n
    g_diag = np.zeros((cfg.n_energies, n), dtype=np.complex128)
    tr_g = np.zeros(cfg.n_energies, dtype=np.complex128)
    for idx, zk in enumerate(z):
        g = inverse(zk * np.eye(n, dtype=np.complex128) - h)
        g_diag[idx] = np.diagonal(g)
        tr_g[idx] = np.trace(g)
    ne = float(-np.imag(np.sum(w * tr_g)) / np.pi)
    etot = float(-np.imag(np.sum(w * z * tr_g)) / np.pi)
    return {"mode": mode, "z": z, "weights": w, "g_diag": g_diag,
            "tr_g": tr_g, "ne": ne, "etot": etot}


def relative_errors(ref: Dict, test: Dict) -> Dict:
    """Paper Table-1 metrics: Re/Im errors of G plus observable drifts.

    Per-energy errors are normalized by the largest |component| of the
    reference at that energy (so the Figure-1 profile shows where the
    *relative* accuracy degrades, i.e. near the poles at E_f).
    """
    dre = np.abs(np.real(test["g_diag"]) - np.real(ref["g_diag"]))
    dim = np.abs(np.imag(test["g_diag"]) - np.imag(ref["g_diag"]))
    norm_re = np.max(np.abs(np.real(ref["g_diag"])), axis=1)
    norm_im = np.max(np.abs(np.imag(ref["g_diag"])), axis=1)
    per_z_real = np.max(dre, axis=1) / np.where(norm_re == 0, 1, norm_re)
    per_z_imag = np.max(dim, axis=1) / np.where(norm_im == 0, 1, norm_im)
    return {
        "per_z_real": per_z_real,
        "per_z_imag": per_z_imag,
        "max_real": float(np.max(per_z_real)),
        "max_imag": float(np.max(per_z_imag)),
        "d_etot": abs(test["etot"] - ref["etot"]) / max(
            1e-30, abs(ref["etot"])),
        "d_ne": abs(test["ne"] - ref["ne"]) / max(
            1e-30, abs(ref["ne"])),
    }
