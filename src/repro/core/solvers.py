"""Ridge / regularized least-squares solvers (§3.2, §6.2 baselines).

All solvers consume the normal-equation data ``H = XᵀX`` (h×h) and
``g = Xᵀy`` (h,) — or the design matrix ``X`` itself for the SVD family —
and return θ(λ) for one or many λ.

The Cholesky-family solvers accept ``backend=`` (``'auto'`` | ``'pallas'`` |
``'reference'`` | a :class:`~repro.core.backends.LinalgBackend`) selecting
the factorize/substitute implementation; a ``chol_fn`` override takes
precedence over the backend's factorization (legacy hook, kept for the
kernel-injection tests).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import tracing
from .backends import BackendLike, resolve_backend

__all__ = [
    "solve_from_factor",
    "solve_packed",
    "solve_interpolant_sweep",
    "solve_cholesky",
    "solve_cholesky_sweep",
    "svd_ridge_factors",
    "svd_ridge_sweep",
    "LowRankFactors",
    "lowrank_ridge_factors",
    "lowrank_ridge_sweep",
    "solve_svd",
    "solve_truncated_svd",
    "randomized_range_finder",
    "solve_randomized_svd",
]


def solve_from_factor(l, g: jax.Array,
                      backend: BackendLike = "reference") -> jax.Array:
    """Forward + back substitution: solve L Lᵀ θ = g (§3.2).

    ``l``: dense (h, h) factor or a
    :class:`~repro.core.packing.PackedFactor` (solved in the packed domain,
    no unpack).
    """
    return resolve_backend(backend).solve_from_factor(l, g)


def solve_packed(pf, g: jax.Array,
                 backend: BackendLike = "reference") -> jax.Array:
    """Packed-domain solve: L Lᵀ θ = g on tile-packed factor(s) (…, P)."""
    return resolve_backend(backend).solve_packed(pf, g)


def solve_interpolant_sweep(model, lams: jax.Array, g: jax.Array,
                            backend: BackendLike = "reference") -> jax.Array:
    """θ(λ) for a λ chunk straight from a fitted
    :class:`~repro.core.picholesky.PiCholesky`: fused Horner evaluation +
    packed substitution, no (q, h, h) intermediate.  (q, h)."""
    return model.solve(lams, g, backend=backend)


def solve_cholesky(hessian: jax.Array, g: jax.Array, lam: jax.Array,
                   chol_fn=None, backend: BackendLike = "reference") -> jax.Array:
    """Exact Chol baseline for one λ."""
    bk = resolve_backend(backend)
    chol_fn = chol_fn or bk.cholesky
    h = hessian.shape[-1]
    with tracing.scope(tracing.ANCHOR_CHOL):
        l = chol_fn(hessian + lam * jnp.eye(h, dtype=hessian.dtype))
    return bk.solve_from_factor(l, g)


def solve_cholesky_sweep(hessian: jax.Array, g: jax.Array, lams: jax.Array,
                         chol_fn=None,
                         backend: BackendLike = "reference") -> jax.Array:
    """Exact Chol for every λ in the grid — the O(q d³) cost piCholesky
    amortizes. (q, h)."""
    bk = resolve_backend(backend)
    return jax.vmap(
        lambda lam: solve_cholesky(hessian, g, lam, chol_fn, bk))(lams)


def svd_ridge_factors(x: jax.Array, y: jax.Array, mode: str = "full",
                      k: int = 0, key: Optional[jax.Array] = None):
    """λ-independent factor stage shared by the SVD family: returns
    ``(s, vt, uty)`` such that θ(λ) = vtᵀ diag(s/(s²+λ)) uty.

    ``mode``: ``'full'`` | ``'truncated'`` (top-k) | ``'randomized'``
    (Halko–Martinsson–Tropp range finder, then top-k).
    """
    if mode == "full":
        u, s, vt = jnp.linalg.svd(x, full_matrices=False)
    elif mode == "truncated":
        u, s, vt = jnp.linalg.svd(x, full_matrices=False)
        u, s, vt = u[:, :k], s[:k], vt[:k]
    elif mode == "randomized":
        key = key if key is not None else jax.random.PRNGKey(0)
        q = randomized_range_finder(x, k, key)
        b = q.T @ x  # (p, h)
        ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
        u = q @ ub
        u, s, vt = u[:, :k], s[:k], vt[:k]
    else:
        raise ValueError(f"unknown SVD mode {mode!r}")
    return s, vt, u.T @ y


def svd_ridge_sweep(factors, lams: jax.Array) -> jax.Array:
    """θ(λ) for every λ from a :func:`svd_ridge_factors` result. (q, h)."""
    s, vt, uty = factors

    def per_lam(lam):
        d = s / (s * s + lam)
        return vt.T @ (d * uty)

    return jax.vmap(per_lam)(jnp.atleast_1d(lams))


def solve_svd(x: jax.Array, y: jax.Array, lams: jax.Array) -> jax.Array:
    """Full-SVD baseline (Eq. 11): factorize X once, reuse across all λ."""
    return svd_ridge_sweep(svd_ridge_factors(x, y, "full"), lams)


def solve_truncated_svd(x: jax.Array, y: jax.Array, lams: jax.Array,
                        k: int) -> jax.Array:
    """t-SVD baseline: keep only the top-k singular triplets."""
    return svd_ridge_sweep(svd_ridge_factors(x, y, "truncated", k), lams)


def randomized_range_finder(x: jax.Array, k: int, key: jax.Array,
                            oversample: int = 10, n_iter: int = 2) -> jax.Array:
    """Halko–Martinsson–Tropp randomized range finder with power iteration."""
    n, h = x.shape
    p = min(h, k + oversample)
    omega = jax.random.normal(key, (h, p), x.dtype)
    y = x @ omega
    q, _ = jnp.linalg.qr(y)
    for _ in range(n_iter):
        q, _ = jnp.linalg.qr(x.T @ q)
        q, _ = jnp.linalg.qr(x @ q)
    return q  # (n, p)


def solve_randomized_svd(x: jax.Array, y: jax.Array, lams: jax.Array, k: int,
                         key: Optional[jax.Array] = None) -> jax.Array:
    """r-SVD baseline [13]: approximate top-k SVD via random projection."""
    return svd_ridge_sweep(svd_ridge_factors(x, y, "randomized", k, key),
                           lams)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LowRankFactors:
    """Spectral factors of a (rank-truncated) fold Hessian:
    H̃ = vtᵀ diag(evals) vt.

    ``vt`` holds *every* computed right singular vector of the training
    design (rows orthonormal, shape (r₀, h), r₀ = min(n, h)); ``evals``
    the squared singular values with entries **zeroed** beyond the
    requested rank.  Zeroing instead of dropping rows is what keeps the
    λ sweep cancellation-free: the truncated directions solve at 1/λ
    through the same ``1/(e+λ)`` expression (e=0), and no
    ``g − V Vᵀ g`` subtraction — catastrophic in fp32 when |g| ≫ |θ| —
    ever appears.  λ-independent: one factorization serves every grid.
    """

    vt: jax.Array
    evals: jax.Array


def lowrank_ridge_factors(x: jax.Array, rank: Optional[int] = None,
                          precision=None) -> LowRankFactors:
    """Low-rank ACV factor stage (Stephenson et al., arXiv:2008.10547).

    SVD of the (n, h) training design — O(n²h) when n ≪ h, vs g·O(h³)
    anchor Cholesky factorizations.  ``rank`` keeps the top-r curvature
    directions (evals beyond r are zeroed, see :class:`LowRankFactors`);
    ``None`` keeps all min(n, h).
    """
    _, s, vt = jnp.linalg.svd(x, full_matrices=False)
    evals = s * s
    if rank is not None:
        r = min(int(rank), s.shape[0])
        evals = jnp.where(jnp.arange(evals.shape[0]) < r, evals, 0.0)
    if precision is not None:
        vt = vt.astype(precision.store_dtype(vt.dtype))
        evals = evals.astype(precision.store_dtype(evals.dtype))
    return LowRankFactors(vt=vt, evals=evals)


def lowrank_ridge_sweep(factors: LowRankFactors, g: jax.Array,
                        lams: jax.Array, compute_dtype=None) -> jax.Array:
    """θ(λ) = V diag(1/(e+λ)) Vᵀg for every λ. (q, h).

    Woodbury form of (H̃ + λI)⁻¹g for H̃ = Vᵀ diag(e) V.  The gradient
    g = Xᵀy lies in range(Vᵀ) by construction, so the true null-space
    component is identically zero and needs no 1/λ term; truncated
    directions (e zeroed) solve at exactly 1/λ through the same
    expression.  Exact (up to rounding) whenever no eval was truncated.
    """
    dt = compute_dtype or jnp.promote_types(g.dtype, jnp.float32)
    vt = factors.vt.astype(dt)
    evals = factors.evals.astype(dt)
    vg = vt @ g.astype(dt)  # (r0,)

    def per_lam(lam):
        return vt.T @ (vg / (evals + lam.astype(dt)))

    return jax.vmap(per_lam)(jnp.atleast_1d(lams))
