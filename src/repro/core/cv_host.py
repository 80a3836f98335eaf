"""Host-loop CV drivers — the pre-engine reference implementations.

These are the original eager drivers (per-fold work vmapped, but traced
op-by-op on every call — no jit, no sharding, no backend switch), kept
verbatim for two jobs the engine cannot do for itself:

* **test oracle** — ``tests/test_engine.py`` checks every
  :class:`~repro.core.engine.CVEngine` strategy against these independent
  implementations (same math, different execution structure), so a bug in
  the batching/sharding machinery cannot hide behind "both paths share the
  code";
* **baseline** — the host-loop structure the engine's batched sweep
  replaces (the paper's §5 "exploit the architecture" claim).

Do not add features here; new work goes through the engine strategies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import picholesky, solvers
from .folds import CVResult, FoldData, holdout_nrmse

__all__ = ["host_cv_exact_cholesky", "host_cv_picholesky", "host_cv_svd",
           "host_cv_pinrmse"]


def _fold_train_stats(folds: FoldData, f: jax.Array):
    return folds.hess - folds.fold_hess[f], folds.grad - folds.fold_grad[f]


def host_cv_exact_cholesky(folds: FoldData, lams: jax.Array,
                           chol_fn=None) -> CVResult:
    """Chol baseline: k·q exact factorizations."""
    k = folds.fold_hess.shape[0]

    def per_fold(f):
        h_tr, g_tr = _fold_train_stats(folds, f)
        thetas = solvers.solve_cholesky_sweep(h_tr, g_tr, lams, chol_fn)
        return jax.vmap(lambda t: holdout_nrmse(
            t, folds.x_folds[f], folds.y_folds[f]))(thetas)

    errs = jax.vmap(per_fold)(jnp.arange(k))  # (k, q)
    return CVResult.from_errors(lams, errs.mean(0), k * len(lams))


def host_cv_picholesky(folds: FoldData, lams: jax.Array, g: int = 4,
                       degree: int = 2, *, block: int = 128,
                       basis: str = "monomial", chol_fn=None) -> CVResult:
    """piCholesky CV: k·g exact factorizations + interpolation for the rest."""
    k = folds.fold_hess.shape[0]
    sample = picholesky.choose_sample_lambdas(float(lams[0]), float(lams[-1]), g)

    def per_fold(f):
        h_tr, g_tr = _fold_train_stats(folds, f)
        model = picholesky.fit(h_tr, sample, degree, block=block, basis=basis,
                               chol_fn=chol_fn)
        l_interp = model.eval_factor(lams)  # (q, h, h)
        thetas = jax.vmap(lambda l: solvers.solve_from_factor(l, g_tr))(l_interp)
        return jax.vmap(lambda t: holdout_nrmse(
            t, folds.x_folds[f], folds.y_folds[f]))(thetas)

    errs = jax.vmap(per_fold)(jnp.arange(k))
    return CVResult.from_errors(lams, errs.mean(0), k * g,
                                sample_lams=np.asarray(sample))


def host_cv_svd(folds: FoldData, lams: jax.Array, mode: str = "full",
                k_trunc: int = 0, key=None) -> CVResult:
    """SVD / t-SVD / r-SVD baselines operating on the raw design matrix."""
    k = folds.fold_hess.shape[0]
    n_f = folds.x_folds.shape[1]
    idx = jnp.arange(k)

    def per_fold(f):
        mask = idx != f
        x_tr = folds.x_folds[mask.nonzero(size=k - 1)[0]].reshape((k - 1) * n_f, -1)
        y_tr = folds.y_folds[mask.nonzero(size=k - 1)[0]].reshape(-1)
        if mode == "full":
            thetas = solvers.solve_svd(x_tr, y_tr, lams)
        elif mode == "truncated":
            thetas = solvers.solve_truncated_svd(x_tr, y_tr, lams, k_trunc)
        else:
            thetas = solvers.solve_randomized_svd(x_tr, y_tr, lams, k_trunc, key)
        return jax.vmap(lambda t: holdout_nrmse(
            t, folds.x_folds[f], folds.y_folds[f]))(thetas)

    errs = jnp.stack([per_fold(f) for f in range(k)])
    return CVResult.from_errors(lams, errs.mean(0), 0)


def host_cv_pinrmse(folds: FoldData, lams: jax.Array, g: int = 4,
                    degree: int = 2, chol_fn=None) -> CVResult:
    """PINRMSE straw-man (§6.5): interpolate the hold-out-error curve itself
    from g exact evaluations — shown by the paper to select wrong λ's."""
    sample = picholesky.choose_sample_lambdas(float(lams[0]), float(lams[-1]), g)
    exact = host_cv_exact_cholesky(folds, sample, chol_fn)
    v = picholesky.vandermonde(sample, degree).astype(
        jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    t = jnp.asarray(exact.errors, v.dtype)
    theta = jnp.linalg.solve(v.T @ v, v.T @ t)
    dense_v = picholesky.vandermonde(lams, degree).astype(v.dtype)
    errs = dense_v @ theta
    k = folds.fold_hess.shape[0]
    return CVResult.from_errors(lams, errs, k * g, sample_lams=np.asarray(sample))
