"""Shared CV data types: fold statistics, hold-out metric, result record.

Lives below both :mod:`repro.core.cv` (the compatibility drivers) and
:mod:`repro.core.engine` (the batched/sharded sweep) so neither imports the
other for these definitions.

The fold trick: with ``H_f = X_fᵀX_f`` per fold, the training Hessian of
fold f is ``H − H_f`` (one pass over the data, §1's O(nd²) paid once).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FoldData", "make_folds", "holdout_nrmse", "CVResult"]


class FoldData(NamedTuple):
    """Per-fold sufficient statistics + raw held-out blocks."""
    hess: jax.Array        # (h, h) total XᵀX
    grad: jax.Array        # (h,)   total Xᵀy
    fold_hess: jax.Array   # (k, h, h)
    fold_grad: jax.Array   # (k, h)
    x_folds: jax.Array     # (k, n_f, h)
    y_folds: jax.Array     # (k, n_f)


def make_folds(x: jax.Array, y: jax.Array, k: int) -> FoldData:
    n = x.shape[0]
    n_f = n // k
    x = x[: n_f * k].reshape(k, n_f, -1)
    y = y[: n_f * k].reshape(k, n_f)
    # full precision: on a TPU an f32 einsum is otherwise one bf16 pass
    hi = jax.lax.Precision.HIGHEST
    fold_hess = jnp.einsum("kni,knj->kij", x, x, precision=hi)
    fold_grad = jnp.einsum("kni,kn->ki", x, y, precision=hi)
    return FoldData(fold_hess.sum(0), fold_grad.sum(0), fold_hess, fold_grad, x, y)


def holdout_nrmse(theta: jax.Array, x_hold: jax.Array, y_hold: jax.Array) -> jax.Array:
    """Normalized RMSE on the held-out fold (paper's hold-out error)."""
    pred = x_hold @ theta
    mse = jnp.mean((pred - y_hold) ** 2)
    denom = jnp.std(y_hold) + 1e-30
    return jnp.sqrt(mse) / denom


@dataclasses.dataclass
class CVResult:
    lams: np.ndarray           # dense candidate grid
    errors: np.ndarray         # (q,) mean hold-out error across folds
    best_lam: float
    best_error: float
    n_exact_chol: int          # factorizations actually performed
    extras: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def from_errors(lams, errors, n_exact, **extras) -> "CVResult":
        """Rank a hold-out curve into a result.

        The argmin runs over the FINITE entries only — ``np.argmin`` on a
        partially-NaN curve returns the first NaN's index, which would
        silently report ``best_lam=nan``.  A curve with *no* finite entry
        cannot be ranked at all (every λ hit a singular fold / overflow):
        that raises ``FloatingPointError`` — the same refusal the engine's
        early-stop search makes mid-stream — instead of returning a
        ``nan``/``inf`` selection the caller would deploy.
        """
        lams = np.asarray(lams)
        errors = np.asarray(errors)
        if errors.size == 0:
            raise ValueError("cannot rank an empty hold-out curve "
                             "(no λ was evaluated)")
        finite = np.isfinite(errors)
        if not finite.any():
            raise FloatingPointError(
                "hold-out curve has no finite value: every λ produced a "
                "non-finite mean error (singular fold? overflow → try "
                "precision='bf16_refined' or fp64); refusing to rank a "
                "curve that cannot be compared")
        i = int(np.flatnonzero(finite)[np.argmin(errors[finite])])
        return CVResult(lams, errors, float(lams[i]), float(errors[i]),
                        n_exact, dict(extras))
