"""Plain piCholesky ridge cross-validation: the reference that decides
``correct``.

The same mathematics as the program, written out straightforwardly in
``jax.numpy`` and imported from nowhere in the program: per fold, the
training Hessian ``H − XfᵀXf``; g exact Cholesky factors at log-spaced
anchors spanning the grid; an order-r monomial fit Θ of every factor entry
by the normal equations ``(VᵀV)Θ = VᵀT`` (Algorithm 1); at each λ the
interpolated factor ``L(λ) = Σ_j λ^j Θ_j`` (lower triangle), the solve
``L Lᵀ θ = g``, optionally ``refine`` sweeps of iterative refinement
``θ += (L Lᵀ)⁻¹ (g − (H + λI) θ)``, and the hold-out NRMSE.

Every product runs through :func:`dot` at a stated number of bf16 passes:
6 is float32 at ``Precision.HIGHEST``; 3 splits each float32 operand into
a high and a low bfloat16 half and keeps three of the four products, which
is what a TPU does at ``Precision.HIGH`` — written out, so the control
computes the same on any platform.  The factorization and the
substitutions are blocked so that their products go through :func:`dot`
too; only the B×B diagonal blocks use ``jnp.linalg``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

PASSES = {"highest": 6, "high": 3}


def dot(spec: str, a: jax.Array, b: jax.Array, passes: int) -> jax.Array:
    """``jnp.einsum(spec, a, b)`` in float32 at ``passes`` bf16 passes."""
    if passes == 6:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be 6 or 3, got {passes}")
    bf = jnp.bfloat16

    def split(x):
        hi = x.astype(bf)
        return hi, (x - hi.astype(jnp.float32)).astype(bf)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

    def mm(x, y):   # bf16 operands, so every product is exact in float32
        return jnp.einsum(spec, x.astype(jnp.float32), y.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def cholesky(a: jax.Array, block: int, passes: int) -> jax.Array:
    """Right-looking blocked Cholesky; the trailing updates go through
    :func:`dot`."""
    h = a.shape[-1]
    out = jnp.zeros_like(a)
    for s in range(0, h, block):
        e = min(s + block, h)
        ljj = jnp.linalg.cholesky(a[s:e, s:e])
        out = out.at[s:e, s:e].set(ljj)
        if e < h:
            lij = solve_triangular(ljj, a[e:, s:e].T, lower=True).T
            out = out.at[e:, s:e].set(lij)
            a = a.at[e:, e:].add(-dot("ik,jk->ij", lij, lij, passes))
    return out


def chol_solve(l: jax.Array, b: jax.Array, block: int,
               passes: int) -> jax.Array:
    """x with L Lᵀ x = b, by blocked forward and back substitution."""
    h = l.shape[-1]
    starts = list(range(0, h, block))
    w = []
    for s in starts:
        e = min(s + block, h)
        r = b[s:e]
        if s:
            r = r - dot("ik,km->im", l[s:e, :s], jnp.concatenate(w), passes)
        w.append(solve_triangular(l[s:e, s:e], r, lower=True))
    x = []
    for s in reversed(starts):
        e = min(s + block, h)
        r = w[s // block]
        if x:
            r = r - dot("ki,km->im", l[e:, s:e], jnp.concatenate(x), passes)
        x.insert(0, solve_triangular(l[s:e, s:e], r, lower=True, trans=1))
    return jnp.concatenate(x)


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def fold_stats(x: jax.Array, y: jax.Array, k: int, passes: int):
    """Per-fold blocks and statistics.  x (n, h), y (n, m) ->
    (x_folds (k, n_f, h), y_folds (k, n_f, m), fold_hess (k, h, h),
    fold_grad (k, h, m))."""
    n_f = x.shape[0] // k
    xf = x[: n_f * k].reshape(k, n_f, -1)
    yf = y[: n_f * k].reshape(k, n_f, -1)
    return (xf, yf, dot("kni,knj->kij", xf, xf, passes),
            dot("kni,knm->kim", xf, yf, passes))


@functools.partial(jax.jit, static_argnames=("g", "degree", "block",
                                             "passes"))
def fit(h_tr: jax.Array, lo: jax.Array, hi: jax.Array, *, g: int,
        degree: int, block: int, passes: int) -> jax.Array:
    """Θ (degree+1, h, h) of one fold, anchored at g log-spaced λ on
    [lo, hi]."""
    anchors = jnp.logspace(jnp.log10(lo), jnp.log10(hi), g)
    eye = jnp.eye(h_tr.shape[-1], dtype=h_tr.dtype)
    factors = jax.vmap(lambda lam: cholesky(h_tr + lam * eye, block,
                                            passes))(anchors)
    v = anchors[:, None] ** jnp.arange(degree + 1, dtype=anchors.dtype)
    gram = dot("gr,gs->rs", v, v, 6)
    rhs = dot("gr,gij->rij", v, factors, passes)
    return jnp.linalg.solve(gram, rhs.reshape(degree + 1, -1)
                            ).reshape(rhs.shape)


@functools.partial(jax.jit, static_argnames=("block", "passes", "refine"))
def curves(theta: jax.Array, lams: jax.Array, h_tr: jax.Array,
           g_tr: jax.Array, x_hold: jax.Array, y_hold: jax.Array, *,
           block: int, passes: int, refine: int) -> jax.Array:
    """Hold-out NRMSE (q, m) of one fold at each λ, for m targets."""
    denom = jnp.std(y_hold, axis=0) + 1e-30

    def one(lam):
        l = jnp.tril(theta[-1])
        for j in range(theta.shape[0] - 2, -1, -1):       # Horner
            l = l * lam + jnp.tril(theta[j])
        th = chol_solve(l, g_tr, block, passes)
        for _ in range(refine):
            resid = g_tr - (dot("ij,jm->im", h_tr, th, passes) + lam * th)
            th = th + chol_solve(l, resid, block, passes)
        pred = dot("ni,im->nm", x_hold, th, passes)
        return jnp.sqrt(jnp.mean((pred - y_hold) ** 2, axis=0)) / denom

    return jax.lax.map(one, lams)


def cv_curves(x, y, *, k: int, grids, g: int, degree: int, block: int,
              refine: int = 0, passes: int = 6) -> list:
    """Mean hold-out curves of one design for each λ grid.

    ``x`` (n, h) and ``y`` (n, m) as the program was given them; ``grids``
    a list of float32 λ arrays.  Grids with the same end points share the
    fitted Θ.  Returns a list of (q, m) numpy arrays, in ``grids`` order.
    Works one fold at a time, so that its peak memory is one fold's.
    """
    import numpy as np

    xf, yf, fh, fg = fold_stats(x, y, k=k, passes=passes)
    hess, grad = fh.sum(0), fg.sum(0)
    total = [0.0] * len(grids)
    for f in range(k):
        h_tr, g_tr = hess - fh[f], grad - fg[f]
        thetas = {}
        for i, lams in enumerate(grids):
            ends = (float(lams[0]), float(lams[-1]))
            if ends not in thetas:
                thetas[ends] = fit(h_tr, lams[0], lams[-1], g=g,
                                   degree=degree, block=block, passes=passes)
            total[i] = total[i] + np.asarray(curves(
                thetas[ends], lams, h_tr, g_tr, xf[f], yf[f], block=block,
                passes=passes, refine=refine))
        del thetas
    return [t / k for t in total]


def curve_gap(got, want) -> float:
    """Largest relative gap |got − want| / want over a curve; a curve
    that is not finite everywhere reads infinite."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want) / np.abs(want)))
