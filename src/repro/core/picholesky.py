"""piCholesky (Algorithm 1): polynomial interpolation of Cholesky factors.

Given a Hessian ``H`` and a sparse set of shifts ``{λ_s}``, factorize
``L^s = chol(H + λ_s I)`` exactly, fit an order-``r`` polynomial to every
entry of ``L`` via one batched least-squares solve, and evaluate the fit at
any dense λ grid for ``O(r d²)`` per value.

Layout: the target matrix ``T`` (g × D) holds tile-packed factors
(:mod:`repro.core.packing`), so the fit ``Θ = (VᵀV)⁻¹VᵀT`` and the
evaluation ``τ(λ)ᵀΘ`` are dense GEMMs (BLAS-3 / MXU, per paper §5).

Basis options (paper uses raw monomials; centered monomials are a
numerically safer drop-in that leaves Algorithm 1 unchanged — see
Thm 4.6's M-matrix change of basis):

* ``basis='monomial'``   — V[s,k] = λ_s^k          (paper, Algorithm 1)
* ``basis='centered'``   — V[s,k] = (λ_s − λ_c)^k  (λ_c = mean of samples)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from . import packing, tracing
from .backends import BackendLike, resolve_backend

__all__ = ["PiCholesky", "fit", "fit_targets", "anchor_factors",
           "pair_factors", "evaluate",
           "evaluate_packed", "vandermonde", "choose_sample_lambdas",
           "refine_solutions", "loo_interp_scores", "select_interpolant"]


def vandermonde(lams: jax.Array, degree: int, center: float | jax.Array = 0.0) -> jax.Array:
    """g × (degree+1) observation matrix V (leading columns of Vandermonde)."""
    x = jnp.asarray(lams) - center
    return jnp.power(x[:, None], jnp.arange(degree + 1)[None, :].astype(x.dtype))


def choose_sample_lambdas(lo: float, hi: float, g: int, spacing: str = "log") -> jax.Array:
    """Pick the g sparse sample shifts from [lo, hi] (paper: subset of the
    exponentially spaced candidate grid)."""
    if spacing == "log":
        return jnp.logspace(jnp.log10(lo), jnp.log10(hi), g)
    return jnp.linspace(lo, hi, g)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PiCholesky:
    """Fitted interpolant. ``theta``: (r+1, P) coefficients over the packed
    layout.  The packed ``(P,)`` representation is the pipeline's native
    currency: :meth:`eval_packed` / :meth:`eval_packed_factor` stay in it
    and :meth:`solve` fuses evaluation with the substitution, so the λ
    sweep never materializes dense factors; :meth:`eval_factor` is the
    explicit dense escape hatch for debugging and dense consumers."""

    theta: jax.Array
    center: jax.Array
    h: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))

    @property
    def degree(self) -> int:
        return self.theta.shape[0] - 1

    def eval_packed(self, lam: jax.Array) -> jax.Array:
        """Horner evaluation at scalar or vector λ -> (…, P) packed rows."""
        lam = jnp.asarray(lam)
        x = (lam - self.center).astype(self.theta.dtype)
        scalar = x.ndim == 0
        x = jnp.atleast_1d(x)

        def horner(acc, coeffs):  # over degrees, highest first
            return acc * x[:, None] + coeffs[None, :], None

        acc = jnp.zeros((x.shape[0], self.theta.shape[1]), self.theta.dtype)
        acc, _ = jax.lax.scan(horner, acc, self.theta[::-1])
        return acc[0] if scalar else acc

    def eval_packed_factor(self, lam: jax.Array) -> "packing.PackedFactor":
        """Interpolated factor(s) in the packed layout: vec is (…, P)."""
        return packing.PackedFactor(vec=self.eval_packed(lam), h=self.h,
                                    block=self.block)

    def solve(self, lam: jax.Array, g: jax.Array,
              backend: BackendLike = "reference") -> jax.Array:
        """θ(λ) = (H + λI)⁻¹ g for a λ chunk via the fused packed pipeline:
        Horner evaluation + forward/back substitution with no dense L(λ)."""
        return resolve_backend(backend).interp_solve(
            self.theta, lam, g, h=self.h, block=self.block,
            center=self.center)

    def eval_factor(self, lam: jax.Array,
                    backend: BackendLike = "reference") -> jax.Array:
        """Dense interpolated factor(s) L(λ): (…, h, h).

        Debug escape hatch — the sweep hot path uses :meth:`solve` /
        :meth:`eval_packed_factor` instead.  On the Pallas backend this is
        the fused Horner+unpack kernel (one pass over Θ), not the two-pass
        eval_packed → unpack_tril route.
        """
        lam = jnp.asarray(lam)
        out = resolve_backend(backend).interp_factors(
            self.theta, lam, h=self.h, block=self.block, center=self.center)
        return out[0] if lam.ndim == 0 else out


def fit(
    hessian: Optional[jax.Array],
    sample_lams: jax.Array,
    degree: int = 2,
    *,
    block: int = 128,
    basis: str = "monomial",
    chol_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    factors: "jax.Array | packing.PackedFactor | None" = None,
    backend: BackendLike = "reference",
) -> PiCholesky:
    """Algorithm 1.  ``hessian``: (h, h) SPD; ``sample_lams``: (g,) with
    g > degree.  ``backend`` selects the factorize/pack implementation
    (Pallas kernels vs ``jnp.linalg``); ``chol_fn`` overrides just the
    factorization; ``factors`` skips factorization if the caller already
    has L^s — either dense (g, h, h) or a
    :class:`~repro.core.packing.PackedFactor` with batched vec (g, P),
    which is consumed without any unpack.  With ``factors`` given the
    Hessian itself is not needed (the factor-cache refit path hands in
    cached anchors only): pass ``hessian=None`` and the geometry is taken
    from the factors.

    Precision: the backend's policy governs the fit — the normal equations
    ``Θ = (VᵀV)⁻¹VᵀT`` run at the policy's *fit* dtype (floored at fp32, so
    bf16-stored anchor targets never degrade the regression itself), and
    the returned Θ is cast to the *storage* dtype (bf16 halves the cached
    state).  The ``native`` policy inherits the target dtype end to end —
    bit-compatible with the pre-policy fit.
    """
    if hessian is None and factors is None:
        raise ValueError("fit needs a hessian to factorize or "
                         "precomputed factors; got neither")
    if hessian is not None:
        h = hessian.shape[-1]
    elif isinstance(factors, packing.PackedFactor):
        h = factors.h
    else:
        h = factors.shape[-1]
    g = sample_lams.shape[0]
    if g <= degree:
        raise ValueError(f"need g > r: got g={g}, r={degree}")
    bk = resolve_backend(backend)
    chol_fn = chol_fn or bk.cholesky

    if isinstance(factors, packing.PackedFactor):
        if factors.block != block or factors.h != h:
            raise ValueError(
                f"packed factors have (h={factors.h}, block={factors.block}); "
                f"fit called with (h={h}, block={block})")
        factors = factors.vec
    elif factors is None:
        factors = anchor_factors(hessian, sample_lams, chol_fn)
    with tracing.scope(tracing.THETA_FIT):
        # Step 2: tile-packed target matrix T (g × P) — aligned BLAS-3 layout.
        targets = (factors if factors.ndim == 2
                   else bk.pack_tril(factors, block))
        theta, center = fit_targets(targets, sample_lams, degree,
                                    block=block, basis=basis, backend=bk)
        return PiCholesky(theta=theta, center=center, h=h, block=block)


def fit_targets(targets: jax.Array, sample_lams: jax.Array, degree: int, *,
                block: int, basis: str = "monomial",
                backend: BackendLike = "reference"):
    """Steps 5–6 of Algorithm 1 on packed targets ``(g, N)``: ``(Θ (r+1,
    N) at the storage dtype, center)``.  Each column is fitted alone, so
    ``N`` may be any whole number of ``block²`` tiles: all of a factor, or
    the slab of it one device fits in a divided state stage."""
    bk = resolve_backend(backend)
    g = sample_lams.shape[0]
    center = (jnp.mean(sample_lams) if basis == "centered"
              else jnp.zeros((), sample_lams.dtype))
    fit_dtype = bk.precision.fit_dtype(targets.dtype)
    store_dtype = bk.precision.store_dtype(targets.dtype)
    v = vandermonde(sample_lams, degree, center).astype(fit_dtype)

    # Θ = (VᵀV)⁻¹ VᵀT — normal equations exactly as in the paper, at the
    # fit dtype; Θ is then stored at the storage dtype.  One packed tile of
    # columns at a time: a fold-batched solve against the whole (r+1, P)
    # right-hand side compiled for v5e to 13.2 GiB of temporaries for the
    # 5-fold state at h=4096, against 4.8 GiB this way.
    h_lam = v.T @ v
    tiles = targets.astype(fit_dtype).reshape(g, -1, block * block)
    theta = jax.lax.map(lambda t: jnp.linalg.solve(h_lam, v.T @ t),
                        jnp.moveaxis(tiles, 1, 0))     # (n, r+1, B²)
    theta = jnp.moveaxis(theta, 0, 1).reshape(degree + 1, -1)
    return theta.astype(store_dtype), center.astype(fit_dtype)


def anchor_factors(hessian: jax.Array, sample_lams: jax.Array,
                   chol_fn: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Step 1: the g exact factorizations ``chol(H + λ_s I)``, (g, h, h)."""
    with tracing.scope(tracing.ANCHOR_CHOL):
        eye = jnp.eye(hessian.shape[-1], dtype=hessian.dtype)
        return jax.vmap(lambda lam: chol_fn(hessian + lam * eye))(sample_lams)


def pair_factors(hessians: jax.Array, lams: jax.Array,
                 chol_fn: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Step 1 for a list of (Hessian, shift) pairs: ``chol(H_p + λ_p I)``,
    (p, h, h) — the anchor factorizations one device runs in a divided
    state stage."""
    with tracing.scope(tracing.ANCHOR_CHOL):
        eye = jnp.eye(hessians.shape[-1], dtype=hessians.dtype)
        return jax.vmap(lambda a, lam: chol_fn(a + lam * eye))(hessians,
                                                               lams)


def loo_interp_scores(
    targets: jax.Array,
    sample_lams: jax.Array,
    degrees: Sequence[int],
    *,
    bases: Sequence[str] = ("monomial",),
    backend: BackendLike = "reference",
) -> dict:
    """Leave-one-anchor-out CV scores for candidate (degree, basis) pairs.

    ``targets``: tile-packed anchor factors, ``(g, P)`` or batched
    ``(k, g, P)`` — exactly what :meth:`~repro.core.factor_cache.FactorCache`
    stores under the anchor digest, so scoring candidates against a warm
    cache performs **zero factorizations**: each candidate fit is a weighted
    normal-equations solve on ``g−1`` anchors plus one Horner row at the
    held-out anchor (GEMMs only, the pyapprox ``cross_validate_pce_degree``
    idiom transplanted to factor space).

    The score of a candidate is the mean (over anchors and folds) relative
    Frobenius error of the held-out packed factor prediction.  Candidates
    need ``g − 1 > degree`` (the reduced fit must still be overdetermined
    enough to solve); offering a degree that violates this raises.

    Returns ``{(degree, basis): float}``.
    """
    t = jnp.asarray(targets)
    if t.ndim == 2:
        t = t[None]                                    # (k=1, g, P)
    lam = jnp.asarray(sample_lams)
    g = int(lam.shape[0])
    for r in degrees:
        if g - 1 <= int(r):
            raise ValueError(
                f"leave-one-out selection needs g - 1 > degree: "
                f"g={g} anchors cannot score degree {r}")
    bk = resolve_backend(backend)
    fit_dtype = bk.precision.fit_dtype(t.dtype)
    t = t.astype(fit_dtype)
    lam = lam.astype(fit_dtype)
    eps = jnp.asarray(jnp.finfo(fit_dtype).tiny, fit_dtype)
    norms = jnp.linalg.norm(t, axis=-1) + eps          # (k, g)

    scores: dict = {}
    for basis in bases:
        if basis not in ("monomial", "centered"):
            raise ValueError(f"unknown basis {basis!r}; "
                             "expected 'monomial' or 'centered'")
        center = (jnp.mean(lam) if basis == "centered"
                  else jnp.zeros((), fit_dtype))
        for r in degrees:
            v = vandermonde(lam, int(r), center)       # (g, r+1)

            def loo_err(s):
                w = (jnp.arange(g) != s).astype(fit_dtype)
                vw = v * w[:, None]                    # zero the held-out row
                gram = vw.T @ v                        # (r+1, r+1)
                rhs = jnp.einsum("gr,kgp->krp", vw, t)
                theta = jax.vmap(
                    lambda b: jnp.linalg.solve(gram, b))(rhs)
                pred = jnp.einsum("r,krp->kp", v[s], theta)
                return jnp.linalg.norm(pred - t[:, s], axis=-1) / norms[:, s]

            errs = jax.vmap(loo_err)(jnp.arange(g))    # (g, k)
            scores[(int(r), basis)] = float(jnp.mean(errs))
    return scores


def select_interpolant(
    targets: jax.Array,
    sample_lams: jax.Array,
    degrees: Optional[Sequence[int]] = None,
    *,
    bases: Sequence[str] = ("monomial", "centered"),
    backend: BackendLike = "reference",
) -> dict:
    """Choose the interpolant (degree, basis) by :func:`loo_interp_scores`.

    ``degrees=None`` tries every LOO-scorable degree ``1 .. g−2``.  Ties
    break toward the *lowest* degree (candidates are scored in ascending
    order and only a strictly better score displaces the incumbent), so
    exactly-polynomial targets select the generating degree, not an
    equally-zero-error overfit.

    Returns ``dict(degree=, basis=, score=, scores={'basis/r': float})``.
    """
    lam = jnp.asarray(sample_lams)
    g = int(lam.shape[0])
    if degrees is None:
        degrees = tuple(range(1, g - 1))
    degrees = tuple(int(r) for r in degrees)
    if not degrees:
        raise ValueError(f"no candidate degrees to select from "
                         f"(g={g} anchors admit degrees 1..{g - 2})")
    scores = loo_interp_scores(targets, lam, degrees, bases=bases,
                               backend=backend)
    best_key, best = None, None
    for basis in bases:                 # stable order: basis-major,
        for r in degrees:               # ascending degree — ties keep the
            s = scores[(r, basis)]      # simplest candidate
            if best is None or s < best:
                best_key, best = (r, basis), s
    return dict(degree=best_key[0], basis=best_key[1], score=best,
                scores={f"{b}/r{r}": s for (r, b), s in scores.items()})


def evaluate_packed(model: PiCholesky, lams: jax.Array) -> "packing.PackedFactor":
    """Interpolated factors at a dense λ grid, still tile-packed: (q, P)."""
    return model.eval_packed_factor(lams)


def evaluate(model: PiCholesky, lams: jax.Array) -> jax.Array:
    """Dense interpolated factors (q, h, h) — debug escape hatch; the sweep
    path consumes :func:`evaluate_packed` / :meth:`PiCholesky.solve`."""
    return model.eval_factor(lams)


def refine_solutions(model: PiCholesky, hessian: jax.Array, g: jax.Array,
                     lams: jax.Array, thetas: jax.Array,
                     backend: BackendLike = "reference",
                     iters: Optional[int] = None) -> jax.Array:
    """Iterative refinement of ``interp_solve`` solutions — the accuracy
    half of the ``bf16_refined`` policy.

    The low-precision interpolated factor is a *preconditioner*: each
    sweep forms the true residual ``r(λ) = g − (H + λI)θ(λ)`` at the
    policy's accumulation dtype (exact λ — never the bf16-quantized one the
    Horner evaluation used) and corrects through one more fused interpolant
    solve with the per-λ residuals as RHS.  One iteration contracts the
    solve error by O(κ·ε_bf16), which is what lets a bf16-stored factor
    reproduce the fp32 hold-out argmin (Wilson et al.: hold-out selection
    tolerates controlled solve error; refinement makes the control
    explicit).  Runs per λ chunk inside ``fold_errors``, so its transient
    (q_chunk, h) residuals ride inside the existing O(chunk · P) budget.

    No-op (returns ``thetas`` unchanged) when the backend policy's
    ``refine_iters`` is 0.  ``iters=`` overrides the policy count — the
    sketched-anchor path uses this to run its IHS contraction loop
    (exact residuals against the dense Hessian, sketched factor as the
    preconditioner) through the same fused solve.
    """
    bk = resolve_backend(backend)
    iters = bk.precision.refine_iters if iters is None else int(iters)
    if iters <= 0:
        return thetas
    ad = bk.precision.accum_dtype(model.theta.dtype)
    hs = hessian.astype(ad)
    gs = g.astype(ad)
    lam_col = jnp.atleast_1d(lams).astype(ad)[:, None]
    th = jnp.atleast_2d(thetas).astype(ad)              # (q, h)
    for _ in range(iters):
        resid = gs[None, :] - (th @ hs + lam_col * th)  # H symmetric
        delta = bk.interp_solve(model.theta, jnp.atleast_1d(lams), resid,
                                h=model.h, block=model.block,
                                center=model.center, rhs_per_lam=True)
        th = th + delta.astype(ad)
    return th.reshape(thetas.shape) if thetas.ndim == 1 else th
