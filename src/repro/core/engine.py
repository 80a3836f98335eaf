"""Unified CV engine: one jitted, batched, sharded fold × λ sweep.

The paper's experiment is a dense grid of independent ridge solves — k folds
by q regularizers.  The legacy drivers in :mod:`repro.core.cv` walked that
grid with host-side Python loops (one trace per fold, NumPy syncs mid-sweep).
This module runs the whole grid as **one jitted computation**:

* folds are batched with ``vmap`` (all per-fold factorizations/fits are a
  single batched kernel launch),
* with a mesh, the grid is laid over a 2-D ``(folds × lams)`` device mesh
  via ``shard_map`` — fold Hessians shard over the fold axis, the λ grid
  over the λ axis (padded to divisibility, see
  :mod:`repro.distributed.sharding`),
* the per-fold training Hessians are donated into the sweep so the largest
  intermediate (k × h × h) never holds two copies in HBM,
* the λ axis is **streamed**: each device's λ shard is processed in
  fixed-size chunks under an outer ``lax.map`` (``lam_chunk=``, default
  VMEM-sized), and the interpolant strategies solve each chunk in the
  tile-packed domain (:class:`~repro.core.packing.PackedFactor` currency,
  fused Horner + packed trsm) — peak sweep memory is O(chunk · P),
  independent of the grid size q,
* all linear algebra goes through one ``backend=`` switch
  (:mod:`repro.core.backends`): Pallas kernels on TPU, ``jnp.linalg``
  elsewhere,
* with a ``cache=`` (:mod:`repro.core.factor_cache`), repeated sweeps over
  overlapping λ grids take the **warm-replay path**: the fitted per-fold Θ
  is content-fingerprinted and reused, skipping the heavy ``fold_state``
  stage entirely — a warm sweep performs *zero* Cholesky factorizations
  and replays any grid over the cached anchor range through the fused
  ``interp_solve`` chunked stream,
* the same seam also drives the **pipelined staged sweep**
  (:meth:`CVEngine.sweep_async` / :meth:`CVEngine.run_async`): per-fold
  ``fold_state`` stages dispatch without blocking (double-buffered donated
  Hessian slices), the λ grid streams through one jitted chunk stage, each
  completed chunk is yielded as a partial hold-out curve, and the
  early-stop search (``stop_tol=``) terminates the stream once the running
  minimum stops improving — the hold-out curve is evaluated only as far as
  selection needs it.

Algorithms plug in through the small :class:`CVStrategy` protocol; the five
paper algorithms (`exact`, `picholesky`, `picholesky_warmstart`, `svd`,
`pinrmse`) ship as built-ins.  Adding a strategy means implementing at most
three methods:

``prepare(x_folds, y_folds, h_tr, g_tr, lams, bk)``
    Replicated setup (runs identically on every device): pick sample λs,
    fit an anchor model, stash training data a fold needs from *other*
    folds.  Returns an arbitrary pytree ``aux`` (default ``()``).
``fold_state(f_idx, h_tr_f, g_tr_f, aux, bk)``
    The heavy λ-independent per-fold stage (factorizations, SVDs, fits).
    Runs under ``vmap`` over folds, sharded over the fold mesh axis.
``fold_errors(state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk)``
    The per-(fold, λ) stage: evaluate/solve/score on a (possibly λ-sharded)
    grid chunk.  Returns the (q_local,) hold-out error curve.

``MChol`` (§6.2) stays a host-side driver in :mod:`repro.core.cv`: its
binary search is decision-dependent and factorizes three shifts per level,
so there is no dense grid to batch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import (Any, Callable, Iterator, Optional, Protocol, Union,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shardlib

from . import factor_cache as cachelib
from . import packing, picholesky, solvers, tracing
from . import sketch as sketchlib
from .backends import BackendLike, LinalgBackend, resolve_backend
from .folds import CVResult, FoldData, holdout_nrmse
from .precision import PrecisionLike

__all__ = [
    "CVStrategy", "CVEngine", "SweepChunk", "make_strategy", "STRATEGIES",
    "ExactCholesky", "PiCholeskyStrategy", "PiCholeskySketched",
    "PiCholeskyWarmstart", "SVDStrategy", "PinrmseStrategy",
    "LowRankStrategy",
]


def _jit(fn: Callable, **jit_kwargs) -> Callable:
    """``jax.jit`` of an engine stage whose matmuls are traced at full
    precision.  On a TPU an f32 matmul otherwise runs as one bf16 pass,
    which is not the f32 arithmetic the precision policies promise (the Θ
    fit, the refinement residual and the scoring all go through XLA).  The
    Pallas kernels set their own MXU precision."""
    @functools.wraps(fn)
    def stage(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return jax.jit(stage, **jit_kwargs)


def _sample_grid(lams: jax.Array, g: int) -> jax.Array:
    """g log-spaced sample shifts spanning the dense grid (traced-safe).

    Same nodes as the host drivers and the ``extras['sample_lams']`` the
    wrappers report — one definition, so they cannot drift apart.
    """
    return picholesky.choose_sample_lambdas(lams[0], lams[-1], g
                                            ).astype(lams.dtype)


def _errors_from_thetas(thetas: jax.Array, x_f: jax.Array,
                        y_f: jax.Array) -> jax.Array:
    with tracing.scope(tracing.SCORE):
        return jax.vmap(lambda t: holdout_nrmse(t, x_f, y_f))(thetas)


def _fold_scores(thetas: jax.Array, x_folds: jax.Array,
                 y_folds: jax.Array) -> jax.Array:
    """(k, q) hold-out errors of each fold's solutions (k, q, h)."""
    return jax.vmap(_errors_from_thetas)(thetas, x_folds, y_folds)


def _split_stats(hess, grad, fold_hess, fold_grad):
    """Per-fold train Hessians and gradients: the totals less each fold's."""
    with tracing.scope(tracing.SPLIT):
        return hess[None] - fold_hess, grad[None] - fold_grad


def _split_folds(total, parts, folds):
    """The train statistics of the folds ``folds`` (an index array of any
    shape): the total less each of those folds'."""
    with tracing.scope(tracing.SPLIT):
        return total[None] - parts[folds]


def _repl(tree) -> Any:
    """``shard_map`` specs placing every leaf of ``tree`` whole on every
    device."""
    return jax.tree.map(lambda _: P(), tree)


def _packed_anchors(factors: jax.Array, block: int, bk) -> jax.Array:
    """The anchor factors tile-packed, (g, P): the Θ fit's targets."""
    with tracing.scope(tracing.THETA_FIT):
        return bk.pack_tril(factors, block)


# ------------------------------------------------------------------ protocol


@runtime_checkable
class CVStrategy(Protocol):
    name: str

    def n_exact_chol(self, k: int, q: int) -> int: ...

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams,
                bk: LinalgBackend) -> Any: ...

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux,
                   bk: LinalgBackend) -> Any: ...

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux,
                    bk: LinalgBackend) -> jax.Array: ...


class StrategyBase:
    """Default no-op prepare/fold_state for strategies that don't need them."""

    #: True when ``fold_state`` reads the per-fold train Hessian — the
    #: pipelined sweep donates each fold's Hessian slice into the per-fold
    #: state stage only then (donating an unread buffer is an XLA warning,
    #: not a win).
    state_uses_hessian: bool = False

    #: True when ``fold_state`` is a pure PER-FOLD function of
    #: (h_tr_f, g_tr_f, anchors, params, backend) — independent of the fold
    #: index and of every *other* fold — AND ``prepare`` depends only on
    #: the λ grid.  That is what lets :meth:`CVEngine.run_batch` stack
    #: several tenants' fold axes into ONE ``fold_state`` dispatch and
    #: slice the batched state back per problem.  Strategies coupling
    #: folds (warmstart's fold-0 anchor fit) or reading the fold index
    #: must leave this False.
    batchable_state: bool = False

    #: True when the strategy can divide its state stage's factorizations
    #: over the devices of a mesh axis (``divided_state``) — the engine
    #: then does so over the λ axis, whose devices hold the same folds.
    #: Such a strategy's ``prepare`` reads only the λ grid, and its state
    #: stage reads only the Hessians of each device's own pairs' folds.
    divisible_state: bool = False

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        return ()

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        return ()

    def cache_meta(self, lams) -> Optional[dict]:
        """Warm-replay cache support (None = not cacheable).

        Cacheable strategies return ``dict(anchors=<(g,) λ grid the fit
        factorizes at>, params=<static fit parameters>)`` — the λ-dependent
        and static halves of the :class:`~repro.core.factor_cache.CacheKey`.
        Contract for a non-None return: ``fold_state`` is a pure function
        of (per-fold train Hessian, anchors, params, backend), and
        ``fold_errors`` must not read ``aux`` (a replayed sweep runs with
        ``aux=()``, skipping ``prepare`` entirely).
        """
        return None


# ---------------------------------------------------------------- strategies


@dataclasses.dataclass(frozen=True, eq=False)
class ExactCholesky(StrategyBase):
    """Chol baseline: factorize at every (fold, λ) — k·q factorizations.

    All the work sits in ``fold_errors`` so it parallelizes over *both* mesh
    axes: each device factorizes only its own (fold, λ) sub-grid.
    """

    chol_fn: Optional[Callable] = None
    name: str = "exact"

    def n_exact_chol(self, k, q):
        return k * q

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        with tracing.scope("cv.exact"):
            thetas = solvers.solve_cholesky_sweep(h_tr_f, g_tr_f, lams,
                                                  self.chol_fn, bk)
        return _errors_from_thetas(thetas, x_f, y_f)


class _InterpolantErrors:
    """Shared λ-stage for the piCholesky family: fused interpolant
    evaluation + substitution at the local λ chunk, entirely in the packed
    domain — no (q_loc, h, h) factor batch is ever materialized (the
    pre-packed-pipeline eval_factor → dense-trsm route survives only as the
    ``PiCholesky.eval_factor`` debug escape hatch).

    Under a refining precision policy (``bf16_refined``) each chunk's
    low-precision solves are corrected by
    :func:`~repro.core.picholesky.refine_solutions` — an fp32 residual
    sweep per λ chunk, riding inside the same O(chunk · P) budget."""

    @staticmethod
    def solutions(state, g_tr_f, lams, bk):
        """θ(λ) of one fold at the λ chunk, (q_loc, h), unrefined: what
        ``fold_errors`` scores where the policy does not refine."""
        with tracing.scope(tracing.LAM_STAGE):
            return state.solve(lams, g_tr_f, backend=bk)

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        thetas = self.solutions(state, g_tr_f, lams, bk)      # (q_loc, h)
        if bk.precision.refine_iters:
            with tracing.scope(tracing.REFINE):
                thetas = picholesky.refine_solutions(
                    state, h_tr_f, g_tr_f, lams, thetas, backend=bk)
        return _errors_from_thetas(thetas, x_f, y_f)


@dataclasses.dataclass(frozen=True, eq=False)
class PiCholeskyStrategy(_InterpolantErrors, StrategyBase):
    """Algorithm 1 per fold: g exact factorizations + a polynomial fit;
    the dense sweep reads the interpolant only."""

    g: int = 4
    degree: int = 2
    block: int = 128
    basis: str = "monomial"
    chol_fn: Optional[Callable] = None
    name: str = "picholesky"
    state_uses_hessian = True
    batchable_state = True
    divisible_state = True

    def n_exact_chol(self, k, q):
        return k * self.g

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        with tracing.scope(tracing.ANCHOR_CHOL):
            return _sample_grid(lams, self.g)

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        return picholesky.fit(h_tr_f, aux, self.degree, block=self.block,
                              basis=self.basis, chol_fn=self.chol_fn,
                              backend=bk)

    def cache_meta(self, lams):
        if self.chol_fn is not None:     # opaque override — unkeyable
            return None
        anchors = _sample_grid(jnp.asarray(lams), self.g)
        return dict(anchors=anchors,
                    params=dict(strategy=self.name, g=self.g,
                                degree=self.degree, block=self.block,
                                basis=self.basis))

    def fold_state_and_anchors(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        """``fold_state`` that also surfaces the tile-packed anchor factors
        (g, P) so the engine can cache them — a later fit with a different
        degree/basis over the same anchors then refits from these targets
        with zero factorizations (``picholesky.fit(factors=...)``)."""
        factors = picholesky.anchor_factors(h_tr_f, aux, bk.cholesky)
        vec = _packed_anchors(factors, self.block, bk)
        pf = packing.PackedFactor(vec=vec, h=h_tr_f.shape[-1],
                                  block=self.block)
        model = picholesky.fit(h_tr_f, aux, self.degree, block=self.block,
                               basis=self.basis, factors=pf, backend=bk)
        # fit from the full-precision targets, cache at the storage dtype
        with tracing.scope(tracing.THETA_FIT):
            return model, vec.astype(bk.precision.store_dtype(vec.dtype))

    def pair_layout(self, k: int, n: int, h: int) -> shardlib.PairLayout:
        """How :meth:`divided_state` deals ``k`` folds' pairs out over
        ``n`` devices."""
        return shardlib.PairLayout(k, self.g, n, h, self.block)

    def divided_state(self, h_loc, k: int, aux, bk, n: int, axis: str):
        """``fold_state`` of the ``k`` folds of a λ row, their k·g anchor
        factorizations divided over the ``n`` devices of mesh axis
        ``axis`` (:meth:`pair_layout`), inside the engine's ``shard_map``
        body.  ``h_loc`` (f, h, h) holds the train Hessians of the folds
        this device's pairs read, its row of
        :meth:`~repro.distributed.sharding.PairLayout.device_folds`.  This
        device factorizes and packs its own (fold, anchor) pairs, an
        all-to-all gives it one tile slab of every pair, it fits Θ on that
        slab for every fold, and a gather gives every device every fold's
        Θ.  Returns ``(state batched over the k folds, this device's
        packed anchors (per_device, P) at the storage dtype)``."""
        h = h_loc.shape[-1]
        lay = self.pair_layout(k, n, h)
        fold, anchor = lay.pairs(jax.lax.axis_index(axis))
        with tracing.scope(tracing.ANCHOR_CHOL):
            hess = h_loc[fold - fold[0]]
        factors = picholesky.pair_factors(hess, aux[anchor],
                                          self.chol_fn or bk.cholesky)
        vec = _packed_anchors(factors, self.block, bk)   # (per_device, P)
        size = vec.shape[-1]
        with tracing.scope(tracing.ANCHOR_EXCHANGE):
            slabs = jnp.pad(vec, ((0, 0), (0, n * lay.slab - size)))
            got = jax.lax.all_to_all(slabs, axis, 1, 0, tiled=True)
            targets = got[:k * self.g].reshape(k, self.g, lay.slab)
        with tracing.scope(tracing.THETA_FIT):
            theta, center = jax.vmap(lambda t: picholesky.fit_targets(
                t, aux, self.degree, block=self.block, basis=self.basis,
                backend=bk))(targets)
        with tracing.scope(tracing.ANCHOR_EXCHANGE):
            # gathered on a new leading axis: a tiled gather along the
            # packed axis compiled for v5e in 2.4 times the time at h=4096
            theta = jax.lax.all_gather(theta, axis)       # (n, k, r+1, slab)
            theta = jnp.moveaxis(theta, 0, 2).reshape(
                k, self.degree + 1, -1)[..., :size]
        state = picholesky.PiCholesky(theta=theta, center=center, h=h,
                                      block=self.block)
        with tracing.scope(tracing.THETA_FIT):
            return state, vec.astype(bk.precision.store_dtype(vec.dtype))

    def anchor_hessian(self, f_idx, h_tr_f, x_folds, bk):
        """Hessian the anchor factorizations run on — the exact per-fold
        training Hessian here; the sketched subclass substitutes its
        sketched gram so interpolant selection scores the same targets
        the sweep will actually fit."""
        return h_tr_f


@dataclasses.dataclass(frozen=True, eq=False)
class PiCholeskySketched(PiCholeskyStrategy):
    """Algorithm 1 over **sketched** anchor Hessians — Iterative Hessian
    Sketch (Pilanci & Wainwright, arXiv:1411.0347) behind the piCholesky
    seam.

    Each fold's anchor factorizations run on ``H̃_f = (S X_tr)ᵀ (S X_tr)``
    built from ``m ≪ n`` sketched rows of the fold's training design
    (reconstructed from the *other* folds' raw blocks, like
    :class:`SVDStrategy`), so forming the anchor Hessian costs O(m·h²)
    instead of O(n·h²) — the win at n ≫ h geometries.  The interpolated
    solves are then IHS-corrected in ``fold_errors``: the sketched factor
    is the *preconditioner* and the residuals are exact (dense ``H_f``),
    so the solve error contracts geometrically with
    ``sketch.ihs_iters`` — reusing the precision policy's
    :func:`~repro.core.picholesky.refine_solutions` loop with an explicit
    iteration override.

    Everything downstream of :func:`~repro.core.picholesky.fit` — packed
    trsm, fused ``interp_solve``, λ-chunking, warm-replay cache, async
    sweep, ``search()`` — consumes the sketched state unchanged.  The
    plan's :meth:`~repro.core.sketch.SketchPlan.descriptor` rides in
    ``cache_meta`` → :class:`~repro.core.factor_cache.CacheKey`, so a
    sketched factor can never silently serve an exact request (nor one
    sketched under a different method/m/seed/iteration count).

    ``fold_state`` reads raw fold rows from ``aux`` and the fold index, so
    it is neither Hessian-donatable nor admission-batchable; ``run_batch``
    degrades to per-problem runs.
    """

    sketch: Optional[sketchlib.SketchPlan] = None
    name: str = "picholesky_sketched"
    state_uses_hessian = False
    batchable_state = False
    divisible_state = False

    def __post_init__(self):
        object.__setattr__(self, "sketch", sketchlib.as_plan(self.sketch))

    def _plan(self) -> sketchlib.SketchPlan:
        if self.sketch is None:
            raise ValueError(
                "picholesky_sketched needs a SketchPlan: pass "
                "CVEngine(sketch=...) or PiCholeskySketched(sketch=...)")
        return self.sketch

    @staticmethod
    def _train_rows(f_idx, x_folds):
        k, n_f, h = x_folds.shape
        others = (f_idx + 1 + jnp.arange(k - 1)) % k
        return x_folds[others].reshape((k - 1) * n_f, h)

    def _sketched_hessian(self, f_idx, x_folds, bk):
        with tracing.scope("cv.sketch"):
            x_tr = self._train_rows(f_idx, x_folds)
            ad = bk.precision.accum_dtype(x_tr.dtype)
            h_sk = sketchlib.sketched_gram(self._plan(), x_tr, f_idx,
                                           accum_dtype=ad)
            return h_sk.astype(x_tr.dtype)

    def anchor_hessian(self, f_idx, h_tr_f, x_folds, bk):
        return self._sketched_hessian(f_idx, x_folds, bk)

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        self._plan()    # fail at trace time, not mid-vmap
        with tracing.scope(tracing.ANCHOR_CHOL):
            return dict(anchors=_sample_grid(lams, self.g), x=x_folds)

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        h_sk = self._sketched_hessian(f_idx, aux["x"], bk)
        return picholesky.fit(h_sk, aux["anchors"], self.degree,
                              block=self.block, basis=self.basis,
                              chol_fn=self.chol_fn, backend=bk)

    def fold_state_and_anchors(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        h_sk = self._sketched_hessian(f_idx, aux["x"], bk)
        factors = picholesky.anchor_factors(h_sk, aux["anchors"],
                                            bk.cholesky)
        vec = _packed_anchors(factors, self.block, bk)
        pf = packing.PackedFactor(vec=vec, h=h_sk.shape[-1],
                                  block=self.block)
        model = picholesky.fit(h_sk, aux["anchors"], self.degree,
                               block=self.block, basis=self.basis,
                               factors=pf, backend=bk)
        with tracing.scope(tracing.THETA_FIT):
            return model, vec.astype(bk.precision.store_dtype(vec.dtype))

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        # The IHS loop IS refine_solutions with the exact Hessian: the
        # sketched interpolant preconditions, the residual is dense-exact.
        # Never reads aux — warm replay runs with aux=().
        with tracing.scope(tracing.LAM_STAGE):
            thetas = state.solve(lams, g_tr_f, backend=bk)
        iters = self._plan().ihs_iters + bk.precision.refine_iters
        if iters:
            with tracing.scope(tracing.REFINE):
                thetas = picholesky.refine_solutions(
                    state, h_tr_f, g_tr_f, lams, thetas, backend=bk,
                    iters=iters)
        return _errors_from_thetas(thetas, x_f, y_f)

    def cache_meta(self, lams):
        meta = super().cache_meta(lams)
        if meta is None:
            return None
        meta["sketch"] = self._plan().descriptor()
        return meta


@dataclasses.dataclass(frozen=True, eq=False)
class PiCholeskyWarmstart(_InterpolantErrors, StrategyBase):
    """Cross-fold warm-starting (paper §7 future work).

    An anchor fit on fold 0 (``g_first`` factorizations over the full λ
    range) provides the coefficient prior Θ⁰.  Later folds' training
    Hessians differ from fold 0's by only two fold blocks (H−H_f vs H−H_0),
    so their factor curves are close to the anchor's: each fold refits only
    the **residual** from ``g_rest`` fresh factorizations at full-range
    nodes,

        Θ_f = Θ⁰ + argmin_Δ ‖V_r Δ − (T_f − V_r Θ⁰)‖² + μ‖S Δ‖²

    with S² = diag(V_rᵀV_r) making the damping scale-relative per monomial
    order (the λ grid spans decades, so absolute Tikhonov either crushes
    the constant term or ignores the quadratic one).  Because the residual
    targets are small, the correction degrades gracefully: with
    ``g_rest ≤ degree`` the unseen directions simply stay at the anchor
    value instead of extrapolating wildly — the failure mode that made the
    original host driver select edge-of-grid λ's.
    """

    g_first: int = 4
    g_rest: int = 2
    degree: int = 2
    mu: float = 1e-6
    block: int = 128
    chol_fn: Optional[Callable] = None
    name: str = "picholesky_warmstart"
    state_uses_hessian = True

    def n_exact_chol(self, k, q):
        # anchor fit + one refresh per fold (fold 0's refresh included:
        # the sweep stays uniform across folds, so it is performed)
        return self.g_first + k * max(self.g_rest, 1)

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        chol = self.chol_fn or bk.cholesky
        with tracing.scope(tracing.ANCHOR_CHOL):
            sample_full = _sample_grid(lams, self.g_first)
            sample_rest = _sample_grid(lams, max(self.g_rest, 1))
        base = picholesky.fit(h_tr[0], sample_full, self.degree,
                              block=self.block, chol_fn=chol, backend=bk)
        with tracing.scope("cv.warmstart"):
            # residual regression runs at the policy's fit dtype
            # (bf16-stored anchors must not degrade the damped least
            # squares)
            fit_dtype = bk.precision.fit_dtype(h_tr.dtype)
            v_rest = picholesky.vandermonde(sample_rest, self.degree
                                            ).astype(fit_dtype)
            gram = v_rest.T @ v_rest
            lhs = gram + self.mu * jnp.diag(jnp.diag(gram))
        return dict(sample_rest=sample_rest, v_rest=v_rest, lhs=lhs,
                    base_theta=base.theta, center=base.center)

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        factors = picholesky.anchor_factors(
            h_tr_f, aux["sample_rest"], self.chol_fn or bk.cholesky)
        with tracing.scope("cv.warmstart"):
            fit_dtype = aux["v_rest"].dtype
            t = bk.pack_tril(factors, self.block).astype(fit_dtype)
            resid = t - aux["v_rest"] @ aux["base_theta"].astype(fit_dtype)
            dtheta = jnp.linalg.solve(aux["lhs"], aux["v_rest"].T @ resid)
            theta = (aux["base_theta"].astype(fit_dtype) + dtheta
                     ).astype(aux["base_theta"].dtype)
        return picholesky.PiCholesky(theta=theta, center=aux["center"],
                                     h=h_tr_f.shape[-1], block=self.block)

    def cache_meta(self, lams):
        if self.chol_fn is not None:
            return None
        # Θ_f depends on both node sets: the fold-0 anchor fit and the
        # per-fold residual refresh grid.
        lams = jnp.asarray(lams)
        anchors = jnp.concatenate([
            _sample_grid(lams, self.g_first),
            _sample_grid(lams, max(self.g_rest, 1))])
        return dict(anchors=anchors,
                    params=dict(strategy=self.name, g_first=self.g_first,
                                g_rest=self.g_rest, degree=self.degree,
                                mu=self.mu, block=self.block))


@dataclasses.dataclass(frozen=True, eq=False)
class SVDStrategy(StrategyBase):
    """SVD / t-SVD / r-SVD baselines on the raw design matrix.

    Training rows come from the k−1 *other* folds, so the raw fold blocks
    ride along replicated in ``aux`` while the heavy per-fold SVD shards
    over the fold axis.
    """

    mode: str = "full"                 # full | truncated | randomized
    k_trunc: int = 0
    key: Optional[jax.Array] = None    # r-SVD projection key (shared by folds)
    name: str = "svd"

    def n_exact_chol(self, k, q):
        return 0

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        return dict(x=x_folds, y=y_folds)

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        with tracing.scope("cv.svd"):
            k, n_f, h = aux["x"].shape
            others = (f_idx + 1 + jnp.arange(k - 1)) % k
            x_tr = aux["x"][others].reshape((k - 1) * n_f, h)
            y_tr = aux["y"][others].reshape(-1)
            s, vt, uty = solvers.svd_ridge_factors(x_tr, y_tr, self.mode,
                                                   self.k_trunc, self.key)
        return dict(s=s, vt=vt, uty=uty)

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        with tracing.scope("cv.svd"):
            thetas = solvers.svd_ridge_sweep(
                (state["s"], state["vt"], state["uty"]), lams)
        return _errors_from_thetas(thetas, x_f, y_f)


@dataclasses.dataclass(frozen=True, eq=False)
class LowRankStrategy(StrategyBase):
    """Low-rank ACV (Stephenson, Udell & Broderick, arXiv:2008.10547) for
    the n ≪ h / rank-r regime the dense pipeline can't touch.

    ``fold_state`` SVDs the fold's raw (n_tr, h) training design — O(n²h),
    vs g·O(h³) anchor Cholesky factorizations — into
    :class:`~repro.core.solvers.LowRankFactors`; ``fold_errors`` sweeps any
    λ grid through the Woodbury identity

        θ(λ) = V (1/(e+λ) − 1/λ) Vᵀg + g/λ,

    exactly equal to the exact ridge path whenever ``rank ≥ rank(X)``
    (zero-eigenvalue directions self-cancel) and the rank-r ACV
    approximation below it.  The state is **λ-independent** — its cache
    entry carries an empty anchor grid, so *any* grid over the same
    problem replays it — and y-independent, so the Hessian-fingerprint
    content addressing is exactly valid (V, e are the eigenpairs of
    ``H_tr``).  ``cache_meta``'s sketch descriptor (``lowrank/r…``) keeps
    rank-truncated factors from ever serving an exact or differently
    truncated request.
    """

    rank: Optional[int] = None      # None = full min(n_tr, h)
    name: str = "low_rank"
    state_uses_hessian = False
    batchable_state = False

    def n_exact_chol(self, k, q):
        return 0

    def descriptor(self) -> str:
        return f"lowrank/r{'full' if self.rank is None else int(self.rank)}"

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        return dict(x=x_folds)

    def fold_state(self, f_idx, h_tr_f, g_tr_f, aux, bk):
        with tracing.scope("cv.low_rank"):
            k, n_f, h = aux["x"].shape
            others = (f_idx + 1 + jnp.arange(k - 1)) % k
            x_tr = aux["x"][others].reshape((k - 1) * n_f, h)
            return solvers.lowrank_ridge_factors(x_tr, self.rank,
                                                 precision=bk.precision)

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        # never reads aux — warm replay runs with aux=()
        with tracing.scope("cv.low_rank"):
            thetas = solvers.lowrank_ridge_sweep(
                state, g_tr_f, lams,
                compute_dtype=bk.precision.accum_dtype(g_tr_f.dtype))
        return _errors_from_thetas(thetas, x_f, y_f)

    def cache_meta(self, lams):
        lams = jnp.asarray(lams)
        # λ-independent state: empty anchor grid, so every grid over the
        # same problem derives the same key — any-grid warm replay.
        # block=0 rides in params because the engine's make_key call sites
        # read the packing block from there; the low-rank state is unpacked.
        return dict(anchors=jnp.zeros((0,), lams.dtype),
                    params=dict(strategy=self.name, block=0,
                                rank=-1 if self.rank is None
                                else int(self.rank)),
                    sketch=self.descriptor())


@dataclasses.dataclass(frozen=True, eq=False)
class PinrmseStrategy(StrategyBase):
    """PINRMSE straw-man (§6.5): interpolate the hold-out-error *curve*
    itself from g exact evaluations — the paper shows it selects wrong λ's.

    The k·g exact evaluations need every fold's statistics at the same g
    nodes plus a cross-fold mean, so they live in ``prepare`` (replicated —
    at engine scale this stage is the cheap one; the dense sweep it replaces
    is the cost being amortized).
    """

    g: int = 4
    degree: int = 2
    chol_fn: Optional[Callable] = None
    name: str = "pinrmse"

    def n_exact_chol(self, k, q):
        return k * self.g

    def prepare(self, x_folds, y_folds, h_tr, g_tr, lams, bk):
        sample = _sample_grid(lams, self.g)

        def fold_curve(h_f, g_f, x_f, y_f):
            thetas = solvers.solve_cholesky_sweep(h_f, g_f, sample,
                                                  self.chol_fn, bk)
            return _errors_from_thetas(thetas, x_f, y_f)

        with tracing.scope("cv.pinrmse"):
            mean_err = jax.vmap(fold_curve)(h_tr, g_tr, x_folds,
                                            y_folds).mean(0)
            # the curve fit runs at the policy's fit dtype (fp32 floor —
            # the interpolated *errors* must not quantize), one definition
            # shared with the factor fits instead of a local
            # jax_enable_x64 probe
            fit_dtype = bk.precision.fit_dtype(mean_err.dtype)
            v = picholesky.vandermonde(sample, self.degree).astype(fit_dtype)
            return jnp.linalg.solve(v.T @ v,
                                    v.T @ mean_err.astype(fit_dtype))

    def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams, aux, bk):
        with tracing.scope("cv.pinrmse"):
            v = picholesky.vandermonde(lams, self.degree).astype(aux.dtype)
            return v @ aux  # identical on every fold ⇒ mean is the curve


STRATEGIES = {
    "exact": ExactCholesky,
    "picholesky": PiCholeskyStrategy,
    "picholesky_sketched": PiCholeskySketched,
    "picholesky_warmstart": PiCholeskyWarmstart,
    "svd": SVDStrategy,
    "low_rank": LowRankStrategy,
    "pinrmse": PinrmseStrategy,
}


def make_strategy(name: str, **params) -> CVStrategy:
    try:
        return STRATEGIES[name](**params)
    except KeyError:
        raise ValueError(
            f"unknown strategy {name!r}; have {sorted(STRATEGIES)}") from None


# -------------------------------------------------------------------- engine


MeshLike = Union[None, str, Mesh]


@dataclasses.dataclass
class SweepChunk:
    """One completed λ chunk of a pipelined sweep — a partial error curve.

    Yielded by :meth:`CVEngine.sweep_async` as each chunk's hold-out errors
    land on the host; ``best_lam`` / ``best_error`` track the running
    minimum over everything streamed so far, and ``stopped`` marks the
    chunk at which the early-stop search terminated the stream.
    """

    index: int               # chunk position in the stream
    start: int               # global λ-grid offset of this chunk's first λ
    n_chunks: int            # chunks the full stream would have
    lams: np.ndarray         # (c,) this chunk's λs (padding stripped)
    fold_errors: np.ndarray  # (k, c) per-fold hold-out errors
    errors: np.ndarray       # (c,) fold-mean partial curve
    best_lam: float          # running argmin λ over all streamed chunks
    best_error: float        # running min mean error
    stopped: bool            # early stop fired at this chunk
    n_exact_chol: int        # factorizations for the grid evaluated so far
    cache: Optional[dict]    # warm-replay cache info (None without a cache)


#: Budget (bytes) the ``lam_chunk='auto'`` rule sizes a chunk's per-λ
#: factors against where the λ stage builds them: (chunk, P) packed rows on
#: the reference path, dense factors for the non-interpolant strategies.
#: The Pallas ``interp_solve`` builds none; its chunk is the kernel's own
#: VMEM rule (:meth:`~repro.core.backends.LinalgBackend.interp_lam_chunk`).
LAM_CHUNK_BUDGET_BYTES = 16 * 1024 * 1024


@dataclasses.dataclass
class CVEngine:
    """Batched/sharded k-fold × λ sweep runner.

    Parameters
    ----------
    strategy:  a :class:`CVStrategy` instance or registry name.
    backend:   ``'auto'`` (Pallas on TPU, reference elsewhere) | ``'pallas'``
               | ``'reference'`` | a :class:`LinalgBackend`.
    mesh:      ``None`` (default: chosen from the problem's geometry — one
               device while the one-device sweep fits what device 0 has
               free, by :func:`~repro.distributed.sharding.sweep_bytes` of
               (k, g, h, n, dtype) against
               :func:`~repro.distributed.sharding.device_bytes_free`, else
               the ``'auto'`` mesh over every local device; a device that
               reports no limit, the CPU, always fits), ``'auto'`` (2-D
               folds × lams mesh over all local devices), or an explicit
               2-D Mesh whose axes are ``(CV_FOLD_AXIS, CV_LAM_AXIS)``.
               Where the λ axis holds more than one device, the devices
               of a λ row hold the same folds, and a strategy that can
               divide its state stage (``divisible_state``: piCholesky)
               deals the (fold, anchor) factorizations out over them
               (:meth:`PiCholeskyStrategy.divided_state`); unless the
               policy refines, each device is then handed only its
               pairs' folds of the train Hessians and no hold-out row,
               and the designs' device scores the solutions.
    donate:    donate the per-fold training Hessians into the jitted sweep
               (``None`` = on except on CPU, where XLA cannot alias).
    block:     Pallas kernel tile size override for small test problems.
    lam_chunk: λ-axis streaming: the per-device λ shard is processed in
               fixed-size chunks under an outer ``lax.map``, so the sweep's
               peak memory is O(chunk · P) regardless of the grid size q.
               ``'auto'`` (default) sizes the chunk by what the λ stage
               holds per λ: on the Pallas ``interp_solve`` path, the
               kernel's VMEM working set (the whole shard at the paper's
               sizes: every Θ tile read once per chunk serves all its λs);
               elsewhere, one chunk's packed or dense factors within
               :data:`LAM_CHUNK_BUDGET_BYTES`.  An ``int`` fixes it;
               ``None`` disables streaming (whole shard in one call).
               Requires ``fold_errors`` to be λ-elementwise — true of every
               built-in strategy (each λ's solve/score is independent).
    cache:     a :class:`~repro.core.factor_cache.FactorCache` enabling the
               warm-replay path (strategies advertising ``cache_meta``,
               i.e. the piCholesky family).  On a fingerprint hit the heavy
               ``fold_state`` stage is skipped entirely and the sweep
               replays the cached Θ through the fused ``interp_solve``
               chunked stream (still O(chunk · P)); on a miss the cold
               stage runs and populates the cache.  ``None`` (default)
               keeps the original single-jit fused sweep.
    reuse:     cache read policy: ``'exact'`` (default — the requested
               grid must derive the very anchor set the entry was fitted
               on), ``'covering'`` (also accept a cached Θ whose anchor
               range covers the requested grid), or ``False`` (write-only:
               never read, always repopulate — the cold baseline for
               warm-vs-cold measurements).
    cache_anchors: also cache the per-(fold, λ_s) tile-packed anchor
               factors; a later run over the same anchors with a different
               degree/basis then refits Θ from them with zero
               factorizations.
    precision: the pipeline's :class:`~repro.core.precision.PrecisionPolicy`
               (a preset name, a policy object, or ``None`` = environment
               default, normally ``native``).  One policy governs every
               layer: factorizations run at its accumulation dtype, fitted
               Θ / cached anchors are stored at its storage dtype (bf16
               halves them, and the VMEM-auto ``lam_chunk`` doubles to
               match), the fused solves feed the MXU at its compute dtype,
               and ``refine_iters`` > 0 adds an fp32 residual-refinement
               sweep per λ chunk on top of the low-precision
               ``interp_solve`` (``bf16_refined`` reproduces the fp32
               hold-out argmin at half the factor bytes).  The policy is
               part of the cache fingerprint: a bf16 entry can never
               silently serve an fp32 request.  When an explicit backend
               *instance* is passed without ``precision``, the backend's
               own policy is adopted — one policy per pipeline, resolved
               once.
    tune:      roofline-guided compile-time autotuning
               (:mod:`repro.distributed.autotune`).  ``False`` (default)
               runs the configured block / λ-chunk / mesh as-is.
               ``'auto'`` searches the legal configuration lattice on the
               first sweep of each problem geometry — every candidate is
               AOT-lowered and scored against the roofline model; nothing
               executes — and runs the predicted-fastest configuration
               (kernel tiles, packing block, λ-chunk and mesh shape all
               follow the choice).  A
               :class:`~repro.distributed.autotune.TunedConfig` pins a
               previously chosen configuration.  Tuning never changes
               *what* is computed — only tiling, chunking and layout —
               and a repeat geometry hits the content-addressed
               ``tune_cache`` without re-lowering anything.
    tune_cache: a :class:`~repro.distributed.autotune.TuningCache` shared
               across engines (the serving layer passes one per server);
               ``None`` with ``tune='auto'`` creates a private one.
    tune_lattice: optional lattice overrides forwarded to
               :func:`~repro.distributed.autotune.tune` (``blocks=``,
               ``chunks=``, ``mesh_shapes=``, ``hw=``) — benches and
               tests shrink the search with this.
    sketch:    a :class:`~repro.core.sketch.SketchPlan` (or its dict form)
               switching anchor factorization to the sketched route:
               ``CVEngine(strategy='picholesky', sketch=plan)`` upgrades
               the strategy to :class:`PiCholeskySketched` — anchor
               Hessians built from ``m ≪ n`` sketched rows, IHS-refined
               solves, cache entries keyed by the plan's descriptor.
               ``None`` (default) keeps exact anchors.
    """

    strategy: Union[CVStrategy, str]
    backend: BackendLike = None
    mesh: MeshLike = None
    donate: Optional[bool] = None
    block: Optional[int] = None
    lam_chunk: Union[None, int, str] = "auto"
    cache: Optional[cachelib.FactorCache] = None
    reuse: Union[bool, str] = "exact"
    cache_anchors: bool = False
    precision: PrecisionLike = None
    tune: Any = False
    tune_cache: Optional[Any] = None
    tune_lattice: Optional[dict] = None
    sketch: Optional[Any] = None

    def __post_init__(self):
        if isinstance(self.strategy, str):
            self.strategy = make_strategy(self.strategy)
        if self.sketch is not None:
            plan = sketchlib.as_plan(self.sketch)
            strat = self.strategy
            if isinstance(strat, PiCholeskySketched):
                if strat.sketch is None:
                    self.strategy = dataclasses.replace(strat, sketch=plan)
                elif strat.sketch != plan:
                    raise ValueError(
                        f"conflicting sketch plans: engine sketch= is "
                        f"{plan.descriptor()} but the strategy carries "
                        f"{strat.sketch.descriptor()}")
            elif isinstance(strat, PiCholeskyStrategy) and \
                    type(strat) is PiCholeskyStrategy:
                self.strategy = PiCholeskySketched(
                    g=strat.g, degree=strat.degree, block=strat.block,
                    basis=strat.basis, chol_fn=strat.chol_fn, sketch=plan)
            else:
                raise ValueError(
                    "sketch= needs the picholesky strategy, got "
                    f"{getattr(strat, 'name', strat)!r}")
            self.sketch = plan
        if isinstance(self.strategy, PiCholeskySketched) \
                and self.strategy.sketch is None:
            raise ValueError(
                "picholesky_sketched needs a SketchPlan: pass "
                "CVEngine(sketch=...) or a strategy instance with sketch=")
        if self.reuse is True:
            self.reuse = "exact"
        if self.reuse not in (False, "exact", "covering"):
            raise ValueError(f"reuse must be 'exact', 'covering' or False; "
                             f"got {self.reuse!r}")
        if self.tune not in (False, "auto") \
                and type(self.tune).__name__ != "TunedConfig":
            raise ValueError(f"tune must be False, 'auto' or a TunedConfig; "
                             f"got {self.tune!r}")
        self._bk = resolve_backend(self.backend, block=self.block,
                                   precision=self.precision)
        self._prec = self._bk.precision   # one policy per pipeline
        self._tuned_engines: dict = {}    # TunedConfig.key() -> derived engine
        if self.donate is None:
            self.donate = jax.default_backend() != "cpu"
        self._programs: dict = {}   # (stage, mesh key, flags) -> jitted
        self._interp_engines: dict = {}  # (degree, basis) -> derived engine
        self._split = jax.jit(_split_stats)
        self._split_folds = jax.jit(_split_folds)

    # -- mesh -------------------------------------------------------------

    def _resolve_mesh(self, folds: FoldData) -> Optional[Mesh]:
        """The sweep's mesh for ``folds``; with ``mesh=None`` the default
        rule reads their geometry (:meth:`_fit_mesh`)."""
        if self.mesh is None:
            return self._fit_mesh(folds)
        if isinstance(self.mesh, Mesh):
            return self.mesh
        if self.mesh == "auto":
            if len(jax.devices()) == 1:
                return None
            return shardlib.make_cv_mesh(folds.fold_hess.shape[0])
        raise ValueError(f"mesh must be None, 'auto' or a Mesh; got {self.mesh!r}")

    def _fit_mesh(self, folds: FoldData) -> Optional[Mesh]:
        """The default mesh: none while the one-device sweep fits what
        device 0 has free, else the ``'auto'`` mesh over every local
        device."""
        devices = jax.local_devices()
        free = shardlib.device_bytes_free(devices[0])
        if len(devices) == 1 or free is None:
            return None
        k, n_f, h = folds.x_folds.shape
        need = shardlib.sweep_bytes(k, getattr(self.strategy, "g", 1), h,
                                    k * n_f, folds.fold_hess.dtype.itemsize)
        return None if need <= free else shardlib.make_cv_mesh(k, devices)

    def _state_division(self, mesh: Optional[Mesh]) -> int:
        """Devices each λ row divides the state stage over: the λ axis
        when the strategy can divide its state, else 1 (each device runs
        the whole state stage of its folds)."""
        if mesh is None or not getattr(self.strategy, "divisible_state",
                                       False):
            return 1
        return mesh.shape[shardlib.CV_LAM_AXIS]

    def _by_pair(self, mesh: Optional[Mesh]) -> bool:
        """Whether the state stage is handed, on each device, only the
        train Hessians of its own pairs' folds (:meth:`_pair_hessians`):
        where the state stage is divided and the λ stage reads no train
        Hessian (the policy does not refine).  Otherwise every device of
        a λ row is handed its row's folds whole."""
        return (self._state_division(mesh) > 1
                and not self._prec.refine_iters)

    def _pair_folds(self, mesh: Mesh, k: int, h: int) -> np.ndarray:
        """(devices, f): the folds whose train Hessians block ``r·n + j``
        of :meth:`_pair_hessians` holds, for device (r, j) of the mesh —
        fold row r's folds r·k/rows … that device j's pairs read
        (``PairLayout.device_folds``)."""
        rows = mesh.shape[shardlib.CV_FOLD_AXIS]
        n = mesh.shape[shardlib.CV_LAM_AXIS]
        local = self.strategy.pair_layout(k // rows, n, h).device_folds()
        return (np.arange(rows)[:, None, None] * (k // rows)
                + local).reshape(rows * n, -1)

    def _pair_hessians(self, mesh: Mesh, folds: FoldData) -> jax.Array:
        """The train Hessians of the divided state stage, each device
        handed only the folds its pairs read (:meth:`_pair_folds`):
        (devices, f, h, h) under ``P((folds, lams))``.  Each device's
        block is made where the designs are and put on its device alone,
        so no device receives a fold it does not read."""
        h = folds.fold_hess.shape[-1]
        of_block = self._pair_folds(mesh, folds.fold_hess.shape[0], h)
        sharding = NamedSharding(mesh, P((shardlib.CV_FOLD_AXIS,
                                          shardlib.CV_LAM_AXIS)))
        shape = of_block.shape + (h, h)
        blocks = [
            jax.device_put(self._split_folds(folds.hess, folds.fold_hess,
                                             of_block[idx[0]]), device)
            for device, idx in
            sharding.addressable_devices_indices_map(shape).items()]
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        blocks)

    def _state_hessians(self, mesh: Optional[Mesh], folds: FoldData,
                        h_tr: jax.Array) -> jax.Array:
        """The train Hessians a state stage over ``mesh`` is handed: the
        pairs' folds (:meth:`_by_pair`), else ``h_tr``."""
        return self._pair_hessians(mesh, folds) if self._by_pair(mesh) \
            else h_tr

    def _shard_counts(self, mesh: Optional[Mesh], folds: FoldData,
                      q_loc: int, ran: bool, fused: bool) -> dict:
        """``extras['engine']['shard']``: the devices, the factorizations
        one device ran for the problem, the bytes it received in the
        anchor exchange, how the state stage's inputs were placed and
        their bytes on the devices other than the designs' (``ran``
        False: the state came from the cache; ``fused``: the state stage
        ran in the fused sweep, which is handed the hold-out rows too)."""
        k, h = folds.fold_hess.shape[0], folds.fold_hess.shape[-1]
        devices = 1 if mesh is None else mesh.devices.size
        k_loc = k if mesh is None else k // mesh.shape[shardlib.CV_FOLD_AXIS]
        n_div = self._state_division(mesh)
        by_pair = self._by_pair(mesh)
        counts = dict(devices=devices, pairs_per_device=0, exchange_bytes=0,
                      inputs="by_pair" if by_pair else "replicated",
                      placed_bytes=0)
        if not ran:
            return counts
        itemsize = folds.fold_hess.dtype.itemsize
        strat = self.strategy
        if n_div == 1:
            counts["pairs_per_device"] = strat.n_exact_chol(k_loc, q_loc)
        else:
            lay = strat.pair_layout(k_loc, n_div, h)
            theta_itemsize = np.dtype(
                self._prec.store_dtype(folds.fold_hess.dtype)).itemsize
            counts.update(pairs_per_device=lay.per_device,
                          exchange_bytes=lay.exchange_bytes(
                              strat.degree, itemsize, theta_itemsize))
        # a device's block of each input, in elements: the train Hessians
        # (its pairs' folds, or its row's) and gradients, and in the fused
        # sweep (unless by pair, where the designs' device scores) the
        # hold-out rows and targets
        n_f = folds.x_folds.shape[1]
        hess = self._pair_folds(mesh, k, h).shape[1] if by_pair else k_loc
        block = hess * h * h + k_loc * h
        if fused and not by_pair:
            block += k_loc * n_f * (h + 1)
        counts["placed_bytes"] = (devices - 1) * block * itemsize
        return counts

    @staticmethod
    def _check_fold_axis(mesh: Optional[Mesh], k: int) -> None:
        """Fail with the engine's error, not a shard_map internal one, when
        the fold count does not tile the mesh's fold axis (folds cannot be
        padded — the count is fixed by the problem)."""
        if mesh is None:
            return
        n_fold = mesh.shape[shardlib.CV_FOLD_AXIS]
        if k % n_fold:
            raise ValueError(
                f"{k} folds not divisible by mesh axis "
                f"{shardlib.CV_FOLD_AXIS}={n_fold}")

    # -- λ-grid validation -------------------------------------------------

    @staticmethod
    def _check_lams(lams, min_q: int = 1, what: str = "sweep") -> jax.Array:
        """Validate a λ grid at the engine's entry points.

        Degenerate grids used to die deep inside the machinery with opaque
        shape errors (``q=0`` in ``pad_to_multiple``/``reshape``, an
        ``IndexError`` on an empty chunk stream) — fail here instead, with
        a message naming the actual problem.  ``q=1`` is legal for a sweep
        (one λ, trivially) but not for :meth:`search` (``min_q=2`` — a
        bracketing search needs a range).
        """
        lams = jnp.asarray(lams)
        if lams.ndim != 1:
            raise ValueError(
                f"λ grid must be 1-D, got shape {tuple(lams.shape)}")
        q = int(lams.shape[0])
        if q == 0:
            raise ValueError(
                f"empty λ grid (q=0): the {what} needs at least "
                f"{min_q} candidate λ value(s)")
        if q < min_q:
            raise ValueError(
                f"λ grid has {q} value(s) but the {what} needs at least "
                f"{min_q} (a single λ defines no range to refine — "
                "use run() for a point evaluation)")
        return lams

    # -- λ chunking --------------------------------------------------------

    def _resolve_chunk(self, q_loc: int, h: int, dtype) -> Optional[int]:
        """Static chunk size for a (q_loc,) λ shard, or None (no streaming)."""
        if self.lam_chunk is None:
            return None
        if self.lam_chunk == "auto":
            return self._auto_chunk(q_loc, h, dtype)
        chunk = int(self.lam_chunk)
        if chunk <= 0:
            raise ValueError(f"lam_chunk must be positive, got {chunk}")
        return chunk

    def _auto_chunk(self, q_loc: int, h: int, dtype,
                    block: Optional[int] = None) -> int:
        """The ``lam_chunk='auto'`` rule, at most ``q_loc``: what the λ
        stage holds per λ, at the policy's *storage* dtype, decides it.
        Interpolant strategies ask their backend's ``interp_solve``; the
        others build a factor per λ, budgeted as packed rows.  The
        autotuner's chunk ladder centres on this same rule."""
        block = (block or getattr(self.strategy, "block", None)
                 or self.block or 128)
        store = self._prec.store_dtype(dtype)
        if isinstance(self.strategy, _InterpolantErrors):
            return self._bk.interp_lam_chunk(
                h, block, q_loc, store, degree=self.strategy.degree,
                budget=LAM_CHUNK_BUDGET_BYTES)
        return min(q_loc, shardlib.auto_lam_chunk(h, block, store,
                                                  LAM_CHUNK_BUDGET_BYTES))

    def _lam_chunk_used(self, q_loc: int, h: int, dtype) -> int:
        """λs per λ-stage call on a (q_loc,) shard: the counter
        ``lam_chunk_resolved`` of ``extras['engine']``."""
        chunk = self._resolve_chunk(q_loc, h, dtype)
        return q_loc if chunk is None else min(chunk, q_loc)

    def _stage_chunk(self, folds: FoldData, lams, mesh) -> int:
        """λs per dispatch of the staged sweep's chunk stage (whole grid
        when streaming is off), padded to the λ mesh axis."""
        q = int(lams.shape[0])
        chunk = self._resolve_chunk(q, folds.fold_hess.shape[-1],
                                    folds.fold_hess.dtype)
        if chunk is None or chunk > q:
            chunk = q
        if mesh is not None:
            chunk += (-chunk) % mesh.shape[shardlib.CV_LAM_AXIS]
        return chunk

    # -- roofline-guided autotuning ---------------------------------------
    #
    # tune='auto' inserts one step before the first sweep of a geometry:
    # the autotuner AOT-lowers the fused sweep for every point of the legal
    # (block × λ-chunk × mesh) lattice, scores the compiled HLO against the
    # roofline model, and the engine delegates the actual run to a DERIVED
    # engine carrying the winning configuration.  The derived engine is a
    # full CVEngine (same strategy math, same cache, tune=False) so every
    # path — run, the pipelined sweep, batched admission — works tuned
    # without per-path plumbing; it is memoized per chosen config so its
    # jit caches warm up exactly like an untuned engine's.

    def _apply_tuned(self, cfg) -> "CVEngine":
        """The derived engine that *runs* a tuned configuration: strategy
        packing block and Pallas kernel tiles re-sized to ``cfg.block``,
        λ-chunk pinned, mesh built from ``cfg.mesh_shape`` (reusing this
        engine's explicit mesh when the shape matches, so jit caches keyed
        on device identity survive).  Shares the factor cache and the
        precision policy; ``tune=False`` on the result is the recursion
        guard."""
        key = cfg.key()
        if key in self._tuned_engines:
            return self._tuned_engines[key]
        from .backends import retile_backend
        strat = self.strategy
        if dataclasses.is_dataclass(strat) and any(
                f.name == "block" for f in dataclasses.fields(strat)) \
                and strat.block != cfg.block:
            strat = dataclasses.replace(strat, block=cfg.block)
        bk = retile_backend(self._bk, chol_block=cfg.block,
                            trsm_block=cfg.block)
        if cfg.mesh_shape is None:
            mesh = None
        else:
            n_fold, n_lam = cfg.mesh_shape
            if isinstance(self.mesh, Mesh) and \
                    (self.mesh.shape.get(shardlib.CV_FOLD_AXIS),
                     self.mesh.shape.get(shardlib.CV_LAM_AXIS)) == \
                    (n_fold, n_lam):
                mesh = self.mesh
            else:
                dev = np.asarray(
                    jax.devices()[: n_fold * n_lam]).reshape(n_fold, n_lam)
                mesh = Mesh(dev, (shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS))
        derived = self._derive(strategy=strat, backend=bk, mesh=mesh,
                               block=cfg.block, lam_chunk=int(cfg.lam_chunk))
        self._tuned_engines[key] = derived
        return derived

    def _derive(self, **fields) -> "CVEngine":
        """An engine on this engine's resolved backend with ``fields``
        changed and every other field carried over (the factor cache and
        the tuning cache shared); ``tune=False``, since a derived engine
        runs one configuration and never tunes again."""
        return dataclasses.replace(
            self, **{"backend": self._bk, "tune": False, **fields})

    def _tuned_engine(self, folds: FoldData, lams):
        """(derived engine, chosen config) for this problem geometry —
        the tune dispatch shared by every public entry point."""
        from repro.distributed import autotune
        if isinstance(self.tune, autotune.TunedConfig):
            cfg = self.tune
        else:
            if self.tune_cache is None:
                self.tune_cache = autotune.TuningCache()
            cfg = autotune.tune(self, folds, jnp.asarray(lams),
                                cache=self.tune_cache,
                                **(self.tune_lattice or {}))
        return self._apply_tuned(cfg), cfg

    # -- sweep construction ----------------------------------------------
    #
    # Every program is a composition of two per-device bodies, the state
    # stage (_state_body) and the λ stage (_lam_body, or _lam_solutions
    # where the designs' device scores), put onto the mesh by _on_mesh and
    # kept in one memo (_program).  The fused sweep runs both in one
    # program; with a cache or staged, the state program (_state_fn) runs
    # apart and the λ program (_replay_fn) replays any grid or chunk from
    # its state.

    @staticmethod
    def _mesh_key(mesh: Optional[Mesh]):
        return None if mesh is None else (tuple(mesh.shape.items()),
                                          tuple(map(id, mesh.devices.flat)))

    def _program(self, key: tuple, build: Callable) -> Callable:
        """The jitted program of ``key`` (its stage, mesh key and flags),
        built by ``build()`` on first use."""
        if key not in self._programs:
            self._programs[key] = build()
        return self._programs[key]

    @staticmethod
    def _on_mesh(body: Callable, mesh: Optional[Mesh], in_specs,
                 out_specs) -> Callable:
        """``body`` itself without a mesh, else its ``shard_map`` over
        ``mesh``."""
        if mesh is None:
            return body
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _stream_errors(self, errors_at, lams, g_tr):
        """Stream ``errors_at`` over the local λ shard in ``lam_chunk``-sized
        chunks under a sequential ``lax.map`` — only one chunk's
        interpolants/factors are live at a time, so peak memory is
        O(chunk · P) however dense the grid.  Composes with the
        folds × lams ``shard_map``: chunking happens per device on the
        local λ shard, whose (k_loc, h) train gradients ``g_tr`` give the
        fold count, h and dtype.  Every λ stage streams here, the divided
        sweep's solutions (``(k_loc, chunk, h)`` a chunk) too, so the
        memory contract has one implementation.
        """
        k_loc, h = g_tr.shape
        q_loc = lams.shape[0]
        chunk = self._lam_chunk_used(q_loc, h, g_tr.dtype)
        with tracing.scope(tracing.LAM_STAGE):
            if chunk == q_loc:
                return errors_at(lams)
            chunks, _ = shardlib.chunk_lams(lams, chunk)  # (n_c, chunk)
            errs = jax.lax.map(errors_at, chunks)     # (n_c, k_loc, chunk, …)
            return jnp.moveaxis(errs, 1, 0).reshape(
                k_loc, -1, *errs.shape[3:])[:, :q_loc]

    def _state_body(self, n_div: int, with_anchors: bool, by_pair: bool):
        """The per-device state stage ``(f_idx, h, g_tr, aux) -> (batched
        state, packed anchors)``: each fold's ``fold_state`` (or
        ``fold_state_and_anchors``) under ``vmap``, or where ``n_div`` > 1
        the folds' ``divided_state`` over the ``n_div`` devices of the λ
        axis — ``h`` then this device's block of :meth:`_pair_hessians`
        (``by_pair``), or its row's train Hessians, of which it takes its
        pairs' folds.  Without ``with_anchors`` the anchors are an empty
        row per fold."""
        strat, bk = self.strategy, self._bk
        lam_ax = shardlib.CV_LAM_AXIS

        def body(f_idx, h, g_tr, aux):
            if n_div > 1:
                k = g_tr.shape[0]
                if by_pair:
                    h_loc = h[0]
                else:
                    rows = strat.pair_layout(k, n_div,
                                             h.shape[-1]).device_folds()
                    h_loc = h[jnp.asarray(rows)[jax.lax.axis_index(lam_ax)]]
                state, vec = strat.divided_state(h_loc, k, aux, bk, n_div,
                                                 lam_ax)
                return state, (vec if with_anchors
                               else jnp.zeros((k, 0), h.dtype))

            def one(f, h_f, g_f):
                if with_anchors:
                    return strat.fold_state_and_anchors(f, h_f, g_f, aux, bk)
                return (strat.fold_state(f, h_f, g_f, aux, bk),
                        jnp.zeros((0,), h_f.dtype))
            return jax.vmap(one)(f_idx, h, g_tr)

        return body

    def _lam_body(self, state, f_idx, h_tr, g_tr, x_folds, y_folds, lams,
                  aux):
        """The per-device λ stage: each fold's ``fold_errors`` on the
        device's λ shard, (k_loc, q_loc) hold-out errors."""
        strat, bk = self.strategy, self._bk

        def errors_at(lams_c):
            return jax.vmap(
                lambda st, f, h, g, x, y: strat.fold_errors(
                    st, f, h, g, x, y, lams_c, aux, bk)
            )(state, f_idx, h_tr, g_tr, x_folds, y_folds)

        return self._stream_errors(errors_at, lams, g_tr)

    def _lam_solutions(self, state, g_tr, lams):
        """The λ stage unscored: each fold's θ(λ) on the device's λ shard,
        (k_loc, q_loc, h)."""
        strat, bk = self.strategy, self._bk

        def thetas_at(lams_c):
            return jax.vmap(lambda st, g: strat.solutions(st, g, lams_c, bk)
                            )(state, g_tr)

        return self._stream_errors(thetas_at, lams, g_tr)

    def _sweep_fn(self, mesh: Optional[Mesh]):
        """Jitted fused sweep ``(h, g_tr, x_folds, y_folds, lams) -> (k, q)``
        errors over ``mesh``: the state and the λ stage in one program.
        Where each device is handed its pairs' folds (:meth:`_by_pair`;
        ``h`` is then :meth:`_pair_hessians`), the by-pair program
        (:meth:`_pair_sweep_fn`) and the designs' device's scoring
        (:meth:`_score_fn`)."""
        def build():
            if self._by_pair(mesh):
                solve, score = self._pair_sweep_fn(mesh), self._score_fn()

                def scored(h_pairs, g_tr, x_folds, y_folds, lams):
                    thetas = solve(h_pairs, g_tr, lams)
                    # θ to the hold-out rows (a jit handed both a mesh
                    # array and a one-device array would put the rows on
                    # the mesh)
                    if len(x_folds.devices()) == 1:
                        thetas = jax.device_put(thetas, *x_folds.devices())
                    return score(thetas, x_folds, y_folds)

                return scored

            strat, bk = self.strategy, self._bk
            state_of = self._state_body(self._state_division(mesh), False,
                                        False)
            fold_ax, lam_ax = shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS

            def body(h_tr, g_tr, x_folds, y_folds, f_idx, lams, aux):
                state, _ = state_of(f_idx, h_tr, g_tr, aux)
                return self._lam_body(state, f_idx, h_tr, g_tr, x_folds,
                                      y_folds, lams, aux)

            def sweep(h_tr, g_tr, x_folds, y_folds, lams):
                f_idx = jnp.arange(h_tr.shape[0])
                aux = strat.prepare(x_folds, y_folds, h_tr, g_tr, lams, bk)
                return self._on_mesh(
                    body, mesh, (P(fold_ax),) * 5 + (P(lam_ax), _repl(aux)),
                    P(fold_ax, lam_ax),
                )(h_tr, g_tr, x_folds, y_folds, f_idx, lams, aux)

            return _jit(sweep, donate_argnums=(0, 1) if self.donate else ())

        return self._program(("sweep", self._mesh_key(mesh)), build)

    def _pair_sweep_fn(self, mesh: Mesh):
        """Jitted ``(pair Hessians, g_tr, lams) -> θ (k, q, h)`` over
        ``mesh``: the divided state stage handed each device only its
        pairs' folds (:meth:`_pair_hessians`), then the λ stage unscored;
        no hold-out row reaches it."""
        def build():
            strat, bk = self.strategy, self._bk
            state_of = self._state_body(self._state_division(mesh), False,
                                        True)
            fold_ax, lam_ax = shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS

            def body(h_pairs, g_tr, lams, aux):
                state, _ = state_of(None, h_pairs, g_tr, aux)
                return self._lam_solutions(state, g_tr, lams)

            def solve(h_pairs, g_tr, lams):
                aux = strat.prepare(None, None, None, g_tr, lams, bk)
                return self._on_mesh(
                    body, mesh, (P((fold_ax, lam_ax)), P(fold_ax), P(lam_ax),
                                 _repl(aux)),
                    P(fold_ax, lam_ax),
                )(h_pairs, g_tr, lams, aux)

            return _jit(solve, donate_argnums=(0,) if self.donate else ())

        return self._program(("pairs", self._mesh_key(mesh)), build)

    def _score_fn(self):
        """Jitted ``(θ (k, q, h), x_folds, y_folds) -> (k, q)`` hold-out
        errors, on the device that holds the hold-out rows."""
        return self._program(("score",), lambda: _jit(_fold_scores))

    def _lower_sweep(self, mesh: Optional[Mesh], h_tr, g_tr, x_folds,
                     y_folds, lams):
        """The program over ``mesh`` that runs the sweep's state stage,
        lowered for these arrays or shapes: the divided program handed
        each device its pairs' folds (:meth:`_by_pair`; the designs'
        device's scoring is a program of its own), else the fused
        sweep."""
        if not self._by_pair(mesh):
            return self._sweep_fn(mesh).lower(h_tr, g_tr, x_folds, y_folds,
                                              lams)
        h = h_tr.shape[-1]
        pairs = jax.ShapeDtypeStruct(
            self._pair_folds(mesh, h_tr.shape[0], h).shape + (h, h),
            h_tr.dtype, sharding=NamedSharding(mesh, P((
                shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS))))
        return self._pair_sweep_fn(mesh).lower(pairs, g_tr, lams)

    def _state_fn(self, mesh: Optional[Mesh], with_anchors: bool,
                  donate: bool = False):
        """Jitted state stage ``(f_idx, h, g_tr, aux) -> (state, packed
        anchors)`` over ``mesh``, the state fold-sharded; ``h`` is
        :meth:`_state_hessians`.  Divided, each device returns its own
        pairs' anchors, which are put back in (fold, anchor) order here.
        ``donate`` donates ``h`` (the staged sweep's one-fold slices)."""
        def build():
            n_div, by_pair = self._state_division(mesh), self._by_pair(mesh)
            body = self._state_body(n_div, with_anchors, by_pair)
            fold_ax, lam_ax = shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS
            pair_anchors = with_anchors and n_div > 1

            def state_stage(f_idx, h, g_tr, aux):
                state, avec = self._on_mesh(
                    body, mesh,
                    (P(fold_ax), P((fold_ax, lam_ax)) if by_pair
                     else P(fold_ax), P(fold_ax), _repl(aux)),
                    (P(fold_ax), P((fold_ax, lam_ax)) if pair_anchors
                     else P(fold_ax)),
                )(f_idx, h, g_tr, aux)
                if pair_anchors:   # (fold rows, λ row's padded pairs, P)
                    k, g = g_tr.shape[0], self.strategy.g
                    rows = mesh.shape[fold_ax]
                    avec = avec.reshape(rows, -1, avec.shape[-1])[
                        :, :k // rows * g].reshape(k, g, -1)
                return state, avec

            return _jit(state_stage, donate_argnums=(1,) if donate else ())

        return self._program(
            ("state", self._mesh_key(mesh), with_anchors, donate), build)

    def _replay_fn(self, mesh: Optional[Mesh]):
        """Jitted λ stage from a batched state, ``(state, h_tr, g_tr,
        x_folds, y_folds, lams, aux=()) -> (k, q)`` errors over ``mesh``:
        the cached path's replay of the whole grid (``aux=()``: a
        cacheable strategy's ``fold_errors`` never reads it, the
        ``cache_meta`` contract) and the staged sweep's chunk stage."""
        def build():
            fold_ax, lam_ax = shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS

            def replay(state, h_tr, g_tr, x_folds, y_folds, lams, aux=()):
                return self._on_mesh(
                    self._lam_body, mesh,
                    (shardlib.cv_state_specs(state),) + (P(fold_ax),) * 5
                    + (P(lam_ax), _repl(aux)),
                    P(fold_ax, lam_ax),
                )(state, jnp.arange(g_tr.shape[0]), h_tr, g_tr, x_folds,
                  y_folds, lams, aux)

            return _jit(replay)

        return self._program(("replay", self._mesh_key(mesh)), build)

    def _prepare_fn(self):
        """Jitted ``prepare`` of the state stages that run apart from the
        λ stage."""
        strat, bk = self.strategy, self._bk
        return self._program(("prepare",), lambda: _jit(
            lambda h_tr, g_tr, x, y, lams: strat.prepare(
                x, y, h_tr, g_tr, lams, bk)))

    def _refit_from_anchors(self, pf: packing.PackedFactor, meta: dict):
        """Θ from cached packed anchor factors — a batched GEMM least-
        squares per fold, zero factorizations (the anchor-hit path)."""
        strat, bk = self.strategy, self._bk
        anchors = jnp.asarray(meta["anchors"])

        def one(vec_f):
            pf_f = packing.PackedFactor(vec=vec_f, h=pf.h, block=pf.block)
            return picholesky.fit(None, anchors, strat.degree,
                                  block=strat.block, basis=strat.basis,
                                  factors=pf_f, backend=bk)

        return _jit(jax.vmap(one))(jnp.asarray(pf.vec))

    # -- the state of the cached and staged sweeps ------------------------
    #
    # run() with a cache, sweep_async and search acquire their fitted state
    # one way (_acquire_state): fingerprint, then a hit, an anchor refit or
    # a cold state stage that populates the cache; then they stream λ
    # through the replay.  The staged sweep on one device dispatches its
    # cold state stage per fold (bounded by a depth-2 StageRing, so at most
    # two donated Hessian slices are in flight) and streams λ chunk by
    # chunk, each completed chunk a partial hold-out curve the early-stop
    # search can act on.  `pipelined=False` runs the *same* jitted programs
    # with a block after every dispatch — the serial reference the parity
    # tests compare bit-for-bit against.

    @contextlib.contextmanager
    def _stage_scope(self, label: str):
        """The host span ``cv.<label>`` around a staged dispatch, and the
        counting scope of stage-granular backends (CountingBackend)."""
        stage = getattr(self._bk, "stage", None)
        counting = (stage(label) if callable(stage)
                    else contextlib.nullcontext())
        with tracing.span(f"cv.{label}"), counting:
            yield

    def _cold_state(self, mesh, folds: FoldData, h_tr, g_tr, lams,
                    with_anchors: bool, pipelined: Optional[bool]):
        """The state stage run afresh: ``prepare``, then the state program
        over ``mesh`` in one dispatch.  The staged sweep (``pipelined``
        not None) on one device dispatches it per fold instead, on
        one-fold slices of the train Hessians (engine-owned copies,
        donated where the strategy reads them), through a depth-2
        :class:`~repro.distributed.sharding.StageRing`: fold f+1's
        factorizations sit in the device queue while fold f's run.
        ``pipelined=False`` blocks after every dispatch.  Returns
        ``(batched state, packed anchors | None, aux)``."""
        strat = self.strategy
        serial = pipelined is False
        with self._stage_scope("prepare"):
            aux = self._prepare_fn()(h_tr, g_tr, folds.x_folds,
                                     folds.y_folds, lams)
        if serial:
            jax.block_until_ready(aux)
        k = g_tr.shape[0]
        with self._stage_scope("fold_state"):
            if mesh is not None or pipelined is None:
                out = self._state_fn(mesh, with_anchors)(
                    jnp.arange(k), self._state_hessians(mesh, folds, h_tr),
                    g_tr, aux)
                if serial:
                    jax.block_until_ready(out)
            else:
                fn = self._state_fn(None, with_anchors, donate=bool(
                    self.donate and getattr(strat, "state_uses_hessian",
                                            False)))
                ring = shardlib.StageRing(depth=2)
                outs = []
                for f in range(k):
                    staged = fn(jnp.arange(f, f + 1), h_tr[f:f + 1],
                                g_tr[f:f + 1], aux)
                    outs.append(ring.admit(staged))
                    if serial:
                        jax.block_until_ready(staged)
                out = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
        state, avec = out
        pf = (packing.PackedFactor(vec=avec, h=h_tr.shape[-1],
                                   block=strat.block)
              if with_anchors else None)
        return state, pf, aux

    def _cache_meta(self, lams) -> Optional[dict]:
        """The strategy's ``cache_meta`` where a cache is attached, else
        None (the sweep does not go through the cache)."""
        if self.cache is None or not hasattr(self.strategy, "cache_meta"):
            return None
        return self.strategy.cache_meta(lams)

    def _cache_key(self, h_tr, meta: dict) -> cachelib.CacheKey:
        """The cache key of a sweep's λ-independent inputs, fingerprinted
        (and its bytes counted) by the attached cache."""
        return self.cache.fingerprint(
            h_tr, meta["anchors"], block=meta["params"]["block"],
            backend=self._bk.name, params=meta["params"],
            precision=self._prec.descriptor(),
            sketch=meta.get("sketch", "exact"))

    def _acquire_state(self, meta: Optional[dict], mesh, folds: FoldData,
                       h_tr, g_tr, lams,
                       pipelined: Optional[bool] = None):
        """The sweep's fitted state, acquired one way by :meth:`run` with
        a cache, :meth:`sweep_async` and :meth:`search`.  With ``meta``
        (:meth:`_cache_meta`): the fingerprint, then a hit, an anchor
        refit, or the cold state stage (:meth:`_cold_state`), which
        populates the cache; without it, the cold state stage alone.
        Returns ``(batched state, aux, status, cache info)``: ``aux`` is
        ``()`` for a cached state, ``status`` ``hit`` / ``refit`` /
        ``miss``, ``bypass`` (a cache, but no ``meta``) or None (no
        cache)."""
        if meta is None:
            state, _, aux = self._cold_state(mesh, folds, h_tr, g_tr, lams,
                                             False, pipelined)
            if self.cache is None:
                return state, aux, None, None
            return state, aux, "bypass", dict(status="bypass")
        strat, cache = self.strategy, self.cache
        key = self._cache_key(h_tr, meta)
        if self.reuse:
            entry = cache.lookup(key, self.reuse)
        else:
            entry = None
            cache.misses += 1     # write-only runs are misses by definition
        status = "hit"
        if entry is None:
            with_anchors = (self.cache_anchors
                            and hasattr(strat, "fold_state_and_anchors"))
            pf = (cache.get_anchors(key)
                  if self.reuse and with_anchors else None)
            if pf is not None:
                # same anchor factors, different polynomial: refit Θ from
                # the cached packed targets — still zero factorizations
                state, status = self._refit_from_anchors(pf, meta), "refit"
            else:
                state, pf, _ = self._cold_state(mesh, folds, h_tr, g_tr,
                                                lams, with_anchors, pipelined)
                status = "miss"
            entry = cache.put(key, state, pf)
        # digest of the entry actually SERVED (≠ the requested key's under
        # a covering hit), so results are attributable to their Θ; the
        # replay streams with aux=() (the cache_meta contract)
        info = dict(status=status, digest=entry.key.digest()[:12],
                    policy=self.reuse, **cache.stats)
        return entry.state, (), status, info

    def sweep_async(self, folds: FoldData, lams: jax.Array, *,
                    stop_tol: Optional[float] = None, stop_patience: int = 2,
                    pipelined: bool = True) -> Iterator[SweepChunk]:
        """Pipelined staged sweep — yields a :class:`SweepChunk` per λ chunk.

        Parameters
        ----------
        stop_tol:      ``None`` disables early stopping.  A float ≥ 0
                       enables the early-stop λ-search: a chunk *improves*
                       when its minimum mean error drops below
                       ``best · (1 − stop_tol)``; after ``stop_patience``
                       consecutive non-improving chunks the stream stops.
                       ``stop_tol=0`` stops only on strict non-improvement,
                       so on a unimodal hold-out curve the returned minimum
                       is exactly the full grid's argmin.  A chunk whose
                       mean hold-out error is non-finite (singular fold,
                       bf16 overflow) raises ``FloatingPointError`` — the
                       search refuses to rank errors it cannot compare
                       rather than silently counting the chunk as
                       non-improving and "stopping" on a ``nan`` λ*.
        stop_patience: consecutive non-improving chunks tolerated before
                       stopping (default 2).
        pipelined:     ``True`` dispatches stages without blocking — the
                       device queue overlaps fold f+1's factorizations with
                       fold f's chunk streaming, and full sweeps keep one
                       chunk of dispatch lookahead.  ``False`` blocks after
                       every stage (the serial reference).  Both orders run
                       the *same* jitted stage functions on the same
                       inputs, so their error curves are **bit-for-bit
                       identical** — pipelining reorders dispatch, never
                       math.

        Composes with the warm-replay cache exactly like :meth:`run`: a hit
        skips the state stage and streams the cached Θ through the chunk
        stage; a miss runs the cold stage and populates the cache *before*
        the λ stream starts, so an early-stopped sweep still leaves a
        complete, replayable entry (the fit is λ-grid independent — only
        the curve evaluation is truncated).
        """
        self._check_stop(stop_tol, stop_patience)
        if self.tune:
            derived, _ = self._tuned_engine(folds, lams)
            yield from derived.sweep_async(
                folds, lams, stop_tol=stop_tol, stop_patience=stop_patience,
                pipelined=pipelined)
            return
        lams = self._check_lams(lams)
        mesh = self._resolve_mesh(folds)
        yield from self._stream(folds, lams, mesh,
                                self._stage_chunk(folds, lams, mesh),
                                stop_tol, stop_patience, pipelined)

    @staticmethod
    def _check_stop(stop_tol: Optional[float], stop_patience: int) -> None:
        """Refuse early-stop arguments the staged sweep cannot act on."""
        if stop_tol is not None and stop_tol < 0:
            raise ValueError(f"stop_tol must be >= 0 or None, got {stop_tol}")
        if stop_patience < 1:
            raise ValueError(
                f"stop_patience must be >= 1, got {stop_patience}")

    def _stream(self, folds: FoldData, lams, mesh: Optional[Mesh],
                chunk: int, stop_tol: Optional[float], stop_patience: int,
                pipelined: bool) -> Iterator[SweepChunk]:
        """The staged sweep over ``mesh`` in chunks of ``chunk`` λs, both
        resolved once by the caller (:meth:`sweep_async`,
        :meth:`run_async`): the state stage, then the λ-chunk stream."""
        lams_np = np.asarray(lams)
        k = folds.fold_hess.shape[0]
        q = int(lams.shape[0])
        self._check_fold_axis(mesh, k)
        h_tr, g_tr = self._split(folds.hess, folds.grad,
                                 folds.fold_hess, folds.fold_grad)
        strat = self.strategy

        # fixed-size chunk schedule (last chunk edge-padded) so one jitted
        # chunk stage serves the whole stream
        chunks, _ = shardlib.chunk_lams(lams, chunk)
        n_c = chunks.shape[0]

        # ---- state stage (cache dispatch identical to run()) ------------
        state, aux, status, cache_info = self._acquire_state(
            self._cache_meta(lams), mesh, folds, h_tr, g_tr, lams, pipelined)
        warm = status in ("hit", "refit")

        # ---- λ-chunk stream ---------------------------------------------
        replay = self._replay_fn(mesh)

        def dispatch(c):
            with self._stage_scope("fold_errors"):
                return replay(state, h_tr, g_tr, folds.x_folds,
                              folds.y_folds, chunks[c], aux)

        # full pipelined sweeps keep one chunk of dispatch lookahead; the
        # early-stop search dispatches chunk-by-chunk (the decision is the
        # sync point), and the serial reference blocks on every stage
        lookahead = pipelined and stop_tol is None
        best = np.inf
        best_lam = float("nan")
        streak = 0
        n_eval = 0
        nxt = dispatch(0) if lookahead else None
        for c in range(n_c):
            e = nxt if nxt is not None else dispatch(c)
            nxt = dispatch(c + 1) if lookahead and c + 1 < n_c else None
            if not pipelined:
                jax.block_until_ready(e)
            width = min(chunk, q - c * chunk)
            with tracing.span("cv.fetch"):
                fold_errs = np.asarray(e)[:, :width]  # syncs this chunk only
            mean = fold_errs.mean(0)
            finite = np.isfinite(mean)
            if not finite.all() and stop_tol is not None:
                # `mean[i] < best` is False for NaN, so a non-finite chunk
                # (singular fold, bf16 overflow) would silently feed the
                # non-improvement streak and the search could "stop" on a
                # curve it never actually ranked — refuse instead
                bad = lams_np[c * chunk + np.flatnonzero(~finite)]
                raise FloatingPointError(
                    f"non-finite hold-out mean at λ={bad[:4].tolist()} "
                    f"(chunk {c}): the early-stop search cannot rank "
                    "non-finite errors; fix the fold/precision (singular "
                    "fold? bf16 overflow → 'bf16_refined') or sweep the "
                    "full grid with stop_tol=None")
            n_eval += width
            if finite.any():
                # argmin over the FINITE entries only — np.argmin would
                # return the first NaN's index and poison best/best_lam
                i = int(np.flatnonzero(finite)[np.argmin(mean[finite])])
                improved = (bool(mean[i] < best * (1.0 - stop_tol))
                            if stop_tol is not None and np.isfinite(best)
                            else bool(mean[i] < best))
                if mean[i] < best:   # strict: ties keep the earlier λ,
                    best = float(mean[i])  # matching argmin on the full curve
                    best_lam = float(lams_np[c * chunk + i])
            else:
                improved = False    # an all-non-finite chunk never improves
            streak = 0 if improved else streak + 1
            stopped = (stop_tol is not None and streak >= stop_patience
                       and c + 1 < n_c)
            yield SweepChunk(
                index=c, start=c * chunk, n_chunks=n_c,
                lams=lams_np[c * chunk: c * chunk + width],
                fold_errors=fold_errs, errors=mean,
                best_lam=best_lam, best_error=float(best),
                stopped=stopped,
                n_exact_chol=0 if warm else strat.n_exact_chol(k, n_eval),
                cache=cache_info)
            if stopped:
                return
        if not np.isfinite(best):
            # the FINISHED stream ranked no finite λ (every chunk's mean
            # was NaN/inf — e.g. a singular fold poisons every λ).  With
            # early stopping this already raised mid-stream; without it the
            # old behavior was to silently yield best_lam=nan.  Refuse the
            # same way: the consumer has seen every partial curve by now,
            # but the sweep as a whole produced nothing rankable.
            raise FloatingPointError(
                "sweep finished with no finite hold-out mean at any λ "
                "(singular fold? overflow → try precision='bf16_refined' "
                "or fp64); refusing to report a nan λ* selection")

    def run_async(self, folds: FoldData, lams: jax.Array, *,
                  stop_tol: Optional[float] = None, stop_patience: int = 2,
                  pipelined: bool = True) -> CVResult:
        """Consume :meth:`sweep_async` into a :class:`CVResult`.

        With early stopping the result covers the evaluated prefix of the
        grid (``extras['engine']['async']`` records how far the stream ran
        and whether it stopped); without it this is the staged equivalent
        of :meth:`run`.
        """
        self._check_stop(stop_tol, stop_patience)
        if self.tune:
            derived, cfg = self._tuned_engine(folds, lams)
            res = derived.run_async(folds, lams, stop_tol=stop_tol,
                                    stop_patience=stop_patience,
                                    pipelined=pipelined)
            res.extras["engine"]["tune"] = cfg.to_json()
            return res
        lams = self._check_lams(lams)
        # the mesh and chunk the stream runs on are the ones reported
        mesh = self._resolve_mesh(folds)
        chunk = self._stage_chunk(folds, lams, mesh)
        parts = list(self._stream(folds, lams, mesh, chunk, stop_tol,
                                  stop_patience, pipelined))
        last = parts[-1]
        errors = np.concatenate([p.errors for p in parts])
        lams_eval = np.concatenate([p.lams for p in parts])
        n_lam = 1 if mesh is None else mesh.shape[shardlib.CV_LAM_AXIS]
        meta = self._result_meta(
            mesh, chunk // n_lam, last.cache,
            shard=self._shard_counts(mesh, folds, chunk // n_lam,
                                     last.n_exact_chol > 0, fused=False))
        meta["async"] = dict(
            pipelined=pipelined, stop_tol=stop_tol,
            stop_patience=stop_patience, stopped=last.stopped,
            chunks_evaluated=len(parts), chunks_total=last.n_chunks,
            lams_evaluated=int(errors.shape[0]))
        return CVResult.from_errors(lams_eval, errors, last.n_exact_chol,
                                    engine=meta)

    # -- adaptive λ-search -------------------------------------------------
    #
    # The dense grid spends one interp_solve per grid point whether or not
    # the point is informative; the search spends them where the hold-out
    # minimum actually is.  It reuses the staged sweep's machinery whole:
    # the state stage (cache dispatch included) runs ONCE over the grid's
    # λ range, then fixed-width refinement waves stream through the same
    # jitted chunk stage `sweep_async` uses — every wave has the same shape,
    # so the whole search compiles exactly one chunk signature, no matter
    # how many refinement levels it takes.

    def search(self, folds: FoldData, lams: jax.Array, *,
               wave: Optional[int] = None, tol_decades: float = 0.05,
               plateau_tol: Optional[float] = None,
               plateau_patience: int = 2, max_waves: int = 32,
               select_interp: bool = False,
               pipelined: bool = True) -> CVResult:
        """Adaptive λ-refinement search over the grid's range.

        Drop-in for :meth:`run`: takes the same dense candidate grid, but
        only its *range* (and density, as the comparison baseline) matter —
        instead of evaluating all q points, the search covers [λ_min,
        λ_max] with one coarse log-spaced wave of ``wave`` points, then
        repeatedly places ``wave`` new points strictly inside the bracket
        formed by the evaluated neighbors of the running minimum
        (trisection generalized to a batched wave: each level shrinks the
        bracket by ≈ 2/(wave+1)).  On a unimodal hold-out curve the final
        bracket contains the dense grid's argmin, so the returned λ* agrees
        with it to within the bracket width.

        Parameters
        ----------
        wave:         λ points per dispatch wave (default: the engine's
                      resolved λ-chunk, capped to 8, floored at 3 — every
                      wave reuses one jitted chunk-stage signature).  With
                      a mesh, padded up to the λ-axis multiple.
        tol_decades:  stop when the bracket around the minimum is narrower
                      than this many log₁₀-decades (default 0.05).
        plateau_tol:  optional error-plateau stop: after
                      ``plateau_patience`` consecutive waves in which the
                      best error improved by less than
                      ``best · plateau_tol`` (relative), stop.  ``None``
                      (default) disables it — interval width terminates.
        max_waves:    hard cap on refinement waves.
        select_interp: run :meth:`select_interpolant` first and search with
                      the chosen (degree, basis) — on a warm anchor cache
                      the selection performs zero factorizations; the
                      choice is recorded under
                      ``extras['engine']['interp_selection']``.

        A wave whose mean hold-out error is non-finite at *every* point
        raises ``FloatingPointError`` (same refusal as the early-stop
        sweep); partially-finite waves rank the finite points only.

        Composes unchanged with the cache (the state stage is acquired
        exactly like :meth:`sweep_async`: hit → zero factorizations, anchor
        refit, or cold populate *before* any wave runs), precision
        policies, mesh sharding, and ``tune='auto'``.  Returns a
        :class:`CVResult` over every evaluated λ (sorted), with the search
        trace under ``extras['engine']['search']``.
        """
        if tol_decades <= 0:
            raise ValueError(f"tol_decades must be > 0, got {tol_decades}")
        if plateau_tol is not None and plateau_tol < 0:
            raise ValueError(
                f"plateau_tol must be >= 0 or None, got {plateau_tol}")
        if plateau_patience < 1:
            raise ValueError(
                f"plateau_patience must be >= 1, got {plateau_patience}")
        if max_waves < 1:
            raise ValueError(f"max_waves must be >= 1, got {max_waves}")
        if self.tune:
            derived, cfg = self._tuned_engine(folds, lams)
            res = derived.search(
                folds, lams, wave=wave, tol_decades=tol_decades,
                plateau_tol=plateau_tol, plateau_patience=plateau_patience,
                max_waves=max_waves, select_interp=select_interp,
                pipelined=pipelined)
            res.extras["engine"]["tune"] = cfg.to_json()
            return res
        if select_interp:
            sel = self.select_interpolant(folds, lams)
            eng = self.with_interpolant(sel["degree"], sel["basis"])
            res = eng.search(
                folds, lams, wave=wave, tol_decades=tol_decades,
                plateau_tol=plateau_tol, plateau_patience=plateau_patience,
                max_waves=max_waves, select_interp=False,
                pipelined=pipelined)
            res.extras["engine"]["interp_selection"] = sel
            return res
        lams = self._check_lams(lams, min_q=2, what="adaptive λ-search")
        lams_np = np.asarray(lams)
        if np.any(lams_np <= 0):
            raise ValueError("adaptive λ-search refines over log-λ: "
                             "every grid value must be positive")
        k = folds.fold_hess.shape[0]
        q = int(lams.shape[0])
        h = folds.fold_hess.shape[-1]
        mesh = self._resolve_mesh(folds)
        self._check_fold_axis(mesh, k)
        h_tr, g_tr = self._split(folds.hess, folds.grad,
                                 folds.fold_hess, folds.fold_grad)
        strat = self.strategy

        # a wave is one chunk-stage dispatch of at most 8 λs
        chunk = self._resolve_chunk(8, h, h_tr.dtype)
        if wave is None:
            w = max(3, min(8, chunk if chunk else 8))
        else:
            w = int(wave)
            if w < 3:
                raise ValueError(
                    f"wave must be >= 3 (a refinement wave needs interior "
                    f"points on both sides of the minimum), got {w}")
        if mesh is not None:
            w += (-w) % mesh.shape[shardlib.CV_LAM_AXIS]

        # state stage once, over the full λ range — identical cache
        # dispatch to sweep_async / run (hit → zero factorizations here)
        state, aux, status, cache_info = self._acquire_state(
            self._cache_meta(lams), mesh, folds, h_tr, g_tr, lams, pipelined)
        warm = status in ("hit", "refit")
        replay = self._replay_fn(mesh)
        dtype = lams.dtype

        def eval_wave(xs):
            """Mean hold-out error at 10**xs — one fixed-shape dispatch."""
            lam_w = np.asarray(10.0 ** xs, dtype=dtype)
            with self._stage_scope("fold_errors"):
                e = replay(state, h_tr, g_tr, folds.x_folds, folds.y_folds,
                           jnp.asarray(lam_w), aux)
            with tracing.span("cv.fetch"):
                return lam_w, np.asarray(e).mean(0)

        lo = float(np.log10(lams_np.min()))
        hi = float(np.log10(lams_np.max()))
        xs_all = np.empty(0)
        lams_all = np.empty(0, dtype=lams_np.dtype)
        errs_all = np.empty(0)
        best = np.inf
        best_x = lo
        waves = 0
        streak = 0
        width = hi - lo
        stopped_on = "max_waves"
        next_xs = np.linspace(lo, hi, w)    # coarse wave spans the range
        while True:
            lam_w, mean = eval_wave(next_xs)
            waves += 1
            finite = np.isfinite(mean)
            if not finite.any():
                raise FloatingPointError(
                    f"adaptive λ-search wave {waves} produced no finite "
                    f"hold-out mean (λ∈[{lam_w.min():.3g}, "
                    f"{lam_w.max():.3g}]): cannot rank the bracket "
                    "(singular fold? overflow → 'bf16_refined'/fp64)")
            xs_all = np.concatenate([xs_all, next_xs])
            lams_all = np.concatenate([lams_all, lam_w])
            errs_all = np.concatenate([errs_all, mean])
            prev_best = best
            j = int(np.flatnonzero(finite)[np.argmin(mean[finite])])
            if mean[j] < best:
                best = float(mean[j])
                best_x = float(next_xs[j])
            improved = (bool(best < prev_best * (1.0 - plateau_tol))
                        if plateau_tol is not None and np.isfinite(prev_best)
                        else bool(best < prev_best))
            streak = 0 if improved else streak + 1
            # bracket: the evaluated neighbors of the running minimum
            order = np.argsort(xs_all)
            xs_sorted = xs_all[order]
            pos = int(np.searchsorted(xs_sorted, best_x))
            left = xs_sorted[pos - 1] if pos > 0 else xs_sorted[0]
            right = (xs_sorted[pos + 1] if pos + 1 < xs_sorted.shape[0]
                     else xs_sorted[-1])
            width = float(right - left)
            if width <= tol_decades:
                stopped_on = "interval"
                break
            if plateau_tol is not None and streak >= plateau_patience:
                stopped_on = "plateau"
                break
            if waves >= max_waves:
                break
            # next wave: w points strictly inside the bracket (log-spaced;
            # the endpoints are already evaluated, so nothing repeats)
            next_xs = np.linspace(left, right, w + 2)[1:-1]

        order = np.argsort(xs_all)
        n_eval = int(xs_all.shape[0])
        n_chol = 0 if warm else strat.n_exact_chol(k, n_eval)
        w_loc = w // (1 if mesh is None else mesh.shape[shardlib.CV_LAM_AXIS])
        meta = self._result_meta(
            mesh, w_loc, cache_info,
            shard=self._shard_counts(mesh, folds, w_loc, not warm,
                                     fused=False))
        meta["search"] = dict(
            wave=w, waves=waves, lams_evaluated=n_eval, dense_q=q,
            evals_vs_grid=n_eval / q, tol_decades=tol_decades,
            plateau_tol=plateau_tol, plateau_patience=plateau_patience,
            interval_decades=width, stopped_on=stopped_on)
        return CVResult.from_errors(lams_all[order], errs_all[order],
                                    n_chol, engine=meta)

    # -- self-tuning interpolation ----------------------------------------

    def with_interpolant(self, degree: int, basis: str) -> "CVEngine":
        """Derived engine running this engine's piCholesky strategy at a
        different (degree, basis) — shares the cache, backend, precision
        and tuning cache, memoized per choice so its jit caches warm up
        like any engine's.  Same anchors ⇒ on a cache with
        ``cache_anchors`` the derived engine's first sweep refits Θ from
        the cached anchor targets with zero factorizations."""
        strat = self.strategy
        if not isinstance(strat, PiCholeskyStrategy):
            raise ValueError(
                "with_interpolant needs the picholesky strategy, got "
                f"{getattr(strat, 'name', strat)!r}")
        key = (int(degree), str(basis))
        if key == (strat.degree, strat.basis):
            return self
        if key not in self._interp_engines:
            self._interp_engines[key] = self._derive(
                strategy=dataclasses.replace(strat, degree=key[0],
                                             basis=key[1]))
        return self._interp_engines[key]

    def _anchor_targets_fn(self):
        """Jitted (k, g, P) anchor-factorize stage for interpolant
        selection: per fold, Cholesky at each anchor shift, tile-packed.
        The anchor Hessian goes through the strategy's ``anchor_hessian``
        hook, so sketched strategies select against the sketched targets
        the sweep will actually fit."""
        strat, bk = self.strategy, self._bk

        def targets(h_tr, anchors, x_folds):
            def per_fold(f, h_f):
                h_eff = strat.anchor_hessian(f, h_f, x_folds, bk)
                factors = picholesky.anchor_factors(h_eff, anchors,
                                                    bk.cholesky)
                return _packed_anchors(factors, strat.block, bk)
            return jax.vmap(per_fold)(jnp.arange(h_tr.shape[0]), h_tr)

        return self._program(("anchor_targets",), lambda: _jit(targets))

    def select_interpolant(self, folds: FoldData, lams: jax.Array, *,
                           degrees=None,
                           bases=("monomial", "centered")) -> dict:
        """Choose the interpolant (degree, basis) by leave-one-anchor-out
        CV against the packed anchor targets
        (:func:`~repro.core.picholesky.select_interpolant`).

        The anchor targets come from the factor cache when its anchor
        fingerprint matches (``cache_anchors=`` entries are degree/basis-
        independent) — **zero factorizations** in that case; otherwise the
        g anchor factorizations run once here and, with ``cache_anchors``,
        are parked as an anchors-only cache entry so the sweep that follows
        (whatever degree won) refits from them without factorizing either.
        Every candidate score after that is GEMMs only.

        Returns the :func:`~repro.core.picholesky.select_interpolant` dict
        plus ``anchor_status`` ∈ {'anchors' (cache hit), 'cold',
        'cold+cached'} and the anchor grid.
        """
        strat, bk = self.strategy, self._bk
        if not isinstance(strat, PiCholeskyStrategy):
            raise ValueError(
                "interpolant selection needs the picholesky strategy, got "
                f"{getattr(strat, 'name', strat)!r}")
        lams = self._check_lams(lams, min_q=2, what="interpolant selection")
        anchors = _sample_grid(lams, strat.g)
        h_tr, _ = self._split(folds.hess, folds.grad,
                              folds.fold_hess, folds.fold_grad)
        meta = strat.cache_meta(lams)
        key = None
        if self.cache is not None and meta is not None:
            key = self._cache_key(h_tr, meta)
        pf = (self.cache.get_anchors(key)
              if key is not None and self.reuse else None)
        status = "anchors"
        if pf is None:
            with self._stage_scope("fold_state"):
                vec = self._anchor_targets_fn()(h_tr, anchors,
                                                folds.x_folds)
            vec = vec.astype(self._prec.store_dtype(vec.dtype))
            pf = packing.PackedFactor(vec=vec, h=int(h_tr.shape[-1]),
                                      block=strat.block)
            status = "cold"
            if key is not None and self.cache_anchors:
                self.cache.put(key, None, pf)   # anchors-only entry
                status = "cold+cached"
        sel = picholesky.select_interpolant(jnp.asarray(pf.vec), anchors,
                                            degrees, bases=bases, backend=bk)
        sel["anchor_status"] = status
        sel["g"] = strat.g
        sel["anchors"] = np.asarray(anchors).tolist()
        return sel

    def advise_anchor(self, folds: FoldData, lams: jax.Array, *,
                      probe_dim: int = 32, n_grid: int = 5) -> dict:
        """Bound-guided anchor placement: score the strategy's anchor
        intervals with the Thm 4.4 machinery
        (:func:`~repro.core.bound.anchor_advisor`) and propose the next
        anchor at the log-midpoint of the weakest interval.

        The bound operators are exact but O(d⁶) (M is d²×d²), so the
        advisor works on a **probe**: the leading ``probe_dim`` principal
        submatrix of the fold-mean training Hessian.  That makes the
        advice a documented heuristic — it guides anchor *placement*,
        it never enters the sweep math.
        """
        strat = self.strategy
        g = getattr(strat, "g", None)
        if g is None:
            raise ValueError(
                "anchor advice needs an anchored interpolant strategy "
                f"(with g sample shifts); {getattr(strat, 'name', strat)!r} "
                "has none")
        lams = self._check_lams(lams, min_q=2, what="anchor advisor")
        from . import bound
        anchors = _sample_grid(lams, g)
        h_tr, _ = self._split(folds.hess, folds.grad,
                              folds.fold_hess, folds.fold_grad)
        d = min(int(probe_dim), int(h_tr.shape[-1]))
        probe = jnp.mean(h_tr, axis=0)[:d, :d]
        out = bound.anchor_advisor(probe, np.asarray(anchors), n_grid=n_grid)
        out["probe_dim"] = d
        out["anchors"] = np.asarray(anchors).tolist()
        return out

    # -- public API -------------------------------------------------------

    def sweep_temp_bytes(self, folds: FoldData, lams: jax.Array) -> int:
        """Live-buffer proxy for the jitted (unsharded) sweep: XLA temp
        allocation in bytes, excluding inputs/outputs.

        This is the measurable form of the O(chunk · P) memory contract —
        the packed-pipeline acceptance test reads it, so there is exactly
        one definition of "the sweep's peak memory".
        """
        lams = jnp.asarray(lams)
        h_tr, g_tr = self._split(folds.hess, folds.grad, folds.fold_hess,
                                 folds.fold_grad)
        lowered = self._sweep_fn(None).lower(h_tr, g_tr, folds.x_folds,
                                             folds.y_folds, lams)
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)

    def replay_temp_bytes(self, folds: FoldData, lams: jax.Array) -> int:
        """XLA temp bytes of the λ-stream (replay) stage alone, from a
        fitted state — the policy-governed O(chunk · P) working set without
        the ``fold_state`` factorization buffers.  This is the quantity the
        precision policy's storage dtype halves, measured the same way as
        :meth:`sweep_temp_bytes`."""
        lams = jnp.asarray(lams)
        h_tr, g_tr = self._split(folds.hess, folds.grad, folds.fold_hess,
                                 folds.fold_grad)
        state, _, _ = self._cold_state(None, folds, h_tr, g_tr, lams, False,
                                       None)
        lowered = self._replay_fn(None).lower(
            state, h_tr, g_tr, folds.x_folds, folds.y_folds, lams)
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)

    def run(self, folds: FoldData, lams: jax.Array) -> CVResult:
        if self.tune:
            derived, cfg = self._tuned_engine(folds, lams)
            res = derived.run(folds, lams)
            res.extras["engine"]["tune"] = cfg.to_json()
            return res
        lams = self._check_lams(lams)
        k = folds.fold_hess.shape[0]
        q = lams.shape[0]
        mesh = self._resolve_mesh(folds)
        self._check_fold_axis(mesh, k)
        if mesh is not None:
            lams_run, _ = shardlib.pad_to_multiple(
                lams, mesh.shape[shardlib.CV_LAM_AXIS])
        else:
            lams_run = lams

        with tracing.span("cv.run", h=int(folds.fold_hess.shape[-1]), k=k,
                          q=int(q)) as span:
            meta = self._cache_meta(lams)
            if meta is not None:
                h_tr, g_tr = self._split(folds.hess, folds.grad,
                                         folds.fold_hess, folds.fold_grad)
                state, _, status, cache_info = self._acquire_state(
                    meta, mesh, folds, h_tr, g_tr, lams_run)
                errs = self._replay_fn(mesh)(state, h_tr, g_tr,
                                             folds.x_folds, folds.y_folds,
                                             lams_run)
                n_chol = (self.strategy.n_exact_chol(k, q)
                          if status == "miss" else 0)
            else:
                # engine-owned train-stat buffers: safe to donate into the
                # sweep
                if self._by_pair(mesh):
                    h_in = self._pair_hessians(mesh, folds)
                    g_tr = self._split_folds(folds.grad, folds.fold_grad,
                                             np.arange(k))
                else:
                    h_in, g_tr = self._split(folds.hess, folds.grad,
                                             folds.fold_hess, folds.fold_grad)
                errs = self._sweep_fn(mesh)(h_in, g_tr, folds.x_folds,
                                            folds.y_folds, lams_run)
                cache_info = (None if self.cache is None
                              else dict(status="bypass"))
                n_chol = self.strategy.n_exact_chol(k, q)
            span.set_metadata(status=(cache_info or {}).get("status", "none"))
            with tracing.span("cv.fetch"):
                errs = np.asarray(errs)[:, :q]
        n_lam = 1 if mesh is None else mesh.shape[shardlib.CV_LAM_AXIS]
        q_loc = int(lams_run.shape[0]) // n_lam
        return CVResult.from_errors(
            lams, errs.mean(0), n_chol, engine=self._result_meta(
                mesh, self._lam_chunk_used(q_loc, folds.fold_hess.shape[-1],
                                           folds.fold_hess.dtype),
                cache_info,
                shard=self._shard_counts(mesh, folds, q_loc, n_chol > 0,
                                         fused=meta is None)))

    def _result_meta(self, mesh: Optional[Mesh], chunk: int,
                     cache: Optional[dict], **more) -> dict:
        """A result's ``extras['engine']``: the engine's configuration,
        the mesh the sweep ran on, the λs each λ-stage call took
        (``chunk``), the cache's info and ``more``."""
        return dict(strategy=self.strategy.name, backend=self._bk.name,
                    precision=self._prec.name,
                    mesh=None if mesh is None else dict(mesh.shape),
                    donated=bool(self.donate), lam_chunk=self.lam_chunk,
                    lam_chunk_resolved=chunk, cache=cache, **more)

    # -- batched admission (multi-tenant serving) ---------------------------

    def _cache_scope(self, tenant: Optional[str]):
        """Tenant-attribution scope on the attached cache (no-op without
        one) — the serving layer's per-tenant hit-rate partitioning."""
        if self.cache is None or tenant is None:
            return contextlib.nullcontext()
        return self.cache.tenant_scope(tenant)

    def run_batch(self, problems, *, tenants=None):
        """Admission-batched sweep: N compatible CV problems, ONE stacked
        ``fold_state`` dispatch, per-problem λ streams — the multi-tenant
        serving entry point (:mod:`repro.serving`).

        ``problems`` is a sequence of ``(FoldData, lams)`` pairs;
        ``tenants`` an optional parallel sequence of tenant labels for the
        cache's per-tenant stat partitioning.  Returns one
        :class:`~repro.core.folds.CVResult` per problem, in order, each
        bit-for-bit equal to what a solo :meth:`run` of that problem
        against the same cache state would produce (the per-fold math is
        identical — stacking reorders *batching*, never arithmetic).

        Dispatch per problem: content fingerprint → cache hit (λ stream
        only) | anchor refit | cold.  All the batch's cold problems are
        concatenated along the fold axis and factorized in **one** batched
        ``fold_state`` call, then sliced back and cached under their own
        per-problem keys — so cross-tenant sharing still works request-by-
        request afterwards.  A problem whose fingerprint duplicates an
        earlier problem *in the same batch* is looked up again after the
        cold stage populates, and served as a genuine hit.

        The fused stacking path engages when every problem shares the fold
        geometry (h, n_f, dtype), derives the same anchor set, the strategy
        advertises ``batchable_state`` (and ``cache_meta``), a cache is
        attached, and the sweep runs on one device; otherwise the batch degrades
        gracefully to per-problem :meth:`run` calls (same results, no
        stacked dispatch).
        """
        problems = [(f, self._check_lams(l)) for f, l in problems]
        if tenants is None:
            tenants = [None] * len(problems)
        if len(tenants) != len(problems):
            raise ValueError(f"{len(tenants)} tenant labels for "
                             f"{len(problems)} problems")
        if not problems:
            return []
        if self.tune:
            # admission groups share a geometry (the server's admission
            # key), so one tune on the batch head covers the batch
            derived, cfg = self._tuned_engine(*problems[0])
            results = derived.run_batch(problems, tenants=tenants)
            for r in results:
                r.extras["engine"]["tune"] = cfg.to_json()
            return results
        strat = self.strategy
        metas = [self._cache_meta(l) for _, l in problems]
        fusable = (self.cache is not None and self.reuse is not False
                   and self.mesh is None
                   and self._fit_mesh(problems[0][0]) is None
                   and getattr(strat, "batchable_state", False)
                   and all(m is not None for m in metas))
        if fusable:
            a0 = np.asarray(metas[0]["anchors"])
            f0 = problems[0][0]
            fusable = all(
                np.array_equal(np.asarray(m["anchors"]), a0)
                and f.fold_hess.shape[1:] == f0.fold_hess.shape[1:]
                and f.x_folds.shape[1:] == f0.x_folds.shape[1:]
                and f.fold_hess.dtype == f0.fold_hess.dtype
                for (f, _), m in zip(problems, metas))
        if not fusable:
            # incompatible admission: same cache/engine, per-problem runs
            out = []
            for (f, l), t in zip(problems, tenants):
                with self._cache_scope(t):
                    out.append(self.run(f, l))
            return out

        cache = self.cache
        splits = [self._split(f.hess, f.grad, f.fold_hess, f.fold_grad)
                  for f, _ in problems]
        keys = [self._cache_key(h_tr, m)
                for (h_tr, _), m in zip(splits, metas)]
        with_anchors = (self.cache_anchors
                        and hasattr(strat, "fold_state_and_anchors"))

        # pass 1 — fingerprint lookup; first occurrence of each digest
        # resolves now, duplicates defer until the cold stage has populated
        n = len(problems)
        entries: list = [None] * n
        statuses: list = [None] * n
        first_of: dict = {}
        cold_idx: list = []
        for i, key in enumerate(keys):
            digest = key.digest()
            if digest in first_of:
                continue                      # deferred to pass 3
            first_of[digest] = i
            with self._cache_scope(tenants[i]):
                entry = cache.lookup(key, self.reuse)
                if entry is not None:
                    entries[i], statuses[i] = entry, "hit"
                    continue
                pf = (cache.get_anchors(key)
                      if with_anchors else None)
            if pf is not None:
                state = self._refit_from_anchors(pf, metas[i])
                with self._cache_scope(tenants[i]):
                    entries[i] = cache.put(key, state, pf)
                statuses[i] = "refit"
            else:
                cold_idx.append(i)

        # pass 2 — ONE stacked fold_state dispatch for every cold problem
        if cold_idx:
            h_stack = jnp.concatenate([splits[i][0] for i in cold_idx])
            g_stack = jnp.concatenate([splits[i][1] for i in cold_idx])
            x_stack = jnp.concatenate(
                [problems[i][0].x_folds for i in cold_idx])
            y_stack = jnp.concatenate(
                [problems[i][0].y_folds for i in cold_idx])
            with self._stage_scope("prepare"):
                aux = self._prepare_fn()(h_stack, g_stack, x_stack, y_stack,
                                         problems[cold_idx[0]][1])
            with self._stage_scope("fold_state"):
                state, avec = self._state_fn(None, with_anchors)(
                    jnp.arange(h_stack.shape[0]), h_stack, g_stack, aux)
            off = 0
            for i in cold_idx:
                k_i = splits[i][0].shape[0]
                st_i = jax.tree.map(lambda x: x[off:off + k_i], state)
                pf_i = (packing.PackedFactor(
                    vec=avec[off:off + k_i], h=splits[i][0].shape[-1],
                    block=metas[i]["params"]["block"])
                    if with_anchors else None)
                off += k_i
                with self._cache_scope(tenants[i]):
                    entries[i] = cache.put(keys[i], st_i, pf_i)
                statuses[i] = "miss"

        # pass 3 — in-batch duplicates are genuine hits now.  If LRU
        # pressure already evicted the first occurrence's entry, its
        # in-memory object is still referenced in `entries` — serve from
        # that (the miss the lookup just counted is accurate: the cache
        # no longer holds it).
        for i, key in enumerate(keys):
            if entries[i] is not None:
                continue
            with self._cache_scope(tenants[i]):
                entry = cache.lookup(key, self.reuse)
            entries[i] = entry if entry is not None \
                else entries[first_of[key.digest()]]
            statuses[i] = "hit"

        # λ streams — per problem (grids differ), through the shared
        # chunked replay stage; O(chunk · P) as everywhere else
        replay = self._replay_fn(None)
        results = []
        for i, ((folds_i, lams_i), (h_tr, g_tr)) in enumerate(
                zip(problems, splits)):
            k_i, q_i = h_tr.shape[0], int(lams_i.shape[0])
            with tracing.span("cv.run", h=int(h_tr.shape[-1]), k=k_i, q=q_i,
                              status=statuses[i]):
                with self._stage_scope("fold_errors"):
                    errs = replay(entries[i].state, h_tr, g_tr,
                                  folds_i.x_folds, folds_i.y_folds, lams_i)
                with tracing.span("cv.fetch"):
                    errs = np.asarray(errs)
            n_chol = (strat.n_exact_chol(k_i, q_i)
                      if statuses[i] == "miss" else 0)
            info = dict(status=statuses[i],
                        digest=entries[i].key.digest()[:12],
                        policy=self.reuse, tenant=tenants[i], **cache.stats)
            results.append(CVResult.from_errors(
                lams_i, errs.mean(0), n_chol, engine=self._result_meta(
                    None, self._lam_chunk_used(q_i, h_tr.shape[-1],
                                               h_tr.dtype), info,
                    batch=dict(size=n, index=i, cold=len(cold_idx)))))
        return results
