"""Linear-algebra backend selection — the single ``backend=`` switch.

Every piCholesky hot spot (factorize, triangular solve, pack/unpack,
interpolant evaluation) has two implementations: the Pallas TPU kernels in
:mod:`repro.kernels` and the ``jnp.linalg`` reference path.  This module
packages each pair behind one object so callers (``solvers.py``,
``picholesky.py``, the :class:`~repro.core.engine.CVEngine`) thread a single
``backend=`` argument instead of per-function ``chol_fn`` plumbing.

Resolution rules for :func:`resolve_backend`:

* ``None`` / ``"auto"`` — Pallas kernels when the default jax backend is TPU
  (compiled) and the plain ``jnp.linalg`` path elsewhere.  On CPU the Pallas
  path would run in interpret mode, which is only useful for testing.
* ``"pallas"`` — force the kernel path (interpret mode off-TPU).
* ``"reference"`` / ``"ref"`` — force the ``jnp.linalg`` path.
* an existing :class:`LinalgBackend` — returned unchanged.

Kernel imports happen lazily inside the Pallas methods so importing
``repro.core`` never drags in the Pallas toolchain.

Every backend also carries the pipeline's
:class:`~repro.core.precision.PrecisionPolicy` (``precision=``): the
factorization runs at the policy's accumulation dtype (never 16-bit), the
packed-domain solves feed the MXU at the compute dtype with full-precision
accumulation, and solutions come back in the accumulation dtype.  The
default ``native`` policy inherits every input dtype — bit-compatible with
the pre-policy backends.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Union

import jax
import jax.numpy as jnp

from .precision import PRESETS, PrecisionLike, PrecisionPolicy, \
    resolve_precision

__all__ = ["LinalgBackend", "ReferenceBackend", "PallasBackend",
           "CountingBackend", "resolve_backend", "retile_backend",
           "BackendLike"]


class LinalgBackend:
    """Interface shared by both backends (duck-typed, no ABC machinery).

    Two groups of methods: the dense surface (``cholesky`` / ``solve_lower``
    / ``solve_from_factor`` / ``pack_tril`` / ``unpack_tril``) and the
    packed-domain surface (``solve_packed`` / ``interp_solve`` /
    ``interp_factors``), which consumes the tile-packed ``(P,)`` layout
    directly so factors never round-trip through a dense ``(h, h)`` buffer
    on the sweep hot path.
    """

    name: str = "abstract"
    precision: PrecisionPolicy = PRESETS["native"]

    def with_precision(self, policy: PrecisionPolicy) -> "LinalgBackend":
        """This backend with ``policy`` attached (same kernels, new dtype
        contract).  Frozen-dataclass backends return a copy."""
        return dataclasses.replace(self, precision=policy)

    def cholesky(self, a: jax.Array) -> jax.Array:
        raise NotImplementedError

    def solve_lower(self, l: jax.Array, b: jax.Array, *,
                    transpose: bool = False) -> jax.Array:
        raise NotImplementedError

    def solve_from_factor(self, l, g: jax.Array) -> jax.Array:
        """L Lᵀ θ = g via forward + back substitution.

        ``l`` may be a dense factor or a :class:`~repro.core.packing.PackedFactor`
        (dispatched to :meth:`solve_packed` — no unpack).
        """
        from .packing import PackedFactor
        if isinstance(l, PackedFactor):
            return self.solve_packed(l, g)
        w = self.solve_lower(l, g)
        return self.solve_lower(l, w, transpose=True)

    def pack_tril(self, mat: jax.Array, block: int) -> jax.Array:
        raise NotImplementedError

    def unpack_tril(self, vec: jax.Array, h: int, block: int) -> jax.Array:
        raise NotImplementedError

    # -- packed-domain surface (the factor pipeline's native currency) -----

    def solve_packed(self, pf, g: jax.Array) -> jax.Array:
        """L Lᵀ θ = g directly on the tile-packed factor (no dense L)."""
        raise NotImplementedError

    def interp_solve(self, theta: jax.Array, lams: jax.Array, g: jax.Array,
                     *, h: int, block: int, center=0.0,
                     rhs_per_lam: bool = False) -> jax.Array:
        """Fused interpolant evaluation + substitution at a λ chunk:
        (q, h) solutions with no (q, h, h) — or even (q, P) on the kernel
        path — intermediate.  ``rhs_per_lam=True`` takes a per-λ RHS
        (q, h[, m]) — the refinement residuals — instead of one shared g."""
        raise NotImplementedError

    def interp_factors(self, theta: jax.Array, lams: jax.Array,
                       *, h: int, block: int, center=0.0) -> jax.Array:
        """Dense interpolated factors (q, h, h) — debug / dense consumers."""
        raise NotImplementedError

    def interp_lam_chunk(self, h: int, block: int, q: int, store_dtype, *,
                         degree: int, budget: int) -> int:
        """λs per :meth:`interp_solve` call, at most ``q``, by what the call
        holds per λ: here its (chunk, P) interpolated rows at
        ``store_dtype``, within ``budget`` bytes."""
        from repro.distributed import sharding
        return min(q, sharding.auto_lam_chunk(h, block, store_dtype, budget))


@dataclasses.dataclass(frozen=True)
class ReferenceBackend(LinalgBackend):
    """``jnp.linalg`` path — correct on every platform, XLA-fused.

    Mixed precision on this path keeps the *storage* contract (bf16 Θ and
    packed rows stream at half the bytes) while the substitutions run at
    the accumulation dtype — ``jnp.linalg`` has no 16-bit factorization,
    and a bf16-stored factor is defined as the rounding of a
    full-precision one, not a bf16 factorization.
    """

    name: str = "reference"
    precision: PrecisionPolicy = PRESETS["native"]

    def cholesky(self, a):
        # factorize at the accumulation dtype: bf16 inputs promote to fp32
        return jnp.linalg.cholesky(
            a.astype(self.precision.accum_dtype(a.dtype)))

    def solve_lower(self, l, b, *, transpose=False):
        l = l.astype(self.precision.accum_dtype(l.dtype))
        b2 = b[..., None] if b.ndim == l.ndim - 1 else b
        out = jax.lax.linalg.triangular_solve(
            l, b2.astype(l.dtype), left_side=True, lower=True,
            transpose_a=transpose)
        return out[..., 0] if b.ndim == l.ndim - 1 else out

    def pack_tril(self, mat, block):
        from . import packing
        return packing.pack_tril(mat, block)

    def unpack_tril(self, vec, h, block):
        from . import packing
        return packing.unpack_tril(vec, h, block)

    def solve_packed(self, pf, g):
        from . import packing
        ad = self.precision.accum_dtype(pf.vec.dtype)
        # vec is consumed at its storage dtype (tiles promote per-GEMM) —
        # no full-width upcast copy of the packed batch
        fn = functools.partial(packing.solve_packed_ref,
                               h=pf.h, block=pf.block, accum_dtype=ad)
        for _ in range(pf.vec.ndim - 1):   # batched factors via vmap
            fn = jax.vmap(fn, in_axes=(0, None))
        return fn(pf.vec, g.astype(ad))

    def interp_solve(self, theta, lams, g, *, h, block, center=0.0,
                     rhs_per_lam=False):
        from . import packing, picholesky
        ad = self.precision.accum_dtype(theta.dtype)
        model = picholesky.PiCholesky(
            theta=theta, center=jnp.asarray(center, ad),
            h=h, block=block)
        # (q, P) interpolated rows at the STORAGE dtype — the policy's
        # memory win on this path; the substitution accumulates at accum
        # with each tile promoted inside its GEMM (no full-width upcast)
        vecs = model.eval_packed(jnp.atleast_1d(lams))
        if rhs_per_lam:
            return jax.vmap(lambda v, gi: packing.solve_packed_ref(
                v, gi.astype(ad), h, block, accum_dtype=ad))(vecs, g)
        return jax.vmap(lambda v: packing.solve_packed_ref(
            v, g.astype(ad), h, block, accum_dtype=ad))(vecs)

    def interp_factors(self, theta, lams, *, h, block, center=0.0):
        from . import picholesky
        model = picholesky.PiCholesky(
            theta=theta, center=jnp.asarray(center, theta.dtype),
            h=h, block=block)
        return self.unpack_tril(model.eval_packed(jnp.atleast_1d(lams)),
                                h, block)


@dataclasses.dataclass(frozen=True)
class PallasBackend(LinalgBackend):
    """Pallas kernel path: blocked Cholesky/trsm, tile pack/unpack, and the
    packed-domain kernels (packed trsm, fused Horner interp-solve/unpack).

    ``chol_block`` / ``trsm_block`` are the *kernel* tile sizes (MXU-sized
    on real TPUs, small in CPU interpret-mode tests).  The packed *layout*
    block is always carried by the data (``pack_tril(mat, block)`` /
    :class:`~repro.core.packing.PackedFactor.block`), never by the backend;
    :func:`resolve_backend` sizes all kernel tiles from one ``block=`` so
    the pack/unpack layout and the compute kernels stay consistent.
    """

    name: str = "pallas"
    chol_block: int = 256
    trsm_block: int = 256
    precision: PrecisionPolicy = PRESETS["native"]

    def _dtypes(self, input_dtype):
        """(compute, accum) static kernel params — None when inherited, so
        native-policy calls hit the exact pre-policy jit cache keys."""
        p = self.precision
        if p.is_native:
            return None, None
        return (str(p.compute_dtype(input_dtype)),
                str(p.accum_dtype(input_dtype)))

    def cholesky(self, a):
        from repro.kernels.chol_blocked import cholesky_blocked
        cd, ad = self._dtypes(a.dtype)
        return cholesky_blocked(a, block=self.chol_block,
                                compute_dtype=cd, accum_dtype=ad)

    def solve_lower(self, l, b, *, transpose=False):
        from repro.kernels.trsm import solve_lower_blocked
        cd, ad = self._dtypes(l.dtype)
        return solve_lower_blocked(l, b, self.trsm_block, transpose=transpose,
                                   compute_dtype=cd, accum_dtype=ad)

    def pack_tril(self, mat, block):
        from repro.kernels.tri_pack import pack_tril

        def one(m):
            return pack_tril(m, block)

        fn = one
        for _ in range(mat.ndim - 2):  # kernel is single-matrix; batch via vmap
            fn = jax.vmap(fn)
        return fn(mat)

    def unpack_tril(self, vec, h, block):
        from repro.kernels.tri_pack import unpack_tril

        def one(v):
            return unpack_tril(v, h, block)

        fn = one
        for _ in range(vec.ndim - 1):
            fn = jax.vmap(fn)
        return fn(vec)

    def solve_packed(self, pf, g):
        from repro.kernels.packed_trsm import solve_packed

        cd, ad = self._dtypes(pf.vec.dtype)
        fn = functools.partial(solve_packed, h=pf.h, block=pf.block,
                               compute_dtype=cd, accum_dtype=ad)
        for _ in range(pf.vec.ndim - 1):
            fn = jax.vmap(fn, in_axes=(0, None))
        return fn(pf.vec, g)

    def interp_solve(self, theta, lams, g, *, h, block, center=0.0,
                     rhs_per_lam=False):
        from repro.kernels.poly_interp import interp_solve
        cd, ad = self._dtypes(theta.dtype)
        return interp_solve(theta, jnp.atleast_1d(lams), g, h, block,
                            center=center, rhs_per_lam=rhs_per_lam,
                            compute_dtype=cd, accum_dtype=ad)

    def interp_factors(self, theta, lams, *, h, block, center=0.0):
        from repro.kernels.poly_interp import interp_factors
        return interp_factors(theta, jnp.atleast_1d(lams), h, block,
                              center=center)

    def interp_lam_chunk(self, h, block, q, store_dtype, *, degree, budget):
        """The kernel builds no factor, so ``budget`` does not bind: its
        chunk is what one sweep's VMEM holds
        (:func:`~repro.kernels.poly_interp.sweep_lam_chunk`)."""
        from repro.kernels.packed_trsm import _resolve_dtypes
        from repro.kernels.poly_interp import sweep_lam_chunk
        cd, ad = _resolve_dtypes(store_dtype, *self._dtypes(store_dtype))
        return sweep_lam_chunk(h, block, q, 1, degree, cd, ad)


class CountingBackend(LinalgBackend):
    """Delegating wrapper that counts calls to ``cholesky`` — the
    factorization-counting hook behind the warm-replay acceptance test and
    the warm-vs-cold bench record.

    Counts **trace-site** calls: under ``jit``/``vmap`` each traced call
    site increments once per trace, not once per batched execution, and a
    cached compiled sweep re-executes without counting.  That is exactly
    the right granularity for the cache contract — a warm replay whose
    computation graph contains *no* factorization keeps the counter at
    zero, while any cold path (however batched) moves it.  Keeps the inner
    backend's ``name`` so cache fingerprints are unaffected by counting.

    Counting is **stage-granular**: the pipelined sweep wraps each stage's
    trace in :meth:`stage`, so :attr:`by_stage` attributes every counted op
    (``cholesky`` and the λ-stage workhorses ``interp_solve`` /
    ``solve_packed``) to the stage whose computation graph contains it —
    e.g. a cold piCholesky sweep counts its factorizations under
    ``'fold_state'`` and only fused interpolant solves under
    ``'fold_errors'``; calls traced outside any scope land in
    ``'unstaged'``.  Like the flat counter, attribution happens at trace
    time: re-executing a compiled stage moves nothing.
    """

    def __init__(self, inner: LinalgBackend, _shared_counts: dict = None):
        self.inner = inner
        # stage label -> {op: trace-site count}; the single source of truth
        # (n_cholesky is derived), shareable across with_precision views
        self.by_stage: dict = {} if _shared_counts is None else _shared_counts
        self._stage: str | None = None

    @property
    def n_cholesky(self) -> int:
        return sum(rec.get("cholesky", 0) for rec in self.by_stage.values())

    @property
    def name(self) -> str:          # fingerprint-transparent
        return self.inner.name

    @property
    def precision(self) -> PrecisionPolicy:   # policy-transparent
        return self.inner.precision

    def with_precision(self, policy: PrecisionPolicy) -> "CountingBackend":
        """A view over the SAME counters with ``policy`` attached.

        Never mutates this instance (an engine attaching its policy must
        not retroactively change another engine sharing the backend), and
        never forks the counts (callers hold this object to read them —
        ops traced through the view keep landing here).
        """
        return CountingBackend(self.inner.with_precision(policy),
                               _shared_counts=self.by_stage)

    def reset(self) -> None:
        self.by_stage.clear()       # in place — views share this dict

    @contextlib.contextmanager
    def stage(self, label: str):
        """Attribute ops traced inside this scope to ``label`` (reentrant —
        nested scopes restore the outer label on exit)."""
        prev, self._stage = self._stage, label
        try:
            yield self
        finally:
            self._stage = prev

    def stage_count(self, label: str, op: str = "cholesky") -> int:
        return self.by_stage.get(label, {}).get(op, 0)

    def _count(self, op: str) -> None:
        rec = self.by_stage.setdefault(self._stage or "unstaged", {})
        rec[op] = rec.get(op, 0) + 1

    def cholesky(self, a):
        self._count("cholesky")
        return self.inner.cholesky(a)

    def solve_lower(self, l, b, *, transpose=False):
        return self.inner.solve_lower(l, b, transpose=transpose)

    def solve_from_factor(self, l, g):
        return self.inner.solve_from_factor(l, g)

    def pack_tril(self, mat, block):
        return self.inner.pack_tril(mat, block)

    def unpack_tril(self, vec, h, block):
        return self.inner.unpack_tril(vec, h, block)

    def solve_packed(self, pf, g):
        self._count("solve_packed")
        return self.inner.solve_packed(pf, g)

    def interp_solve(self, theta, lams, g, *, h, block, center=0.0,
                     rhs_per_lam=False):
        self._count("interp_solve")
        return self.inner.interp_solve(theta, lams, g, h=h, block=block,
                                       center=center,
                                       rhs_per_lam=rhs_per_lam)

    def interp_factors(self, theta, lams, *, h, block, center=0.0):
        return self.inner.interp_factors(theta, lams, h=h, block=block,
                                         center=center)

    def interp_lam_chunk(self, h, block, q, store_dtype, *, degree, budget):
        return self.inner.interp_lam_chunk(h, block, q, store_dtype,
                                           degree=degree, budget=budget)


BackendLike = Union[None, str, LinalgBackend]


def retile_backend(bk: LinalgBackend, *, chol_block: int | None = None,
                   trsm_block: int | None = None) -> LinalgBackend:
    """``bk`` with the given Pallas kernel tile sizes (the autotuner's
    block dimension).  Backends without kernel tiles (reference) are
    returned unchanged; a :class:`CountingBackend` is re-wrapped around
    its retiled inner backend **sharing the same counters** — retiling
    must never fork the counts a test is holding a reference to."""
    if chol_block is None and trsm_block is None:
        return bk
    if isinstance(bk, CountingBackend):
        inner = retile_backend(bk.inner, chol_block=chol_block,
                               trsm_block=trsm_block)
        if inner is bk.inner:
            return bk
        return CountingBackend(inner, _shared_counts=bk.by_stage)
    if isinstance(bk, PallasBackend):
        return dataclasses.replace(
            bk, chol_block=chol_block or bk.chol_block,
            trsm_block=trsm_block or bk.trsm_block)
    return bk


def resolve_backend(backend: BackendLike = None, *,
                    block: int | None = None,
                    chol_block: int | None = None,
                    trsm_block: int | None = None,
                    precision: PrecisionLike = None) -> LinalgBackend:
    """Map a ``backend=`` argument to a concrete :class:`LinalgBackend`.

    ``block`` (when given) sizes **all** Pallas kernel tiles
    (``chol_block`` and ``trsm_block``) from the one value callers use as
    their packing-layout block — so small test problems get proportionate
    interpret-mode kernels and the pack/unpack layout never disagrees with
    the compute tiles.  ``chol_block`` / ``trsm_block`` override the tiles
    individually (the autotuner's chosen kernel tiles; they also re-tile a
    backend *instance* via :func:`retile_backend`).  The packed-domain
    kernels take their tile size from the data's own layout block
    (:class:`~repro.core.packing.PackedFactor`), which is consistent by
    construction.

    ``precision`` attaches a :class:`~repro.core.precision.PrecisionPolicy`
    (name, policy object, or ``None`` = the environment default).  A
    backend *instance* keeps its own policy unless ``precision`` is given
    explicitly — the engine resolves its policy from the backend it ends up
    with, so there is exactly one policy per pipeline.
    """
    if isinstance(backend, LinalgBackend):
        if precision is not None:
            pol = resolve_precision(precision)
            if pol != backend.precision:
                backend = backend.with_precision(pol)
        return retile_backend(backend, chol_block=chol_block,
                              trsm_block=trsm_block)
    pol = resolve_precision(precision)
    if backend is None or backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend in ("reference", "ref", "jnp"):
        return ReferenceBackend(precision=pol)
    if backend == "pallas":
        cb = chol_block or block
        tb = trsm_block or block
        if cb is not None or tb is not None:
            return PallasBackend(chol_block=cb or 256, trsm_block=tb or 256,
                                 precision=pol)
        return PallasBackend(precision=pol)
    raise ValueError(f"unknown backend {backend!r}; expected 'auto', "
                     "'pallas', 'reference', or a LinalgBackend")
