"""Stage names for the profiler, on the device and on the host.

Two helpers, one name table.  :func:`scope` is ``jax.named_scope``: opened
inside traced code, it costs nothing at run time and lands in the
``op_name`` metadata of every HLO op traced under it, so a device trace
can attribute each op to the stage that issued it.  :func:`span` is
``jax.profiler.TraceAnnotation``: a host event in the profiler's own
trace, on the same clock as the device ops (about a microsecond when no
trace is running).  Nested scopes and spans resolve to the innermost.

Device scopes, opened inside the jitted stage functions (a scope around a
call to an already compiled function would add nothing):

``cv.split``
    site: the functions ``CVEngine._split`` and ``CVEngine._split_folds``
    jit.  Covers: the per-fold train Hessians and gradients from the fold
    stats (on the divided sweep, each device's block of its pairs' folds,
    made on the designs' device).
``cv.anchor_chol``
    site: ``picholesky.anchor_factors`` (every anchor-factorizing path) and
    the anchor grid of ``prepare``.  Covers: the g anchor factorizations
    and the shifted Hessians they read; other strategies' factorizations.
``cv.theta_fit``
    site: ``picholesky.fit`` from ``pack_tril`` to the returned Θ (so also
    ``CVEngine._refit_from_anchors``).  Covers: packing of the anchor
    factors and the normal equations.
``cv.anchor_exchange``
    site: ``PiCholeskyStrategy.divided_state`` (a mesh whose λ axis holds
    more than one device).  Covers: the all-to-all that gives each device
    one tile slab of every (fold, anchor) pair's packed factor, the gather
    of the Θ slabs, and the pads and slices around them.  The Θ fit
    between them is ``cv.theta_fit``.
``cv.lam_stage``
    site: ``_InterpolantErrors.fold_errors`` around ``state.solve``, and
    ``CVEngine._stream_errors``.  Covers: ``interp_solve`` (both
    substitution sweeps, the diagonal-block inversion, Horner, pads) and
    the ``lax.map`` chunking of the λ grid.
``cv.refine``
    site: around ``picholesky.refine_solutions``.  Covers: the refinement
    sweeps (``bf16_refined``).
``cv.score``
    site: ``_errors_from_thetas``.  Covers: hold-out scoring, inside the
    sweep, or on the divided sweep handed its pairs' folds in a program
    of its own on the designs' device (``CVEngine._score_fn``).
``cv.<strategy>``
    the rest of a non-piCholesky strategy: ``cv.exact``, ``cv.svd``,
    ``cv.low_rank``, ``cv.sketch``, ``cv.warmstart``, ``cv.pinrmse``.

Host spans:

``cv.run``
    ``CVEngine.run``, and one per problem of ``run_batch``; args ``h``,
    ``k``, ``q`` and the cache ``status`` (``miss`` / ``hit`` / ``refit``
    / ``bypass``, ``none`` without a cache).
``cache.fingerprint``
    ``factor_cache.make_key``; arg ``bytes``.  Inside it ``cache.d2h``,
    the Hessians' copy to the host; the rest is hashing.
``cache.lookup``
    ``FactorCache.lookup`` / ``get_anchors`` / ``put``; arg ``result``.
``cv.fetch``
    the curve's copy to the host in ``run``, ``sweep_async``, ``search``
    and ``run_batch``.
``cv.prepare``, ``cv.fold_state``, ``cv.fold_errors``
    the staged dispatches, through ``CVEngine._stage_scope``.

Counters beside them:

``FactorCache.fingerprint_bytes``
    in ``FactorCache.stats``: the host bytes hashed into cache keys.
``shard``
    in ``extras['engine']`` of ``run``, ``run_async`` and ``search``:
    ``devices``, the devices of the sweep's mesh (1 without one);
    ``pairs_per_device``, the factorizations one device ran for the
    problem (0 when the state came from the cache); ``exchange_bytes``,
    the bytes one device received from the others in
    ``cv.anchor_exchange``, worked out from the layout's shapes
    (``PairLayout.exchange_bytes``, 0 where nothing is exchanged), not
    read from the device; ``inputs``, ``"by_pair"`` where each device of
    a divided state stage was handed only its pairs' folds of the train
    Hessians (``CVEngine._by_pair``), else ``"replicated"`` (each device
    its fold row's folds whole); ``placed_bytes``, the bytes of the train
    Hessians and gradients, and in the fused sweep of ``run`` the
    hold-out rows and targets, that the program running the state stage
    was handed on the devices other than the designs' (λ grids and
    ``aux``, a few hundred bytes, left out), worked out from the shapes
    the placement used, not read from the device (0 without a mesh, or
    when the state came from the cache).
``lam_chunk_resolved``
    in ``extras['engine']`` of every result that records ``lam_chunk``
    (``run``, ``run_async``, ``search``, ``run_batch``): the λs one call
    of the λ stage takes on a device, the configured ``lam_chunk``
    resolved; with ``'auto'`` on the Pallas path, the λ columns each read
    of a Θ tile serves.
"""
from __future__ import annotations

import jax

SPLIT = "cv.split"
ANCHOR_CHOL = "cv.anchor_chol"
THETA_FIT = "cv.theta_fit"
LAM_STAGE = "cv.lam_stage"
REFINE = "cv.refine"
SCORE = "cv.score"
ANCHOR_EXCHANGE = "cv.anchor_exchange"
#: the device scopes of the one-device piCholesky pipeline, in pipeline
#: order (a divided state stage adds ANCHOR_EXCHANGE)
SCOPES = (SPLIT, ANCHOR_CHOL, THETA_FIT, LAM_STAGE, REFINE, SCORE)


def scope(name: str):
    """A device scope: ``jax.named_scope``, for use inside traced code."""
    return jax.named_scope(name)


def span(name: str, **args):
    """A host span: ``jax.profiler.TraceAnnotation`` with its args as the
    event's stats.  More args can follow with ``set_metadata``."""
    return jax.profiler.TraceAnnotation(name, **args)
