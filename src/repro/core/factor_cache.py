"""Warm-replay factor cache: reuse fitted Θ / packed anchors across sweeps.

The paper's premise is that factorization over the λ grid dominates CV cost;
once the anchor Cholesky factors are fitted, the interpolant Θ — (r+1, P),
q-independent — answers *any* later grid over the same anchor range at zero
factorization cost.  This module is that seam made concrete: a content-
addressed cache of per-fold fitted :class:`~repro.core.picholesky.PiCholesky`
states (and optionally the per-(fold, λ_s) packed anchor factors), consumed
by :class:`~repro.core.engine.CVEngine` via its ``cache=`` / ``reuse=``
wiring.  On a hit the engine skips ``fold_state`` entirely and replays the
sweep through the fused ``interp_solve`` chunked stream.

Keying — a :class:`CacheKey` is a content fingerprint, never an object id:

* ``fold_hashes``   sha256 of each fold's training Hessian (shape + dtype
                    + bytes), so a perturbed problem can never hit,
* ``anchors``       the anchor-λ grid the fit factorized at,
* ``h, block``      packed-layout geometry,
* ``dtype``         of the training Hessians,
* ``backend``       name of the :class:`~repro.core.backends.LinalgBackend`
                    that produced the factors,
* ``params``        the strategy's static fit parameters (degree, basis, …),
* ``precision``     the :class:`~repro.core.precision.PrecisionPolicy`
                    descriptor the state was fitted/stored under — a bf16
                    entry can never silently serve an fp32 request,
* ``sketch``        how the anchor factors were *produced*
                    (:meth:`~repro.core.sketch.SketchPlan.descriptor`, a
                    low-rank descriptor, or ``'exact'``) — a sketched or
                    rank-truncated factor can never silently serve an
                    exact request, on any of the three lookup routes.

Three derived digests serve three lookups:

* :meth:`CacheKey.digest`        — exact hit (everything matches),
* :meth:`CacheKey.base_digest`   — everything but the anchor grid; the
  ``'covering'`` reuse policy accepts a cached Θ whose anchor range covers
  the requested grid,
* :meth:`CacheKey.anchor_digest` — only what the anchor *factors* depend on
  (Hessians, anchor λs, geometry, dtype, backend); a Θ miss with an anchor
  hit refits the polynomial from the cached
  :class:`~repro.core.packing.PackedFactor` targets without factorizing.

Persistence goes through :class:`~repro.checkpoint.CheckpointManager`
(Θ and PackedFactor are already pytrees): each entry is one checkpoint step
plus an ``index.json`` sidecar recording the key and leaf specs, so caches
survive across processes and torn writes are skipped on load.

Service-shaped deployments bound residency with ``FactorCache(max_bytes=)``
— a byte-budget LRU over the entries' array payload (eviction counters in
:attr:`FactorCache.stats`); an evicted entry can only miss and repopulate,
never serve stale.  Population is stage-aligned with the engine's pipelined
sweep: the entry is written as soon as the ``fold_state`` stage completes,
*before* the λ stream starts, so an early-stopped sweep
(:meth:`~repro.core.engine.CVEngine.sweep_async` with ``stop_tol=``) still
leaves a complete, replayable entry — Θ is λ-grid independent; only the
curve evaluation is truncated.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint import CheckpointManager

from . import packing, picholesky, solvers, tracing

__all__ = ["CacheKey", "CacheEntry", "FactorCache", "array_hash",
           "hessian_fingerprint", "make_key", "INDEX_FILENAME"]


INDEX_FILENAME = "index.json"

#: Relative slack when testing whether a cached anchor range covers a
#: requested λ range under the ``'covering'`` reuse policy — exactly the
#: float noise of recomputing grid endpoints, not a semantic tolerance.
COVER_RTOL = 1e-12


def array_hash(arr) -> str:
    """sha256 of an array's shape + dtype + raw bytes (host transfer)."""
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.shape).encode())
    h.update(str(a.dtype).encode())
    h.update(a.tobytes())
    return h.hexdigest()


def hessian_fingerprint(h_tr) -> Tuple[str, ...]:
    """Per-fold content hash of the (k, h, h) training-Hessian stack."""
    a = np.asarray(h_tr)
    if a.ndim != 3:
        raise ValueError(f"expected (k, h, h) fold Hessians, got {a.shape}")
    return tuple(array_hash(f) for f in a)


def _digest(payload: dict) -> str:
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


@dataclasses.dataclass(frozen=True)
class CacheKey:
    """Content fingerprint of one fitted fold×anchor state (see module doc)."""

    fold_hashes: Tuple[str, ...]
    anchors: Tuple[float, ...]
    h: int
    block: int
    dtype: str
    backend: str
    params: Tuple[Tuple[str, Any], ...]
    precision: str = "native"
    #: anchor-production descriptor — ``'exact'`` for dense Cholesky,
    #: ``SketchPlan.descriptor()`` for sketched anchors, ``'lowrank/r…'``
    #: for the low-rank path.  A first-class field (not a ``params``
    #: entry) so :meth:`anchor_digest` — which deletes ``params`` for
    #: degree/basis-independent anchor reuse — still separates sketched
    #: from exact factors.
    sketch: str = "exact"

    def _payload(self) -> dict:
        return dict(fold_hashes=list(self.fold_hashes),
                    anchors=list(self.anchors), h=self.h, block=self.block,
                    dtype=self.dtype, backend=self.backend,
                    params=[list(p) for p in self.params],
                    precision=self.precision, sketch=self.sketch)

    def digest(self) -> str:
        return _digest(self._payload())

    def base_digest(self) -> str:
        p = self._payload()
        del p["anchors"]
        return _digest(p)

    def anchor_digest(self) -> str:
        """What the anchor *factors* L_s = chol(H_f + λ_s I) depend on —
        independent of the polynomial degree/basis, so cached anchors can
        re-fit a different interpolant without any factorization."""
        p = self._payload()
        del p["params"]
        return _digest(p)

    def to_json(self) -> dict:
        return self._payload()

    @classmethod
    def from_json(cls, rec: dict) -> "CacheKey":
        return cls(fold_hashes=tuple(rec["fold_hashes"]),
                   anchors=tuple(float(a) for a in rec["anchors"]),
                   h=int(rec["h"]), block=int(rec["block"]),
                   dtype=str(rec["dtype"]), backend=str(rec["backend"]),
                   params=tuple((str(k), v) for k, v in rec["params"]),
                   precision=str(rec.get("precision", "native")),
                   sketch=str(rec.get("sketch", "exact")))


def make_key(h_tr, anchors, *, block: int, backend: str,
             params: Dict[str, Any], precision: str = "native",
             sketch: str = "exact") -> CacheKey:
    """Fingerprint a sweep's λ-independent inputs.

    ``h_tr``: (k, h, h) per-fold training Hessians (hashed on host — one
    device sync per ``run``, the price of content addressing).
    ``anchors``: the anchor-λ grid the fit would factorize at.
    ``params``: the strategy's static fit parameters (degree, basis, g, …).
    ``precision``: the policy descriptor the state is fitted/stored under
    (:meth:`~repro.core.precision.PrecisionPolicy.descriptor`).
    ``sketch``: the anchor-production descriptor (``'exact'`` | a
    :meth:`~repro.core.sketch.SketchPlan.descriptor` | ``'lowrank/r…'``).

    Host spans: ``cache.fingerprint`` around it all, ``cache.d2h`` around
    the Hessians' copy to the host; the rest is hashing.
    """
    with tracing.span("cache.fingerprint", bytes=int(h_tr.nbytes)):
        with tracing.span("cache.d2h"):
            h_tr = np.asarray(h_tr)
        return CacheKey(
            fold_hashes=hessian_fingerprint(h_tr),
            anchors=tuple(float(a) for a in np.asarray(anchors).ravel()),
            h=int(h_tr.shape[-1]), block=int(block),
            dtype=str(h_tr.dtype), backend=str(backend),
            params=tuple(sorted(params.items())),
            precision=str(precision), sketch=str(sketch))


def _tree_nbytes(tree) -> int:
    """Total bytes of every array leaf (aval-based — never syncs a
    device buffer that is still being computed).  Reflects the leaves'
    *actual* dtypes — a post-``astype`` bf16 state counts its bf16 bytes,
    so ``max_bytes`` LRU budgets stay honest under mixed precision."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        total += int(nbytes if nbytes is not None
                     else np.asarray(leaf).nbytes)
    return total


def _tree_nbytes_at(tree, dtype) -> int:
    """What the same leaves would weigh if every float leaf were stored at
    ``dtype`` — the baseline the ``bytes_saved`` counter compares against
    (the training-Hessian dtype the problem arrived in)."""
    import jax.numpy as jnp
    item = np.dtype(dtype).itemsize
    total = 0
    for leaf in jax.tree.leaves(tree):
        a_dt = getattr(leaf, "dtype", None)
        size = int(getattr(leaf, "size", np.asarray(leaf).size))
        if a_dt is not None and jnp.issubdtype(a_dt, jnp.inexact):
            total += size * item
        else:
            total += size * np.dtype(a_dt or np.float64).itemsize
    return total


@dataclasses.dataclass
class CacheEntry:
    """One cached fit: the batched-over-folds Θ state, and optionally the
    per-(fold, λ_s) tile-packed anchor factors that produced it.

    ``state=None`` marks an **anchors-only** entry: the interpolant
    selection path (:meth:`~repro.core.engine.CVEngine.select_interpolant`)
    factorizes the anchors before any Θ has been fitted and parks them
    here so whichever (degree, basis) the caller settles on refits with
    zero factorizations.  Such entries serve :meth:`FactorCache.get_anchors`
    but can never satisfy a state ``lookup``."""

    key: CacheKey
    #: fitted per-fold state: a :class:`~repro.core.picholesky.PiCholesky`
    #: (theta (k, r+1, P), center (k,)) or, for the low-rank strategy, a
    #: :class:`~repro.core.solvers.LowRankFactors` (vt (k, r, h), evals
    #: (k, r)).  ``None`` marks an anchors-only entry.
    state: Optional[Any]
    anchors: Optional[packing.PackedFactor] = None   # vec (k, g, P)
    hits: int = 0
    nbytes: int = 0                       # array payload (state + anchors),
    #                                       at the leaves' POST-astype dtypes
    bytes_saved: int = 0                  # vs storing at the Hessian dtype
    last_used: int = 0                    # LRU clock tick of last touch


class FactorCache:
    """In-memory, content-addressed store of fitted interpolant states.

    ``lookup`` policies:

    * ``'exact'``    — the full :meth:`CacheKey.digest` must match (the
      requested grid derives the same anchor set the entry was fitted on).
    * ``'covering'`` — accept any entry matching on :meth:`base_digest`
      whose anchor range covers the requested range (the cached Θ answers
      the sub-range, at the wider fit's interpolation accuracy).

    ``max_bytes`` bounds the resident array payload for service-shaped
    deployments: every write evicts least-recently-used entries (the LRU
    clock ticks on hits, anchor reads, and writes) until the total fits
    the budget.  The entry being written always survives — a cache whose
    budget is smaller than one entry degrades to capacity one, never to
    refusing writes.  Eviction is invalidation-safe by construction: an
    evicted digest simply misses and repopulates (all lookup indexes are
    purged with the entry), so a stale hit is impossible.

    Counters (``hits`` / ``misses`` / ``anchor_hits`` / ``evictions`` /
    ``bytes_saved`` / ``fingerprint_bytes``, the host bytes hashed into
    keys by :meth:`fingerprint`) are cumulative over the cache's lifetime
    — eviction never rewrites history (the *resident* saving is the
    separate :attr:`live_bytes_saved`); tests and the benchmark read them
    via :attr:`stats`.

    Multi-tenant deployments partition the read/write counters per tenant
    with :meth:`tenant_scope`: every ``lookup`` / ``get_anchors`` / ``put``
    inside the scope is also attributed to that tenant's row in
    :attr:`tenant_stats`.  Attribution is bookkeeping only — the *entries*
    are deliberately shared (cross-tenant reuse is the serving layer's
    whole hit-rate story), and content addressing already guarantees a
    tenant can never read a state its own bytes did not fingerprint.
    """

    def __init__(self, max_bytes: Optional[int] = None):
        if max_bytes is not None and max_bytes <= 0:
            raise ValueError(f"max_bytes must be positive or None, "
                             f"got {max_bytes}")
        self.max_bytes = max_bytes
        self.entries: Dict[str, CacheEntry] = {}
        self._by_base: Dict[str, List[str]] = {}
        self._by_anchor: Dict[str, str] = {}
        self.hits = 0
        self.misses = 0
        self.anchor_hits = 0
        self.evictions = 0
        #: cumulative bytes mixed-precision storage has saved across every
        #: ``put`` over the cache's lifetime (NOT shrunk by eviction — the
        #: old live-entries-only accounting made an eviction retroactively
        #: rewrite the reported saving)
        self.bytes_saved = 0
        self.fingerprint_bytes = 0
        self.tenant_stats: Dict[str, Dict[str, int]] = {}
        self._tenant: Optional[str] = None
        self._tick = 0

    def __len__(self) -> int:
        return len(self.entries)

    @property
    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.entries.values())

    @property
    def live_bytes_saved(self) -> int:
        """Bytes mixed-precision storage is saving *right now* vs keeping
        every resident entry at its problem's (training-Hessian) dtype —
        shrinks when a reduced-precision entry is evicted, unlike the
        cumulative :attr:`bytes_saved` counter."""
        return sum(e.bytes_saved for e in self.entries.values())

    @property
    def stats(self) -> dict:
        return dict(entries=len(self.entries), hits=self.hits,
                    misses=self.misses, anchor_hits=self.anchor_hits,
                    evictions=self.evictions, bytes=self.total_bytes,
                    bytes_saved=self.bytes_saved,
                    live_bytes_saved=self.live_bytes_saved,
                    fingerprint_bytes=self.fingerprint_bytes,
                    max_bytes=self.max_bytes)

    def fingerprint(self, h_tr, anchors, **params) -> CacheKey:
        """:func:`make_key`, counting the Hessian bytes it hashes on the
        host into :attr:`fingerprint_bytes`."""
        key = make_key(h_tr, anchors, **params)
        self.fingerprint_bytes += int(h_tr.nbytes)
        return key

    # ------------------------------------------------- per-tenant counters

    @contextlib.contextmanager
    def tenant_scope(self, tenant: Optional[str]):
        """Attribute every cache operation inside the scope to ``tenant``'s
        partition of the counters (``None`` = unattributed).  Scopes nest;
        the innermost wins — the engine's batched-admission path switches
        the scope per problem while the entries stay shared."""
        prev, self._tenant = self._tenant, tenant
        try:
            yield self
        finally:
            self._tenant = prev

    def _tenant_count(self, field: str, amount: int = 1) -> None:
        if self._tenant is None:
            return
        rec = self.tenant_stats.setdefault(
            self._tenant, dict(hits=0, misses=0, anchor_hits=0, puts=0))
        rec[field] += amount

    def hit_rate(self, tenant: Optional[str] = None) -> float:
        """hits / (hits + misses), overall or for one tenant's partition."""
        if tenant is None:
            hits, misses = self.hits, self.misses
        else:
            rec = self.tenant_stats.get(
                tenant, dict(hits=0, misses=0))
            hits, misses = rec["hits"], rec["misses"]
        total = hits + misses
        return hits / total if total else 0.0

    def _touch(self, entry: CacheEntry) -> None:
        self._tick += 1
        entry.last_used = self._tick

    # ---------------------------------------------------------------- read

    def lookup(self, key: CacheKey, policy: str = "exact"
               ) -> Optional[CacheEntry]:
        if policy not in ("exact", "covering"):
            raise ValueError(f"unknown reuse policy {policy!r}; "
                             "expected 'exact' or 'covering'")
        with tracing.span("cache.lookup") as span:
            entry = self._find(key, policy)
            span.set_metadata(result="miss" if entry is None else "hit")
        if entry is None:
            self.misses += 1
            self._tenant_count("misses")
            return None
        self.hits += 1
        self._tenant_count("hits")
        entry.hits += 1
        self._touch(entry)
        return entry

    def _find(self, key: CacheKey, policy: str) -> Optional[CacheEntry]:
        """The entry serving ``key`` under ``policy``, or None."""
        entry = self.entries.get(key.digest())
        if entry is not None and entry.state is None:
            entry = None        # anchors-only entry: no Θ to serve
        if entry is None and policy == "covering" and key.anchors:
            lo, hi = min(key.anchors), max(key.anchors)
            best_width = None
            for digest in self._by_base.get(key.base_digest(), ()):
                cand = self.entries[digest]
                if cand.state is None:
                    continue    # anchors-only — cannot cover a state read
                c_lo, c_hi = min(cand.key.anchors), max(cand.key.anchors)
                if (c_lo <= lo + abs(lo) * COVER_RTOL
                        and hi <= c_hi + abs(c_hi) * COVER_RTOL):
                    # tightest covering range wins: a Θ fitted over fewer
                    # decades answers the sub-range more accurately
                    width = c_hi - c_lo
                    if best_width is None or width < best_width:
                        best_width, entry = width, cand
        return entry

    def get_anchors(self, key: CacheKey) -> Optional[packing.PackedFactor]:
        """Cached packed anchor factors for ``key``'s anchor fingerprint
        (degree/basis-independent), or None.  Counts as an anchor hit."""
        with tracing.span("cache.lookup") as span:
            digest = self._by_anchor.get(key.anchor_digest())
            entry = None if digest is None else self.entries[digest]
            anchors = None if entry is None else entry.anchors
            span.set_metadata(result="anchor miss" if anchors is None
                              else "anchor hit")
        if anchors is not None:  # entry may have been repopulated bare
            self.anchor_hits += 1
            self._tenant_count("anchor_hits")
            self._touch(entry)
        return anchors

    # --------------------------------------------------------------- write

    def put(self, key: CacheKey, state: Optional[picholesky.PiCholesky],
            anchors: Optional[packing.PackedFactor] = None) -> CacheEntry:
        """Write one entry.  ``state=None`` with ``anchors`` stores an
        anchors-only entry (served by :meth:`get_anchors` only — the
        interpolant-selection path's pre-Θ write)."""
        if state is None and anchors is None:
            raise ValueError("refusing to cache an empty entry: "
                             "need a fitted state, packed anchors, or both")
        with tracing.span("cache.lookup", result="put"):
            digest = key.digest()
            nbytes = _tree_nbytes((state, anchors))
            baseline = _tree_nbytes_at((state, anchors), key.dtype)
            entry = CacheEntry(key=key, state=state, anchors=anchors,
                               nbytes=nbytes,
                               bytes_saved=max(0, baseline - nbytes))
            self.bytes_saved += entry.bytes_saved
            self._tenant_count("puts")
            if digest not in self.entries:
                self._by_base.setdefault(key.base_digest(), []).append(digest)
            self.entries[digest] = entry
            if anchors is not None:
                self._by_anchor[key.anchor_digest()] = digest
            self._touch(entry)
            self._evict_to_budget(keep=digest)
            return entry

    # ------------------------------------------------------ byte-budget LRU

    def _evict(self, digest: str) -> None:
        """Drop one entry and purge every lookup index that could serve it
        (exact, covering and anchor routes) — an evicted digest can only
        MISS afterwards, never return a stale state."""
        entry = self.entries.pop(digest)
        base = entry.key.base_digest()
        siblings = self._by_base.get(base)
        if siblings is not None:
            siblings[:] = [d for d in siblings if d != digest]
            if not siblings:
                del self._by_base[base]
        anchor = entry.key.anchor_digest()
        if self._by_anchor.get(anchor) == digest:
            del self._by_anchor[anchor]
        self.evictions += 1

    def _evict_to_budget(self, keep: str) -> None:
        if self.max_bytes is None:
            return
        while self.total_bytes > self.max_bytes and len(self.entries) > 1:
            victim = min((d for d in self.entries if d != keep),
                         key=lambda d: self.entries[d].last_used)
            self._evict(victim)

    # --------------------------------------------------- persistence (disk)

    @staticmethod
    def _leaf_spec(arr) -> dict:
        a = np.asarray(arr)
        return dict(shape=list(a.shape), dtype=str(a.dtype))

    @staticmethod
    def _leaf_like(spec: dict) -> np.ndarray:
        return np.zeros(tuple(spec["shape"]), dtype=np.dtype(spec["dtype"]))

    def save(self, directory: str) -> str:
        """Persist every entry through :class:`CheckpointManager` (one step
        per entry, ``keep=None`` so nothing is garbage-collected) plus an
        ``index.json`` sidecar.  Crash-safe end to end: new saves always
        take FRESH step numbers (never rewriting a step an existing index
        may reference), the index flips last via ``os.replace``, and only
        then are steps the new index doesn't reference pruned — a torn
        save leaves the previous index valid and self-consistent."""
        mgr = CheckpointManager(directory, keep=None)
        base = max(mgr.all_steps(), default=-1) + 1
        index = {"schema": "factor_cache/v1", "entries": []}
        for offset, (digest, e) in enumerate(sorted(self.entries.items())):
            step = base + offset
            tree = {}
            if isinstance(e.state, solvers.LowRankFactors):
                tree["vt"] = e.state.vt
                tree["evals"] = e.state.evals
                srec_out = {"kind": "low_rank",
                            "vt": self._leaf_spec(e.state.vt),
                            "evals": self._leaf_spec(e.state.evals)}
            elif e.state is not None:
                tree["theta"] = e.state.theta
                tree["center"] = e.state.center
                srec_out = {"h": e.state.h, "block": e.state.block,
                            "theta": self._leaf_spec(e.state.theta),
                            "center": self._leaf_spec(e.state.center)}
            else:
                srec_out = None
            if e.anchors is not None:
                tree["anchors_vec"] = e.anchors.vec
            mgr.save(step, tree)
            rec = {
                "step": step, "digest": digest, "key": e.key.to_json(),
                "state": srec_out,
                "anchors": None if e.anchors is None else {
                    "h": e.anchors.h, "block": e.anchors.block,
                    "vec": self._leaf_spec(e.anchors.vec)},
            }
            index["entries"].append(rec)
        path = os.path.join(directory, INDEX_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(index, f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        # only after the flip is it safe to drop steps the live index no
        # longer references (a crash mid-prune just leaves harmless extras)
        referenced = {rec["step"] for rec in index["entries"]}
        for s in mgr.all_steps():
            if s not in referenced:
                shutil.rmtree(mgr.step_dir(s), ignore_errors=True)
        return path

    @classmethod
    def load(cls, directory: str,
             max_bytes: Optional[int] = None) -> "FactorCache":
        """Rebuild a cache from :meth:`save` output.  Entries whose
        checkpoint fails the manager's hash verification (torn writes) are
        skipped, never half-loaded; a stale digest (index/payload mismatch)
        is likewise dropped.  ``max_bytes`` applies the byte-budget LRU to
        the reloaded cache (entries beyond the budget are evicted in index
        order — oldest first — during the load)."""
        cache = cls(max_bytes=max_bytes)
        path = os.path.join(directory, INDEX_FILENAME)
        if not os.path.exists(path):
            return cache
        with open(path) as f:
            index = json.load(f)
        mgr = CheckpointManager(directory, keep=None)
        for rec in index.get("entries", ()):
            key = CacheKey.from_json(rec["key"])
            if key.digest() != rec["digest"]:
                continue
            srec = rec["state"]
            kind = (srec or {}).get("kind", "picholesky")
            like = {}
            if srec is not None and kind == "low_rank":
                like["vt"] = cls._leaf_like(srec["vt"])
                like["evals"] = cls._leaf_like(srec["evals"])
            elif srec is not None:
                like["theta"] = cls._leaf_like(srec["theta"])
                like["center"] = cls._leaf_like(srec["center"])
            arec = rec.get("anchors")
            if arec is not None:
                like["anchors_vec"] = cls._leaf_like(arec["vec"])
            try:
                tree = mgr.restore(rec["step"], like)
            except IOError:
                continue
            if any(np.asarray(tree[name]).shape != np.asarray(ref).shape
                   or np.asarray(tree[name]).dtype != np.asarray(ref).dtype
                   for name, ref in like.items()):
                continue     # index/payload mismatch — drop, never mis-serve
            if srec is None:
                state = None
            elif kind == "low_rank":
                state = solvers.LowRankFactors(
                    vt=tree["vt"], evals=tree["evals"])
            else:
                state = picholesky.PiCholesky(
                    theta=tree["theta"], center=tree["center"],
                    h=int(srec["h"]), block=int(srec["block"]))
            anchors = None
            if arec is not None:
                anchors = packing.PackedFactor(
                    vec=tree["anchors_vec"], h=int(arec["h"]),
                    block=int(arec["block"]))
            cache.put(key, state, anchors)
        return cache
