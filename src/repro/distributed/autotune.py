"""Roofline-guided compile-time autotuner for the CV sweep.

Every hot-path knob in the pipeline used to be a static guess: the Pallas
backend hardcoded 256-wide kernel tiles, ``sharding.auto_lam_chunk`` sized
the λ-chunk from a fixed VMEM budget, and the folds × lams mesh shape was
caller-chosen (or the gcd heuristic).  This module *searches* that space
at compile time, with zero candidate executions:

1. **Enumerate** the legal configuration lattice for a problem geometry
   (h, k, q, dtype/precision, device count): kernel/packing block ×
   λ-chunk (the VMEM-auto value plus a pow2 ladder around it) × mesh
   shapes factoring the device count whose fold axis divides k
   (:func:`~repro.distributed.sharding.mesh_shape_candidates`).
2. **AOT-lower** the engine's jitted sweep — the ``fold_state`` +
   ``fold_errors`` stages jitted together, λ axis streamed under
   ``lax.map`` — for each candidate via ``jit(...).lower(shapes).compile()``
   on abstract :class:`jax.ShapeDtypeStruct` inputs.  Nothing runs; the
   compiled artifact is only *read*.
3. **Score** each artifact with the loop-aware HLO walker
   (:func:`~repro.distributed.hlo_cost.analyze_hlo` — λ-chunk ``while``
   loops are expanded by their trip count, so a small chunk's extra trips
   are priced) and the three roofline terms
   (:func:`~repro.distributed.roofline.roofline` against the detected
   :class:`~repro.distributed.roofline.HW` preset).  The predicted step
   time is ``max(compute, memory, collective)`` per device.
4. **Choose** the predicted-fastest :class:`TunedConfig`.  The engine's
   default configuration is always a candidate, and wins ties — tuning
   can refine the default, never silently regress its *prediction*.

Repeat tuning is free through the content-addressed :class:`TuningCache`
(keyed like the factor cache's ``CacheKey``: geometry + dtype + strategy
params + backend + precision + device fingerprint + lattice + HW),
persisted across processes via the checkpoint manager.

Entry points: :meth:`CVEngine(tune='auto') <repro.core.engine.CVEngine>`
threads the chosen config through the whole stack (strategy packing
block, Pallas kernel tiles, λ-chunk, mesh); :func:`tune` /
:func:`score_candidates` are the callable surface the bench and the
serving layer use directly.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import shutil
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.checkpoint.manager import CheckpointManager

from . import roofline as rl
from . import sharding as shardlib

__all__ = ["TunedConfig", "TuningCache", "fingerprint",
           "candidate_lattice", "score_candidates", "tune",
           "lower_sweep", "DEFAULT_BLOCKS"]

#: The kernel/packing block lattice on real problems (MXU-aligned tile
#: widths).  Candidates wider than the problem (block ≥ 2h) degenerate to
#: the same single padded tile and are pruned; benches and interpret-mode
#: tests pass proportionate lattices explicitly.
DEFAULT_BLOCKS = (128, 256, 512)

INDEX_FILENAME = "tuning_index.json"


# ------------------------------------------------------------------ config


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One point of the configuration lattice (and the tuner's verdict).

    ``mesh_shape`` is ``(n_fold, n_lam)`` or ``None`` (no mesh — single
    device execution).  ``predicted_s`` is the roofline-predicted step
    time (state + λ stream, per device); ``source`` records how the
    config was obtained (``'tuned'`` — fresh search, ``'cache'`` —
    tuning-cache hit, ``'default'`` — the engine's untuned configuration,
    ``'candidate'`` — a scored lattice point).
    """

    block: int
    lam_chunk: int
    mesh_shape: Optional[Tuple[int, int]] = None
    predicted_s: float = float("nan")
    source: str = "candidate"

    def key(self) -> tuple:
        return (self.block, self.lam_chunk, self.mesh_shape)

    def to_json(self) -> dict:
        return {"block": self.block, "lam_chunk": self.lam_chunk,
                "mesh_shape": (None if self.mesh_shape is None
                               else list(self.mesh_shape)),
                "predicted_s": self.predicted_s, "source": self.source}

    @classmethod
    def from_json(cls, d: dict) -> "TunedConfig":
        ms = d.get("mesh_shape")
        return cls(block=int(d["block"]), lam_chunk=int(d["lam_chunk"]),
                   mesh_shape=None if ms is None else tuple(int(x) for x in ms),
                   predicted_s=float(d.get("predicted_s", float("nan"))),
                   source=str(d.get("source", "candidate")))


# ------------------------------------------------------------- fingerprint


def device_fingerprint() -> dict:
    """What makes a tuning verdict machine-specific: platform, device
    kind, and how many devices the mesh lattice can factor over."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "n_devices": len(jax.devices())}


def fingerprint(*, h: int, k: int, n_f: int, q: int, dtype: str,
                lam_dtype: str, params: dict, backend: str, precision: str,
                lattice: dict, hw_name: str,
                devices: Optional[dict] = None) -> str:
    """Content digest of everything a tuning verdict depends on — keyed
    like the factor cache's ``CacheKey``: problem geometry + dtype +
    strategy params + backend + precision + device fingerprint, plus the
    candidate lattice and HW preset the search ranked against (a wider
    lattice or recalibrated HW must re-tune, never serve a stale
    verdict)."""
    payload = {
        "schema": "tuning_key/v1",
        "h": int(h), "k": int(k), "n_f": int(n_f), "q": int(q),
        "dtype": str(dtype), "lam_dtype": str(lam_dtype),
        "params": {str(a): repr(b) for a, b in sorted(params.items())},
        "backend": str(backend), "precision": str(precision),
        "lattice": {str(a): repr(b) for a, b in sorted(lattice.items())},
        "hw": str(hw_name),
        "devices": devices or device_fingerprint(),
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


# ------------------------------------------------------------------ cache


class TuningCache:
    """Content-addressed store of tuning verdicts (digest → config).

    Counters make the no-re-lowering contract testable: ``lowerings``
    increments once per candidate AOT compile, so a second :func:`tune`
    of the same geometry must be a ``hit`` that leaves it unchanged.

    Persistence rides the checkpoint manager exactly like the factor
    cache: :meth:`save` writes the verdict table as one checkpoint step
    (a uint8 JSON blob, sha256-manifested) plus an ``index.json`` sidecar
    recording the step and blob length (the like-tree
    :meth:`~repro.checkpoint.manager.CheckpointManager.restore` needs);
    the index flips last via ``os.replace`` so a torn save leaves the
    previous table valid, and stale steps are pruned only after the flip.
    """

    def __init__(self):
        self.configs: dict = {}    # digest -> TunedConfig
        self.hits = 0
        self.misses = 0
        self.lowerings = 0         # candidate AOT lower+compile count

    def __len__(self) -> int:
        return len(self.configs)

    def get(self, digest: str) -> Optional[TunedConfig]:
        cfg = self.configs.get(digest)
        if cfg is None:
            self.misses += 1
            return None
        self.hits += 1
        return cfg

    def put(self, digest: str, config: TunedConfig) -> TunedConfig:
        self.configs[digest] = config
        return config

    @property
    def stats(self) -> dict:
        return dict(entries=len(self.configs), hits=self.hits,
                    misses=self.misses, lowerings=self.lowerings)

    # -- persistence (checkpoint manager) ---------------------------------

    def save(self, directory: str) -> str:
        mgr = CheckpointManager(directory, keep=None)
        step = max(mgr.all_steps(), default=-1) + 1
        blob = json.dumps({d: c.to_json()
                           for d, c in sorted(self.configs.items())},
                          sort_keys=True).encode()
        arr = np.frombuffer(blob, dtype=np.uint8).copy()
        mgr.save(step, [arr])
        index = {"schema": "tuning_cache/v1", "step": step,
                 "nbytes": int(arr.size)}
        path = os.path.join(directory, INDEX_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(index, f, indent=1)
        os.replace(tmp, path)                      # atomic flip
        for s in mgr.all_steps():                  # prune superseded steps
            if s != step:
                shutil.rmtree(mgr.step_dir(s), ignore_errors=True)
        return path

    @classmethod
    def load(cls, directory: str) -> "TuningCache":
        cache = cls()
        path = os.path.join(directory, INDEX_FILENAME)
        if not os.path.exists(path):
            return cache
        with open(path) as f:
            index = json.load(f)
        if index.get("schema") != "tuning_cache/v1":
            return cache
        mgr = CheckpointManager(directory, keep=None)
        like = [np.zeros(int(index["nbytes"]), dtype=np.uint8)]
        try:
            (arr,) = mgr.restore(int(index["step"]), like)
        except IOError:
            return cache          # torn step: serve an empty cache, re-tune
        table = json.loads(
            np.asarray(arr, dtype=np.uint8).tobytes().decode())
        for digest, d in table.items():
            cache.configs[digest] = TunedConfig.from_json(d)
        return cache


# ----------------------------------------------------------------- lattice


def _pow2_near(x: float, lo: int, hi: int) -> int:
    """The power of two nearest ``x`` (log scale), clipped to [lo, hi]."""
    x = max(float(x), 1.0)
    p = 2 ** int(round(math.log2(x)))
    return max(lo, min(hi, p))


def chunk_ladder(auto: int, q: int) -> Tuple[int, ...]:
    """λ-chunk candidates around the VMEM-auto value: the auto chunk plus
    a pow2 ladder at ×¼, ×½, ×2, ×4 (clipped to [1, q], deduped).  The
    walker prices a smaller chunk's extra ``lax.map`` trips and a larger
    chunk's bigger working set, so the ladder spans both failure modes of
    the static heuristic."""
    auto = max(1, min(int(auto), q))
    out = {auto}
    for mult in (0.25, 0.5, 2.0, 4.0):
        out.add(_pow2_near(auto * mult, 1, q))
    return tuple(sorted(out))


def candidate_lattice(*, h: int, k: int, q: int, n_devices: int,
                      default: TunedConfig,
                      blocks: Optional[Sequence[int]] = None,
                      chunks: Optional[Sequence[int]] = None,
                      mesh_shapes: Optional[Sequence] = None,
                      auto_chunk: Optional[Callable[[int, int], int]] = None
                      ) -> List[TunedConfig]:
    """The legal configuration lattice for one problem geometry.

    ``default`` (the engine's untuned configuration) is always the first
    element — the search can only ever match or beat its prediction, and
    ties resolve to it.  Blocks whose padded single-tile layout coincides
    (block ≥ 2·2^ceil(log2(h)) beyond the first covering tile) are pruned
    by the ``block >= 2 * h`` guard.  Per-block chunk ladders centre on
    ``auto_chunk(block, q_loc)``, the engine's ``lam_chunk='auto'`` rule
    (:meth:`~repro.core.engine.CVEngine._auto_chunk`), else on the
    default's chunk.
    """
    blocks = tuple(blocks) if blocks is not None else DEFAULT_BLOCKS
    blocks = tuple(dict.fromkeys(
        b for b in blocks if b == default.block or b < 2 * h or b <= h))
    if default.block not in blocks:
        blocks = (default.block,) + blocks
    if mesh_shapes is None:
        mesh_shapes = ([None] if n_devices <= 1 else
                       [None] + [tuple(s) for s in
                                 shardlib.mesh_shape_candidates(k, n_devices)
                                 if s != (1, 1)])
    else:
        mesh_shapes = [None if s is None else tuple(s) for s in mesh_shapes]
    if default.mesh_shape not in mesh_shapes:
        mesh_shapes = [default.mesh_shape] + list(mesh_shapes)

    cands = [default]
    seen = {default.key()}
    for mesh_shape in mesh_shapes:
        n_lam = 1 if mesh_shape is None else mesh_shape[1]
        q_loc = max(1, math.ceil(q / n_lam))
        for block in blocks:
            if chunks is not None:
                ladder = tuple(max(1, min(int(c), q_loc)) for c in chunks)
            elif auto_chunk is not None:
                ladder = chunk_ladder(auto_chunk(block, q_loc), q_loc)
            else:
                ladder = chunk_ladder(default.lam_chunk, q_loc)
            for chunk in dict.fromkeys(ladder):
                cand = TunedConfig(block=block, lam_chunk=chunk,
                                   mesh_shape=mesh_shape)
                if cand.key() not in seen:
                    seen.add(cand.key())
                    cands.append(cand)
    return cands


# ----------------------------------------------------------------- scoring


def _abstract_problem(folds, lams) -> tuple:
    """ShapeDtypeStructs of the sweep's traced inputs (h_tr, g_tr,
    x_folds, y_folds) — nothing device-resident is needed to lower."""
    import jax

    def sds(x):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                    if not hasattr(x, "dtype") else x.dtype)

    k, n_f, h = folds.x_folds.shape
    dtype = folds.fold_hess.dtype
    h_tr = jax.ShapeDtypeStruct((k, h, h), dtype)
    g_tr = jax.ShapeDtypeStruct((k, h), dtype)
    x_s = sds(folds.x_folds)
    y_s = sds(folds.y_folds)
    return h_tr, g_tr, x_s, y_s


def lower_sweep(engine, folds, lams):
    """AOT lower + compile the engine's fused sweep (``fold_state`` +
    chunked ``fold_errors`` in one jit) on abstract shapes.  Returns
    ``(compiled, chips)``.  Nothing executes — this is the tuner's (and
    the roofline bench's) read-only view of a candidate."""
    import jax
    import jax.numpy as jnp

    k = folds.fold_hess.shape[0]
    mesh = engine._resolve_mesh(folds)
    engine._check_fold_axis(mesh, k)
    h_tr, g_tr, x_s, y_s = _abstract_problem(folds, lams)
    lams = jnp.asarray(lams)
    q = int(lams.shape[0])
    if mesh is not None:
        q += (-q) % mesh.shape[shardlib.CV_LAM_AXIS]
    lam_s = jax.ShapeDtypeStruct((q,), lams.dtype)
    compiled = engine._lower_sweep(mesh, h_tr, g_tr, x_s, y_s,
                                   lam_s).compile()
    chips = 1 if mesh is None else int(np.prod(list(mesh.shape.values())))
    return compiled, chips


def score_candidates(engine, folds, lams, candidates: Sequence[TunedConfig],
                     *, hw: Optional[rl.HW] = None,
                     cache: Optional[TuningCache] = None
                     ) -> List[TunedConfig]:
    """Predict each candidate's step time — AOT lowering only, zero
    executions.  Returns the candidates with ``predicted_s`` filled in
    (order preserved).  ``cache`` (when given) only counts lowerings."""
    hw = hw or rl.detect_hw()
    out = []
    for cand in candidates:
        derived = engine._apply_tuned(cand)
        compiled, chips = lower_sweep(derived, folds, lams)
        if cache is not None:
            cache.lowerings += 1
        roof = rl.roofline(compiled, chips, hw=hw)
        out.append(dataclasses.replace(cand, predicted_s=roof.step_s))
    return out


# -------------------------------------------------------------------- tune


def default_config(engine, folds, q: int) -> TunedConfig:
    """The engine's untuned configuration for ``folds`` and a grid of
    ``q`` λ as a lattice point: strategy / engine block, the resolved
    λ-chunk (VMEM-auto, explicit int, or the whole grid when streaming is
    off), and the mesh the engine would build (the gcd heuristic under
    ``mesh='auto'``; with ``mesh=None`` the default rule on the folds'
    geometry)."""
    block = getattr(engine.strategy, "block", None) or engine.block or 128
    chunk = engine._resolve_chunk(q, folds.fold_hess.shape[-1],
                                  folds.fold_hess.dtype)
    chunk = q if chunk is None else min(chunk, q)
    mesh = engine._resolve_mesh(folds)
    mesh_shape = (None if mesh is None else
                  (mesh.shape[shardlib.CV_FOLD_AXIS],
                   mesh.shape[shardlib.CV_LAM_AXIS]))
    return TunedConfig(block=block, lam_chunk=chunk, mesh_shape=mesh_shape,
                       source="default")


def tune(engine, folds, lams, *, cache: Optional[TuningCache] = None,
         blocks: Optional[Sequence[int]] = None,
         chunks: Optional[Sequence[int]] = None,
         mesh_shapes: Optional[Sequence] = None,
         hw: Optional[rl.HW] = None) -> TunedConfig:
    """Choose the predicted-fastest configuration for ``engine`` on this
    problem geometry.  See the module docstring for the pipeline; the
    returned config's ``source`` is ``'cache'`` on a tuning-cache hit
    (no lowering at all), else ``'tuned'``.
    """
    import jax
    import jax.numpy as jnp

    hw = hw or rl.detect_hw()
    k, n_f, h = folds.x_folds.shape
    lams = jnp.asarray(lams)
    q = int(lams.shape[0])
    dtype = folds.fold_hess.dtype
    n_devices = len(jax.devices())

    default = default_config(engine, folds, q)
    lattice_desc = dict(
        blocks=tuple(blocks) if blocks else DEFAULT_BLOCKS,
        chunks=tuple(chunks) if chunks else "auto-ladder",
        mesh_shapes=(tuple("none" if s is None else tuple(s)
                           for s in mesh_shapes)
                     if mesh_shapes is not None else "factorizations"),
        default=default.key())
    meta = (engine.strategy.cache_meta(lams)
            if hasattr(engine.strategy, "cache_meta") else None)
    params = dict(meta["params"]) if meta else {}
    params.pop("block", None)                     # block is what we tune
    params.setdefault("strategy", engine.strategy.name)

    digest = fingerprint(
        h=h, k=k, n_f=n_f, q=q, dtype=str(dtype), lam_dtype=str(lams.dtype),
        params=params, backend=engine._bk.name,
        precision=engine._prec.descriptor(), lattice=lattice_desc,
        hw_name=hw.name)
    if cache is not None:
        hit = cache.get(digest)
        if hit is not None:
            return dataclasses.replace(hit, source="cache")

    cands = candidate_lattice(
        h=h, k=k, q=q, n_devices=n_devices, default=default,
        blocks=blocks, chunks=chunks, mesh_shapes=mesh_shapes,
        auto_chunk=lambda block, q_loc: engine._auto_chunk(
            q_loc, h, dtype, block))
    scored = score_candidates(engine, folds, lams, cands, hw=hw, cache=cache)
    # strict < over a default-first list: ties (and equal-cost degenerate
    # candidates) resolve to the default configuration
    best = scored[0]
    for cand in scored[1:]:
        if cand.predicted_s < best.predicted_s:
            best = cand
    chosen = dataclasses.replace(best, source="tuned")
    if cache is not None:
        cache.put(digest, chosen)
    return chosen
