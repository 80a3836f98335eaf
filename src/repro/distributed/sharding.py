"""Spec-axis → NamedSharding resolution.

Model param specs carry literal axis tags: "model", "fsdp" (resolved to the
innermost data axis when FSDP is on, else dropped) or None.  This module
turns a spec tree into NamedSharding / PartitionSpec trees and validates
divisibility so a bad mesh fails loudly at lowering time, not deep in XLA.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import Spec

__all__ = ["spec_pspec", "param_pspecs", "param_shardings", "data_pspec",
           "CV_FOLD_AXIS", "CV_LAM_AXIS", "make_cv_mesh", "cv_axis_sizes",
           "mesh_shape_candidates", "PairLayout", "sweep_bytes",
           "device_bytes_free",
           "pad_to_multiple", "chunk_lams", "auto_lam_chunk",
           "cv_state_specs", "StageRing"]


def spec_pspec(spec: Spec, ctx) -> P:
    """PartitionSpec for one param Spec under the given MeshCtx."""
    out = []
    for dim, ax in zip(spec.shape, spec.axes):
        if ax is None:
            out.append(None)
            continue
        mesh_ax = ctx.fsdp_axis if ax == "fsdp" else ax
        if mesh_ax is None:
            out.append(None)
            continue
        size = ctx.axis_size(mesh_ax)
        if size > 1 and dim % size != 0:
            raise ValueError(
                f"dim {dim} of {spec.shape} not divisible by mesh axis "
                f"{mesh_ax}={size}")
        out.append(mesh_ax)
    return P(*out)


def param_pspecs(tree: Any, ctx) -> Any:
    return jax.tree.map(lambda s: spec_pspec(s, ctx), tree,
                        is_leaf=lambda x: isinstance(x, Spec))


def param_shardings(tree: Any, ctx) -> Any:
    if ctx.mesh is None:
        raise ValueError("param_shardings requires a mesh")
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, spec_pspec(s, ctx)),
                        tree, is_leaf=lambda x: isinstance(x, Spec))


def data_pspec(ctx, ndim: int) -> P:
    """Batch-sharded PartitionSpec for an input of rank ``ndim``."""
    return P(ctx.dp_axes, *([None] * (ndim - 1)))


# --------------------------------------------------------------- CV engine
#
# The CV sweep is a dense (fold × λ) grid of independent solves, so its
# natural mesh is 2-D: fold Hessians shard over CV_FOLD_AXIS, the λ grid
# over CV_LAM_AXIS.  These helpers pick the mesh shape from the problem
# size and pad the λ grid so shard_map divisibility always holds.

CV_FOLD_AXIS = "folds"
CV_LAM_AXIS = "lams"


def cv_axis_sizes(k: int, n_devices: int) -> Tuple[int, int]:
    """(n_fold, n_lam) mesh shape for ``k`` folds on ``n_devices`` devices.

    The fold axis takes the largest device count that divides ``k`` (fold
    count is fixed by the problem; it cannot be padded), the λ axis absorbs
    the remaining devices (the λ grid *can* be padded, see
    :func:`pad_to_multiple`).
    """
    n_fold = math.gcd(k, n_devices)
    return n_fold, n_devices // n_fold


def mesh_shape_candidates(k: int, n_devices: int) -> list:
    """Every legal (n_fold, n_lam) mesh shape for ``k`` folds on
    ``n_devices`` devices: all factorizations ``n_fold · n_lam ==
    n_devices`` whose fold axis divides ``k`` (folds cannot be padded; the
    λ grid can).  This is the mesh dimension of the autotuner's candidate
    lattice — :func:`cv_axis_sizes` picks one member (the gcd heuristic),
    the tuner scores them all."""
    out = []
    for n_fold in range(1, n_devices + 1):
        if n_devices % n_fold == 0 and k % n_fold == 0:
            out.append((n_fold, n_devices // n_fold))
    return out


def make_cv_mesh(k: int, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D (folds × lams) mesh over ``devices`` (default: all local)."""
    devices = list(devices if devices is not None else jax.devices())
    n_fold, n_lam = cv_axis_sizes(k, len(devices))
    dev = np.asarray(devices[: n_fold * n_lam]).reshape(n_fold, n_lam)
    return Mesh(dev, (CV_FOLD_AXIS, CV_LAM_AXIS))


@dataclasses.dataclass(frozen=True)
class PairLayout:
    """The divided state stage: the ``k·g`` (fold, anchor) factorizations
    of the folds a λ row of the mesh holds, dealt out over its ``n``
    devices.

    Every device of a λ row holds the same folds, so a fold-sharded state
    stage would repeat each of their factorizations on all ``n`` devices.
    Here device ``j`` factorizes pairs ``j·per_device … (j+1)·per_device −
    1`` of the fold-major pair list; the list is padded to
    ``n·per_device`` (never the folds), a padding pair repeating the last.
    The packed factors are exchanged in ``n`` slabs of whole tiles: device
    ``j`` receives slab ``j`` of every pair, fits Θ on it for every fold,
    and a gather gives every device every fold's Θ.  A device's pairs are
    contiguous, so they read a run of folds (:meth:`device_folds`), and
    only those folds' training Hessians need be on it.
    """

    k: int       # folds on the λ row
    g: int       # anchors per fold
    n: int       # devices on the λ axis
    h: int       # order of the factors
    block: int   # their packing tile

    @property
    def per_device(self) -> int:
        """Factorizations each device runs: ⌈k·g / n⌉."""
        return -(-self.k * self.g // self.n)

    @property
    def tiles(self) -> int:
        """Tiles of one packed factor."""
        from repro.core import packing   # local: distributed ↔ core layering
        nt = packing.num_tiles(self.h, self.block)
        return nt * (nt + 1) // 2

    @property
    def slab(self) -> int:
        """Elements of one exchanged slab: ⌈tiles / n⌉ whole tiles."""
        return -(-self.tiles // self.n) * self.block ** 2

    def pairs(self, j: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(fold, anchor) indices of device ``j``'s pairs (traced ``j``)."""
        p = jnp.minimum(j * self.per_device + jnp.arange(self.per_device),
                        self.k * self.g - 1)
        return p // self.g, p % self.g

    def device_folds(self) -> np.ndarray:
        """(n, f) the folds each device's pairs read, in order, from the
        first fold of its first pair: device ``j``'s pair of fold ``fold``
        reads row ``j``'s entry ``fold − fold[0]`` (:meth:`pairs`).  ``f``
        is the widest run; a shorter row repeats its last fold, as a
        padding pair does."""
        p = np.minimum(np.arange(self.n)[:, None] * self.per_device
                       + np.arange(self.per_device), self.k * self.g - 1)
        first, last = p[:, 0] // self.g, p[:, -1] // self.g
        width = int((last - first).max()) + 1
        return np.minimum(first[:, None] + np.arange(width), last[:, None])

    def exchange_bytes(self, degree: int, factor_itemsize: int,
                       theta_itemsize: int) -> int:
        """Bytes one device receives from the others per problem, from
        the collectives' shapes: the all-to-all of the packed factors
        ((n−1) slabs of its ``per_device`` pairs) and the gather of Θ
        ((n−1) slabs of ``k·(degree+1)`` rows)."""
        rows = (self.per_device * factor_itemsize
                + self.k * (degree + 1) * theta_itemsize)
        return (self.n - 1) * self.slab * rows


#: The one-device fused sweep's temporaries, in dense ``h×h`` factors per
#: (fold, anchor) pair.  XLA's memory analysis of the sweep for a
#: described v5e (k=5, g=4, n=4h, f32) reads 3.8 at h=4096 (5.12 GB of
#: temporaries, 5.73 GB in all) and 2.9 at h=8192 (15.61 GB; the compile
#: is refused, 16.79 GiB of 15.75), so the larger: an estimate too high
#: only costs a mesh where one device would have done.
SWEEP_FACTORS_PER_PAIR = 3.8


def sweep_bytes(k: int, g: int, h: int, n_rows: int, itemsize: int) -> int:
    """Device bytes the one-device fused sweep takes, from shapes: its
    arguments (the ``k`` train Hessians and the ``n_rows × h`` design) and
    the state stage's temporaries, :data:`SWEEP_FACTORS_PER_PAIR` dense
    factors for each of the ``k·g`` (fold, anchor) pairs."""
    args = k * h * h + n_rows * h
    return int(itemsize * (args + SWEEP_FACTORS_PER_PAIR * k * g * h * h))


def device_bytes_free(device: jax.Device) -> Optional[int]:
    """The memory one device can still give a program: its limit less
    what is in use (the designs already made there, say), or None where
    it reports no limit (the CPU)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return stats["bytes_limit"] - stats.get("bytes_in_use", 0)


def cv_state_specs(state: Any) -> Any:
    """Fold-sharded PartitionSpec tree for a per-fold state pytree.

    Cached/replayed fold states (e.g. the batched
    :class:`~repro.core.picholesky.PiCholesky` a warm sweep reuses) carry
    the fold axis as every leaf's leading dimension, so they shard over
    :data:`CV_FOLD_AXIS` exactly like the training Hessians they were
    fitted from — cache shards follow the folds × lams mesh.
    """
    return jax.tree.map(lambda _: P(CV_FOLD_AXIS), state)


class StageRing:
    """Bounded-lookahead dispatch ring (double buffering at ``depth=2``).

    The pipelined sweep dispatches per-fold ``fold_state`` stages without
    blocking; each dispatch consumes a donated per-fold Hessian slice, so
    unbounded lookahead would hold every fold's donated input in flight at
    once.  ``admit`` blocks on the *oldest* outstanding stage output before
    accepting a new dispatch, keeping at most ``depth`` stages (and their
    donated buffers) live — fold f+1's factorizations overlap fold f's
    chunk streaming, fold f+2's wait their turn.
    """

    def __init__(self, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.depth = depth
        self._live: list = []

    def admit(self, staged: Any) -> Any:
        """Register a freshly dispatched stage output, blocking on the
        oldest outstanding one if the ring is full.  Returns ``staged``."""
        if len(self._live) >= self.depth:
            jax.block_until_ready(self._live.pop(0))
        self._live.append(staged)
        return staged

    def drain(self) -> None:
        """Block on everything still in flight (end of the stage stream)."""
        while self._live:
            jax.block_until_ready(self._live.pop(0))


def pad_to_multiple(x: jax.Array, multiple: int, axis: int = 0):
    """Pad ``x`` along ``axis`` (edge mode) to a length divisible by
    ``multiple``; returns (padded, original_length)."""
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, mode="edge"), n


def auto_lam_chunk(h: int, block: int, dtype, budget: int) -> int:
    """λ-chunk size whose per-chunk packed working set fits ``budget`` bytes.

    The ``lam_chunk='auto'`` rule wherever the λ stage builds a factor per
    λ: the reference ``interp_solve``'s (chunk, P) rows, the
    non-interpolant strategies' factors
    (:meth:`~repro.core.engine.CVEngine._auto_chunk`).
    ``dtype`` is the *storage* dtype of the streamed interpolant rows
    (:meth:`~repro.core.precision.PrecisionPolicy.store_dtype`) — halving
    the itemsize (bf16) doubles the chunk at the same budget, which is the
    memory half of the mixed-precision contract.
    """
    from repro.core import packing   # local: distributed ↔ core layering
    per_lam = packing.packed_nbytes(h, block, dtype)
    return max(1, int(budget // per_lam))


def chunk_lams(lams: jax.Array, chunk: int):
    """Reshape a (local) λ grid into fixed-size chunks for the streamed
    sweep: (q,) → ((q_pad // chunk), chunk) plus the original length.

    Edge-padding keeps the padded tail numerically benign (repeats the last
    λ — an SPD shift that always factorizes); ``chunk > q`` degenerates to
    one padded chunk.  Composes with the λ-axis ``shard_map`` padding: that
    one runs on the global grid, this one on the per-device shard.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    padded, n = pad_to_multiple(lams, chunk)
    return padded.reshape(-1, chunk), n
