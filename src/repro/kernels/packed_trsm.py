"""Triangular solves directly on tile-packed factors (packed-domain trsm).

The packed layout (:mod:`repro.core.packing`) stores the lower tiles of L in
tile-column-major order, so a column sweep of blocked forward substitution
walks the packed buffer panel by panel — and because column ``i`` of packed
``L`` is exactly row ``i`` of ``Lᵀ``, the *reverse* column sweep is back
substitution.  Nothing ever unpacks to the dense ``(h, h)`` matrix: peak
kernel footprint is one ``B×B`` tile + the RHS block, which is what lets the
λ sweep stream interpolated factors in constant memory.

Kernel layout: sequential grid ``(nt, nt)`` — outer step ``s`` is the tile
row being solved, inner step ``u`` streams that row's tiles (fetched via a
scalar-prefetched (s, u) → packed-index map; already-solved rows come from
the revisited output ref).  Diagonal tiles are pre-inverted once outside the
kernel (shared by both sweeps: ``inv(L_jj)ᵀ = inv(L_jjᵀ)``) so every inner
step is one ``B×B @ B×q`` MXU GEMM.

Mixed precision (:mod:`repro.core.precision`): ``compute_dtype`` is what the
MXU GEMM operands are cast to (bf16 halves the streamed tile traffic),
``accum_dtype`` is what the GEMMs accumulate in and the solution/output ref
live in — fp32 whenever compute is 16-bit, so the substitution recurrence
never accumulates rounding in bf16.  Diagonal tiles are inverted at the
accumulation dtype (inverting a bf16-rounded triangle is the unstable half
of the tradeoff), then cast down for the MXU.  Defaults (``None``) inherit
the factor's dtype — bit-compatible with the pre-policy kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing

from .compat import mxu_dot, vmem_scratch

__all__ = ["solve_lower_packed", "solve_packed"]


def _make_kernel(block: int, nt: int, reverse: bool):
    def kernel(idx_ref, inv_ref, g_ref, tiles_ref, out_ref, acc_ref):
        s = pl.program_id(0)
        u = pl.program_id(1)
        i = (nt - 1 - s) if reverse else s   # tile row being solved
        t = (nt - 1 - u) if reverse else u   # tile column being visited

        @pl.when((s == 0) & (u == 0))
        def _init():  # unsolved rows must read 0.0, not uninitialized VMEM
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(u == 0)
        def _zero_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # In iteration order, off-diagonal contributions (solved rows) come
        # first, the diagonal solve last: forward visits t = 0..i, the
        # reverse sweep visits t = nt−1..i.
        contrib = (t > i) if reverse else (t < i)

        @pl.when(contrib)
        def _accumulate():
            # MXU operands at the compute dtype (the tile already is), the
            # accumulation at the scratch/accum dtype
            w_t = out_ref[pl.ds(t * block, block), :]
            tile = tiles_ref[0].T if reverse else tiles_ref[0]
            acc_ref[...] += mxu_dot(tile, w_t.astype(tile.dtype),
                                    acc_ref.dtype)

        @pl.when(t == i)
        def _solve():
            g_i = g_ref[pl.ds(i * block, block), :]
            inv = inv_ref[0].T if reverse else inv_ref[0]
            rhs = (g_i - acc_ref[...]).astype(inv.dtype)
            out_ref[pl.ds(i * block, block), :] = mxu_dot(
                inv, rhs, out_ref.dtype)

    return kernel


@functools.lru_cache(maxsize=None)
def _step_tile_indices(h: int, block: int, reverse: bool) -> np.ndarray:
    """(nt²,) packed-tile index for grid step (s, u); 0 for skipped steps."""
    nt = packing.num_tiles(h, block)
    pmap = packing.tile_pos_map(h, block)
    idx = np.zeros(nt * nt, np.int32)
    for s in range(nt):
        i = nt - 1 - s if reverse else s
        for u in range(nt):
            t = nt - 1 - u if reverse else u
            if reverse and t >= i:
                idx[s * nt + u] = pmap[t, i]   # row i of Lᵀ = column i of L
            elif not reverse and t <= i:
                idx[s * nt + u] = pmap[i, t]
    return idx


def _resolve_dtypes(ref_dtype, compute_dtype, accum_dtype):
    """(compute, accum) dtype pair: inherit by default, never accumulate in
    a 16-bit type — the one rule shared by every packed kernel (the rule
    itself lives in :func:`repro.core.precision.default_accum_dtype`)."""
    from repro.core.precision import default_accum_dtype

    cd = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else jnp.dtype(ref_dtype)
    ad = (jnp.dtype(accum_dtype) if accum_dtype is not None
          else default_accum_dtype(cd))
    return cd, ad


def _inv_diag_tiles(vec: jax.Array, h: int, block: int,
                    accum_dtype=None) -> jax.Array:
    """(nt, B, B) pre-inverted diagonal tiles (identity-padded tail),
    inverted at ``accum_dtype`` for stability."""
    tiles = vec.reshape(-1, block, block)
    diag = packing._diag_tiles(tiles, h, block)
    if accum_dtype is not None:
        diag = diag.astype(accum_dtype)
    return packing.invert_diag_tiles(diag)


@functools.partial(jax.jit, static_argnames=("h", "block", "transpose",
                                             "interpret", "compute_dtype",
                                             "accum_dtype"))
def solve_lower_packed(vec: jax.Array, g: jax.Array, h: int, block: int = 128,
                       *, transpose: bool = False,
                       interpret: bool | None = None,
                       compute_dtype=None, accum_dtype=None) -> jax.Array:
    """Solve L w = g (or Lᵀ w = g) from the packed factor ``vec`` (P,).

    ``g``: (h,) or (h, q).  Matches :func:`repro.core.packing.solve_lower_packed`.
    ``compute_dtype`` / ``accum_dtype``: see module doc — defaults inherit
    ``vec.dtype``; the solution comes back in the accumulation dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    cd, ad = _resolve_dtypes(vec.dtype, compute_dtype, accum_dtype)
    nt = packing.num_tiles(h, block)
    hp = nt * block
    squeeze = g.ndim == 1
    g2 = (g[:, None] if squeeze else g).astype(ad)
    q = g2.shape[1]
    if hp != h:
        g2 = jnp.pad(g2, ((0, hp - h), (0, 0)))

    tiles = vec.astype(cd).reshape(-1, block, block)
    inv_diag = _inv_diag_tiles(vec, h, block, accum_dtype=ad).astype(cd)
    idx = jnp.asarray(_step_tile_indices(h, block, transpose))

    def inv_index(s, u, idx):
        return ((nt - 1 - s) if transpose else s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((1, block, block), inv_index),
            pl.BlockSpec((hp, q), lambda s, u, idx: (0, 0)),
            pl.BlockSpec((1, block, block),
                         lambda s, u, idx: (idx[s * nt + u], 0, 0)),
        ],
        out_specs=pl.BlockSpec((hp, q), lambda s, u, idx: (0, 0)),
        scratch_shapes=[vmem_scratch((block, q), g2.dtype)],
    )
    w = pl.pallas_call(
        _make_kernel(block, nt, transpose),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hp, q), g2.dtype),
        interpret=interpret,
        name="packed_trsm_upper" if transpose else "packed_trsm_lower",
    )(idx, inv_diag, g2, tiles)
    w = w[:h]
    return w[:, 0] if squeeze else w


def solve_packed(vec: jax.Array, g: jax.Array, h: int, block: int = 128, *,
                 interpret: bool | None = None,
                 compute_dtype=None, accum_dtype=None) -> jax.Array:
    """L Lᵀ θ = g entirely in the packed domain (forward + back sweep)."""
    w = solve_lower_packed(vec, g, h, block, transpose=False,
                           interpret=interpret, compute_dtype=compute_dtype,
                           accum_dtype=accum_dtype)
    return solve_lower_packed(vec, w, h, block, transpose=True,
                              interpret=interpret, compute_dtype=compute_dtype,
                              accum_dtype=accum_dtype)
