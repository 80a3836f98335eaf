"""Blocked triangular solves (the per-λ back-end of §3.2) as Pallas kernels.

Solving ``L w = g`` / ``Lᵀ θ = w`` for the whole λ sweep at once makes the
right-hand side a (h × q) block — so the substitution becomes a chain of
``B×B @ B×q`` MXU GEMMs instead of q separate vector solves.  Diagonal tiles
are pre-inverted once (q-independent) so the kernel contains no sequential
scalar solve at all.

Kernel layout: sequential grid over tile-rows; the full RHS block lives in
VMEM as the output ref (revisited every step), each step reads one (B × h)
row-panel of L, masks the not-yet-solved columns, and updates its B rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compat import mxu_dot

__all__ = ["solve_lower_blocked", "solve_factor_sweep"]


def _make_solve_kernel(block: int, nt: int, reverse: bool,
                       compute_dtype=None):
    def kernel(panel_ref, inv_ref, g_ref, w_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():  # unsolved rows must be 0.0, not uninitialized VMEM
            w_ref[...] = jnp.zeros_like(w_ref)

        i = (nt - 1 - step) if reverse else step
        h = nt * block
        col = jax.lax.broadcasted_iota(jnp.int32, (block, h), 1)
        if reverse:
            mask = col >= (i + 1) * block   # columns already solved (above)
        else:
            mask = col < i * block          # columns already solved (below)
        panel = jnp.where(mask, panel_ref[...], 0.0)
        w = w_ref[...]
        if compute_dtype is not None:       # MXU at reduced precision,
            panel = panel.astype(compute_dtype)   # full-precision accum
            w = w.astype(compute_dtype)
        s = mxu_dot(panel, w, w_ref.dtype)
        g_i = g_ref[pl.ds(i * block, block), :]
        rhs = g_i - s
        inv = inv_ref[0]
        if compute_dtype is not None:
            rhs = rhs.astype(compute_dtype)
            inv = inv.astype(compute_dtype)
        w_i = mxu_dot(inv, rhs, w_ref.dtype)
        w_ref[pl.ds(i * block, block), :] = w_i

    return kernel


@functools.partial(jax.jit, static_argnames=("transpose", "interpret", "block",
                                             "compute_dtype", "accum_dtype"))
def solve_lower_blocked(l: jax.Array, g: jax.Array, block: int = 256, *,
                        transpose: bool = False,
                        interpret: bool | None = None,
                        compute_dtype=None, accum_dtype=None) -> jax.Array:
    """Solve L w = g (or Lᵀ w = g) for lower-triangular L.  g: (h,) or (h, q).

    ``compute_dtype``/``accum_dtype``: MXU operand vs accumulation dtype —
    the factor state, diagonal inversion, and solution live at the
    accumulation dtype (defaults inherit ``l.dtype``).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from .packed_trsm import _resolve_dtypes
    cd, ad = _resolve_dtypes(l.dtype, compute_dtype, accum_dtype)
    cd_gemm = None if cd == ad else cd
    l = l.astype(ad)
    h = l.shape[-1]
    nt = -(-h // block)
    hp = nt * block
    squeeze = g.ndim == 1
    g2 = (g[:, None] if squeeze else g).astype(ad)
    q = g2.shape[1]
    if hp != h:
        l = jnp.pad(l, ((0, hp - h), (0, hp - h)))
        l = l.at[h:, h:].set(jnp.eye(hp - h, dtype=l.dtype))
        g2 = jnp.pad(g2, ((0, hp - h), (0, 0)))

    mat = l.T if transpose else l
    # row-panels of the (possibly transposed) operator, and inverted diag tiles
    diag = jnp.stack([jax.lax.dynamic_slice(mat, (k * block, k * block),
                                            (block, block)) for k in range(nt)])
    eye = jnp.eye(block, dtype=l.dtype)
    inv_diag = jax.lax.linalg.triangular_solve(
        diag, jnp.broadcast_to(eye, diag.shape), left_side=True,
        lower=not transpose, transpose_a=False)

    kernel = _make_solve_kernel(block, nt, reverse=transpose,
                                compute_dtype=cd_gemm)

    def row_index(step, *_):
        return ((nt - 1 - step) if transpose else step, 0)

    w = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block, hp), row_index),
            pl.BlockSpec((1, block, block),
                         lambda step: ((nt - 1 - step) if transpose else step, 0, 0)),
            pl.BlockSpec((hp, q), lambda step: (0, 0)),
        ],
        out_specs=pl.BlockSpec((hp, q), lambda step: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, q), g2.dtype),
        interpret=interpret,
        name="trsm_upper" if transpose else "trsm_lower",
    )(mat, inv_diag, g2)
    w = w[:h]
    return w[:, 0] if squeeze else w


def solve_factor_sweep(ls: jax.Array, g: jax.Array, block: int = 256, *,
                       interpret: bool | None = None) -> jax.Array:
    """Solve L_t L_tᵀ θ_t = g for a sweep of factors (q, h, h) -> (q, h)."""
    def one(l):
        w = solve_lower_blocked(l, g, block, transpose=False, interpret=interpret)
        return solve_lower_blocked(l, w, block, transpose=True, interpret=interpret)

    return jax.vmap(one)(ls)
