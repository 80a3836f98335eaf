"""Fused Horner evaluation + triangular unpack / packed solve.

The paper evaluates the D interpolating polynomials into a packed vector and
then unpacks it into L(λ) — two passes over O(d²) data.  On TPU the packed
coefficient tiles Θ (r+1 per tile) can be streamed through VMEM **once**,
Horner-evaluated in registers, and written directly to the unpacked factor
position — halving HBM traffic for the interpolation step (the step §3.3
prices at O(rd²), i.e. memory-bound: arithmetic intensity ≈ r/4 FLOP/byte).

Two fusions live here:

* :func:`interp_factors` — Horner + unpack: grid (q, nt, nt), λ-major so
  each interpolated factor streams out contiguously; the λ value reaches
  the kernel through SMEM.  Still materializes (q, h, h) — the debug /
  dense-consumer path.
* :func:`interp_solve` — Horner + packed trsm: the production sweep path.
  Interpolated tiles are Horner-evaluated in registers *inside* the
  triangular-solve walk of :mod:`repro.kernels.packed_trsm`, so no
  interpolated factor — packed or dense — is ever written to HBM.  Peak
  footprint per λ is one coefficient tile stack ((r+1)·B²) + the (h,)
  solution, which is what makes the chunked λ sweep O(chunk · h) instead
  of O(q · h²).

Mixed precision (:mod:`repro.core.precision`): Θ may arrive stored in bf16;
``compute_dtype`` sets the Horner/GEMM operand dtype (default: Θ's own),
``accum_dtype`` the GEMM accumulation + solution dtype (fp32 on 16-bit
compute).  Diagonal tiles are Horner-evaluated and inverted at the
accumulation dtype before being cast down for the MXU.  ``rhs_per_lam=True``
accepts a per-λ right-hand side (q, h[, m]) — the refinement sweep's
residuals — reusing the kernel's batched-RHS back-substitution path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import SMEM, mxu_dot, vmem_scratch

from repro.core import packing

__all__ = ["interp_factors", "interp_solve"]


def _make_kernel(degree: int):
    def kernel(pidx_ref, lam_ref, theta_ref, out_ref):
        t = pl.program_id(0)
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(i >= j)
        def _lower():
            x = lam_ref[0, t]
            acc = theta_ref[degree, 0]
            for k in range(degree - 1, -1, -1):  # Horner, in registers
                acc = acc * x + theta_ref[k, 0]
            out_ref[0] = acc

        @pl.when(i < j)
        def _upper():
            out_ref[...] = jnp.zeros_like(out_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("h", "block", "interpret"))
def interp_factors(theta: jax.Array, lams: jax.Array, h: int, block: int = 128,
                   *, center: jax.Array | float = 0.0,
                   interpret: bool | None = None) -> jax.Array:
    """Evaluate Θ ((r+1) × P) at λ grid (q,) -> interpolated factors (q, h, h).

    Fuses polynomial evaluation with the packed→triangular unpack.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    degree = theta.shape[0] - 1
    nt = packing.num_tiles(h, block)
    pidx = jnp.asarray(packing.tile_pos_map(h, block).reshape(-1), jnp.int32)

    q = lams.shape[0]
    x = (lams.astype(theta.dtype) - jnp.asarray(center, theta.dtype))
    theta_t = theta.reshape(degree + 1, -1, block, block)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q, nt, nt),
        in_specs=[
            pl.BlockSpec(memory_space=SMEM),  # λ values, (1, q)
            pl.BlockSpec((degree + 1, 1, block, block),
                         lambda t, i, j, pidx: (0, pidx[i * nt + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda t, i, j, pidx: (t, i, j)),
    )
    out = pl.pallas_call(
        _make_kernel(degree),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, nt * block, nt * block), theta.dtype),
        interpret=interpret,
        name="interp_factors",
    )(pidx, x[None], theta_t)
    return out[:, :h, :h]


# ------------------------------------------------- fused Horner + packed trsm


def _make_solve_kernel(degree: int, block: int, nt: int, reverse: bool,
                       rhs_batched: bool):
    def kernel(idx_ref, lam_ref, inv_ref, g_ref, theta_ref, out_ref, acc_ref):
        c = pl.program_id(0)                 # λ index within the chunk
        s = pl.program_id(1)
        u = pl.program_id(2)
        i = (nt - 1 - s) if reverse else s   # tile row being solved
        t = (nt - 1 - u) if reverse else u   # tile column being visited

        @pl.when((s == 0) & (u == 0))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(u == 0)
        def _zero_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        contrib = (t > i) if reverse else (t < i)

        @pl.when(contrib)
        def _accumulate():
            # Horner at the coefficient (compute) dtype: λ is quantized to
            # it per step, the GEMM accumulates at the scratch dtype
            x = lam_ref[0, c].astype(theta_ref.dtype)
            tile = theta_ref[degree, 0]
            for k in range(degree - 1, -1, -1):  # Horner, in registers
                tile = tile * x + theta_ref[k, 0]
            tile = tile.T if reverse else tile
            w_t = out_ref[0, pl.ds(t * block, block), :]
            acc_ref[...] += mxu_dot(tile, w_t.astype(tile.dtype),
                                    acc_ref.dtype)

        @pl.when(t == i)
        def _solve():
            if rhs_batched:
                g_i = g_ref[0, pl.ds(i * block, block), :]
            else:
                g_i = g_ref[pl.ds(i * block, block), :]
            inv = inv_ref[0, 0].T if reverse else inv_ref[0, 0]
            rhs = (g_i - acc_ref[...]).astype(inv.dtype)
            out_ref[0, pl.ds(i * block, block), :] = mxu_dot(
                inv, rhs, out_ref.dtype)

    return kernel


def _interp_sweep(theta_t: jax.Array, x: jax.Array, inv_diag: jax.Array,
                  g: jax.Array, h: int, block: int, reverse: bool,
                  interpret: bool) -> jax.Array:
    """One triangular sweep over all λ: (q, hp, nrhs) ← Horner-fused solve.

    ``g`` is either the shared (hp, nrhs) RHS (forward sweep — the same g
    for every λ, no per-λ broadcast in HBM) or the per-λ (q, hp, nrhs)
    intermediate (back sweep consuming the forward solutions).
    """
    from .packed_trsm import _step_tile_indices

    degree = theta_t.shape[0] - 1
    nt = packing.num_tiles(h, block)
    hp = nt * block
    q = x.shape[0]
    rhs_batched = g.ndim == 3
    nrhs = g.shape[-1]
    idx = jnp.asarray(_step_tile_indices(h, block, reverse))

    def inv_index(c, s, u, idx):
        return (c, (nt - 1 - s) if reverse else s, 0, 0)

    if rhs_batched:
        g_spec = pl.BlockSpec((1, hp, nrhs), lambda c, s, u, idx: (c, 0, 0))
    else:
        g_spec = pl.BlockSpec((hp, nrhs), lambda c, s, u, idx: (0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q, nt, nt),
        in_specs=[
            pl.BlockSpec(memory_space=SMEM),                  # λ values, (1, q)
            pl.BlockSpec((1, 1, block, block), inv_index),
            g_spec,
            pl.BlockSpec((degree + 1, 1, block, block),
                         lambda c, s, u, idx: (0, idx[s * nt + u], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hp, nrhs), lambda c, s, u, idx: (c, 0, 0)),
        scratch_shapes=[vmem_scratch((block, nrhs), g.dtype)],
    )
    # λ goes in as (1, q): under a fold vmap its block then still spans the
    # array's last two dims, as the TPU lowering requires of an SMEM block
    return pl.pallas_call(
        _make_solve_kernel(degree, block, nt, reverse, rhs_batched),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, hp, nrhs), g.dtype),
        interpret=interpret,
        name="interp_solve_bwd" if reverse else "interp_solve_fwd",
    )(idx, x[None], inv_diag, g, theta_t)


@functools.partial(jax.jit, static_argnames=("h", "block", "interpret",
                                             "rhs_per_lam", "compute_dtype",
                                             "accum_dtype"))
def interp_solve(theta: jax.Array, lams: jax.Array, g: jax.Array, h: int,
                 block: int = 128, *, center: jax.Array | float = 0.0,
                 interpret: bool | None = None, rhs_per_lam: bool = False,
                 compute_dtype=None, accum_dtype=None) -> jax.Array:
    """Solve L(λ) L(λ)ᵀ θ = g at every λ without materializing any L(λ).

    ``theta``: (r+1, P) packed interpolant coefficients; ``lams``: (q,);
    ``g``: (h,) or (h, m) shared RHS — or, with ``rhs_per_lam=True``, a
    per-λ RHS (q, h) / (q, h, m) (the refinement residuals).  Returns
    (q, h) (or (q, h, m)) in the accumulation dtype.  The interpolated
    factor exists only tile-by-tile in registers: the only O(h²) buffer in
    the whole sweep is Θ itself, which is q-independent — and stays at its
    storage dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from .packed_trsm import _resolve_dtypes
    cd, ad = _resolve_dtypes(theta.dtype, compute_dtype, accum_dtype)
    degree = theta.shape[0] - 1
    nt = packing.num_tiles(h, block)
    hp = nt * block
    if rhs_per_lam:
        squeeze = g.ndim == 2                      # (q, h) -> (q, h, 1)
        g2 = (g[..., None] if squeeze else g).astype(ad)
        if hp != h:
            g2 = jnp.pad(g2, ((0, 0), (0, hp - h), (0, 0)))
    else:
        squeeze = g.ndim == 1
        g2 = (g[:, None] if squeeze else g).astype(ad)
        if hp != h:
            g2 = jnp.pad(g2, ((0, hp - h), (0, 0)))

    x = (lams.astype(ad) - jnp.asarray(center, ad))
    theta_t = theta.astype(cd).reshape(degree + 1, -1, block, block)

    # Diagonal tiles are the only place substitution needs an inverse, so
    # they alone are interpolated ahead of the sweep: (q, nt, B, B) — O(q·h·B)
    # not O(q·h²) — then pre-inverted (identity-padded tail, shared by both
    # sweeps via transposition).  Horner + inversion run at the accumulation
    # dtype (inverting bf16-rounded triangles in bf16 is the unstable half),
    # the inverses feed the MXU at the compute dtype.
    diag_coeff = theta.reshape(degree + 1, -1, block, block
                               )[:, packing.column_starts(h, block)].astype(ad)
    diag = diag_coeff[degree]
    for k in range(degree - 1, -1, -1):
        diag = diag * x[:, None, None, None] + diag_coeff[k]
    tail = packing._identity_tail(h, block)
    if tail.any():
        diag = diag.at[:, nt - 1].add(jnp.asarray(tail, diag.dtype))
    inv_diag = packing.invert_diag_tiles(diag).astype(cd)

    w = _interp_sweep(theta_t, x, inv_diag, g2, h, block, False, interpret)
    out = _interp_sweep(theta_t, x, inv_diag, w, h, block, True, interpret)
    out = out[:, :h]
    return out[..., 0] if squeeze else out

