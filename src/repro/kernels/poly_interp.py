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
  The triangular-solve walk of :mod:`repro.kernels.packed_trsm` reads each
  Θ tile stack once per λ chunk and applies it to every λ of the chunk at
  once: L(λ)·w_λ = Σ_k x_λ^k (Θ_k·w_λ), one MXU product per coefficient
  tile with the chunk's λs (and right-hand sides) as its rows, Horner over
  k on the products.  No interpolated factor — packed or dense — is ever
  written to HBM; the λ-dependent footprint is the chunk's diagonal
  inverses and solution rows in VMEM (:func:`sweep_vmem_bytes`), which
  sizes the chunk (:func:`sweep_lam_chunk`).

Mixed precision (:mod:`repro.core.precision`): Θ may arrive stored in bf16;
``compute_dtype`` sets the MXU operand dtype (default: Θ's own),
``accum_dtype`` the product accumulation, Horner and solution dtype (fp32
on 16-bit compute).  Diagonal tiles are Horner-evaluated and inverted at
the accumulation dtype before being cast down for the MXU.
``rhs_per_lam=True`` accepts a per-λ right-hand side (q, h[, m]) — the
refinement sweep's residuals — as the rows of the same block.
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

#: VMEM one λ-batched sweep may plan to hold: v5e's default scoped VMEM
#: (16 MiB) less room for what Mosaic keeps beside the blocks.
SWEEP_VMEM_BYTES = 12 * 1024 * 1024


def _row_tile(itemsize: int) -> int:
    """Rows of one VMEM tile at ``itemsize`` (8 for 32-bit, 16 for bf16)."""
    return 8 * max(1, 4 // itemsize)


def sweep_vmem_bytes(h: int, block: int, n_lam: int, n_rhs: int,
                     degree: int, compute_dtype, accum_dtype) -> int:
    """VMEM one sweep of :func:`interp_solve` holds for ``n_lam`` λs of
    ``n_rhs`` right-hand sides each: every blocked operand twice (the
    pipeline's double buffer), the accumulator once.  Per λ that is its
    diagonal inverses (2·B² at the compute dtype) and its solution rows
    (in and out, 4·m·h at the accumulation dtype); Θ's tile stack is
    shared by every λ."""
    cd = jnp.dtype(compute_dtype).itemsize
    ad = jnp.dtype(accum_dtype).itemsize
    hp = packing.num_tiles(h, block) * block
    rows = -(-n_lam * n_rhs // _row_tile(ad)) * _row_tile(ad)
    solution = 2 * 2 * rows * hp * ad          # right-hand side in, out
    inverses = 2 * n_lam * block * block * cd
    theta = 2 * (degree + 1) * block * block * cd
    lam_rows = 2 * rows * 128 * ad             # x per row, lane-padded
    return solution + inverses + theta + lam_rows + rows * block * ad


def sweep_lam_chunk(h: int, block: int, q: int, n_rhs: int, degree: int,
                    compute_dtype, accum_dtype) -> int:
    """Most λs, at most ``q`` and at least 1, one sweep applies per Θ read
    within :data:`SWEEP_VMEM_BYTES` (:func:`sweep_vmem_bytes`)."""
    lo, hi = 1, max(1, q)
    while lo < hi:              # the working set grows with the λ count
        mid = (lo + hi + 1) // 2
        if sweep_vmem_bytes(h, block, mid, n_rhs, degree, compute_dtype,
                            accum_dtype) <= SWEEP_VMEM_BYTES:
            lo = mid
        else:
            hi = mid - 1
    return lo


def _make_solve_kernel(degree: int, nt: int, reverse: bool, n_lam: int,
                       n_rhs: int):
    # The solution is held transposed, one row per (λ, right-hand side)
    # and the tile's B entries along lanes, so L_it·w is w·L_itᵀ (forward)
    # and L_tiᵀ·w is w·L_ti (back): no tile is ever transposed.
    trans = not reverse

    def kernel(idx_ref, x_ref, inv_ref, g_ref, theta_ref, out_ref, acc_ref):
        s = pl.program_id(0)
        u = pl.program_id(1)
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
            # L(λ)·w = Σ_k x^k (Θ_k·w): one MXU product per coefficient
            # tile for every λ of the chunk, Horner over k on the products
            w_t = out_ref[t].astype(theta_ref.dtype)
            x = x_ref[...]                                  # (rows, 1)
            acc = mxu_dot(w_t, theta_ref[degree, 0], acc_ref.dtype,
                          transpose_b=trans)
            for k in range(degree - 1, -1, -1):
                acc = acc * x + mxu_dot(w_t, theta_ref[k, 0], acc_ref.dtype,
                                        transpose_b=trans)
            acc_ref[...] += acc

        @pl.when(t == i)
        def _solve():
            @pl.loop(0, n_lam)
            def _(c):                # each λ's rows by its own inverse
                rows = pl.ds(c * n_rhs, n_rhs)
                rhs = (g_ref[i, rows, :] - acc_ref[rows, :]
                       ).astype(inv_ref.dtype)
                out_ref[i, rows, :] = mxu_dot(rhs, inv_ref[0, c],
                                              out_ref.dtype,
                                              transpose_b=trans)

    return kernel


def _interp_sweep(theta_t: jax.Array, x_rows: jax.Array, inv_diag: jax.Array,
                  g: jax.Array, h: int, block: int, reverse: bool,
                  n_rhs: int, interpret: bool) -> jax.Array:
    """One triangular sweep over a λ chunk: (nt, rows, B) ← Horner-fused
    solve, rows = λ-major (λ, right-hand side) pairs.

    Each Θ tile stack is read once and applied to every row; ``inv_diag``
    is (nt, c, B, B), the c λs' inverse diagonal tiles of each tile row.
    """
    from .packed_trsm import _step_tile_indices

    degree = theta_t.shape[0] - 1
    nt = packing.num_tiles(h, block)
    rows = g.shape[1]
    n_lam = inv_diag.shape[1]
    idx = jnp.asarray(_step_tile_indices(h, block, reverse))

    def whole(s, u, idx):
        return (0, 0, 0)

    def inv_index(s, u, idx):
        return ((nt - 1 - s) if reverse else s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda s, u, idx: (0, 0)),   # x per row
            pl.BlockSpec((1, n_lam, block, block), inv_index),
            pl.BlockSpec((nt, rows, block), whole),
            pl.BlockSpec((degree + 1, 1, block, block),
                         lambda s, u, idx: (0, idx[s * nt + u], 0, 0)),
        ],
        out_specs=pl.BlockSpec((nt, rows, block), whole),
        scratch_shapes=[vmem_scratch((rows, block), g.dtype)],
    )
    return pl.pallas_call(
        _make_solve_kernel(degree, nt, reverse, n_lam, n_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        interpret=interpret,
        name="interp_solve_bwd" if reverse else "interp_solve_fwd",
    )(idx, x_rows, inv_diag, g, theta_t)


def _solve_chunk(theta: jax.Array, x: jax.Array, g: jax.Array, h: int,
                 block: int, cd, ad, interpret: bool) -> jax.Array:
    """(c, h, m) solutions at the c shifts ``x`` for right-hand sides
    ``g`` (c, h, m): both sweeps, one Θ read per sweep for the chunk."""
    degree = theta.shape[0] - 1
    nt = packing.num_tiles(h, block)
    hp = nt * block
    c, _, m = g.shape
    theta_t = theta.astype(cd).reshape(degree + 1, -1, block, block)

    # Diagonal tiles are the only place substitution needs an inverse, so
    # they alone are interpolated ahead of the sweep: (nt, c, B, B) — O(c·h·B)
    # not O(c·h²) — then pre-inverted (identity-padded tail, shared by both
    # sweeps via transposition).  Horner + inversion run at the accumulation
    # dtype (inverting bf16-rounded triangles in bf16 is the unstable half),
    # the inverses feed the MXU at the compute dtype.
    diag_coeff = theta.reshape(degree + 1, -1, block, block
                               )[:, packing.column_starts(h, block), None
                                 ].astype(ad)
    diag = diag_coeff[degree]
    for k in range(degree - 1, -1, -1):
        diag = diag * x[:, None, None] + diag_coeff[k]
    diag = jnp.broadcast_to(diag, (nt, c, block, block))
    tail = packing._identity_tail(h, block)
    if tail.any():
        diag = diag.at[nt - 1].add(jnp.asarray(tail, diag.dtype))
    inv_diag = packing.invert_diag_tiles(diag).astype(cd)

    # row λ·m + j holds right-hand side j of λ, its h entries in B-wide
    # tiles: (nt, c·m, B)
    rows = jnp.pad(jnp.swapaxes(g, 1, 2).reshape(c * m, h),
                   ((0, 0), (0, hp - h)))
    rows = jnp.swapaxes(rows.reshape(c * m, nt, block), 0, 1)
    x_rows = jnp.repeat(x, m)[:, None]
    w = _interp_sweep(theta_t, x_rows, inv_diag, rows, h, block, False, m,
                      interpret)
    out = _interp_sweep(theta_t, x_rows, inv_diag, w, h, block, True, m,
                        interpret)
    out = jnp.swapaxes(out, 0, 1).reshape(c, m, hp)[:, :, :h]
    return jnp.swapaxes(out, 1, 2)


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
    factor exists only as products with the solution: the only O(h²)
    buffer in the whole sweep is Θ itself, which is q-independent — and
    stays at its storage dtype.  λs go through the kernel in chunks of
    :func:`sweep_lam_chunk` (all of them when they fit its VMEM), each
    chunk reading every Θ tile once per sweep.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from .packed_trsm import _resolve_dtypes
    cd, ad = _resolve_dtypes(theta.dtype, compute_dtype, accum_dtype)
    q = lams.shape[0]
    if rhs_per_lam:
        squeeze = g.ndim == 2                      # (q, h) -> (q, h, 1)
        g3 = (g[..., None] if squeeze else g).astype(ad)
    else:
        squeeze = g.ndim == 1
        g2 = (g[:, None] if squeeze else g).astype(ad)
        g3 = jnp.broadcast_to(g2, (q,) + g2.shape)
    x = lams.astype(ad) - jnp.asarray(center, ad)

    chunk = sweep_lam_chunk(h, block, q, g3.shape[-1], theta.shape[0] - 1,
                            cd, ad)
    solve = functools.partial(_solve_chunk, theta, h=h, block=block, cd=cd,
                              ad=ad, interpret=interpret)
    if chunk >= q:
        out = solve(x, g3)
    else:   # more λs than one sweep's VMEM holds: chunks in sequence
        n_c = -(-q // chunk)
        pad = n_c * chunk - q
        xs = jnp.pad(x, (0, pad), mode="edge").reshape(n_c, chunk)
        gs = jnp.pad(g3, ((0, pad), (0, 0), (0, 0))).reshape(
            (n_c, chunk) + g3.shape[1:])
        out = jax.lax.map(lambda a: solve(*a), (xs, gs))
        out = out.reshape((n_c * chunk,) + out.shape[2:])[:q]
    return out[..., 0] if squeeze else out
