"""Blocked right-looking Cholesky as Pallas TPU kernels.

The factorization ``A = LLᵀ`` is the paper's dominant O(d³) cost.  TPU-native
structure (MXU tiles instead of LAPACK panels):

* ``_panel_kernel`` — one pallas_call per tile-column: grid step 0 runs the
  unblocked ``potf2`` on the diagonal tile **and** forms ``L₁₁⁻¹`` in a VMEM
  scratch (persists across the sequential TPU grid); steps i>0 are pure MXU
  GEMMs ``L_{i1} = A_{i1}·L₁₁⁻ᵀ`` (the trsm, recast as a matmul against the
  cached inverse — triangular solves don't vectorize on the MXU, matmuls do).
* ``_syrk_kernel`` — trailing update ``A₂₂ −= L₂₁L₂₁ᵀ`` over the lower tiles
  only (grid masks the strictly-upper tiles to a copy-through).

The JAX-level driver walks tile columns; every FLOP executed between panel
potf2s is a dense ``B×B`` MXU matmul, which is what drives this kernel
toward the compute roofline on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compat import mxu_dot, vmem_scratch

__all__ = ["cholesky_blocked"]


# The TPU lowering has no dynamic slice, so the two in-register recurrences
# below never index at the loop variable: a row or column is picked by an
# iota mask and a reduction, and written back by a masked select.


def _iota(shape, axis: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pick_col(a: jax.Array, k) -> jax.Array:
    """Column ``k`` of ``a`` as a (rows, 1) vector."""
    return jnp.sum(jnp.where(_iota(a.shape, 1) == k, a, 0.0),
                   axis=1, keepdims=True)


def _pick_row(a: jax.Array, k) -> jax.Array:
    """Row ``k`` of ``a`` as a (1, cols) vector."""
    return jnp.sum(jnp.where(_iota(a.shape, 0) == k, a, 0.0),
                   axis=0, keepdims=True)


def _potf2(a: jax.Array) -> jax.Array:
    """Unblocked Cholesky of a B×B tile (functional, in-register).

    Reads only the lower triangle: it is mirrored once, and every rank-1
    update subtracts the symmetric ``c cᵀ``, so row ``k`` of the trailing
    block equals column ``k`` bit for bit and supplies ``cᵀ`` without a
    per-step transpose.
    """
    b = a.shape[0]
    rows, cols = _iota((b, b), 0), _iota((b, b), 1)
    a = jnp.where(rows >= cols, a, a.T)

    def body(k, a):
        col = _pick_col(a, k)                                # (B, 1)
        pivot = jnp.sqrt(_pick_row(col, k))                  # (1, 1)
        c = jnp.where(_iota((b, 1), 0) > k, col / pivot, 0.0)
        c_t = jnp.where(_iota((1, b), 1) > k, _pick_row(a, k) / pivot, 0.0)
        a = jnp.where((rows > k) & (cols > k), a - c * c_t, a)
        return jnp.where(cols == k, jnp.where(rows == k, pivot, c), a)

    a = jax.lax.fori_loop(0, b, body, a)
    return jnp.where(rows >= cols, a, 0.0)


def _inv_lower(l: jax.Array) -> jax.Array:
    """X with L X = I via row-wise forward substitution (in-register)."""
    b = l.shape[0]
    l_t = l.T                 # column k of Lᵀ is row k of L, along sublanes

    def body(k, x):
        row = _pick_col(l_t, k)                              # (B, 1): L[k, :]
        s = jnp.sum(jnp.where(_iota((b, b), 0) < k, x, 0.0) * row,
                    axis=0, keepdims=True)
        e_k = (_iota((1, b), 1) == k).astype(l.dtype)
        new = (e_k - s) / _pick_row(row, k)                  # ÷ L[k, k]
        return jnp.where(_iota((b, b), 0) == k, new, x)

    return jax.lax.fori_loop(0, b, body, jnp.zeros_like(l))


def _make_panel_kernel(compute_dtype=None):
    def kernel(panel_ref, out_ref, inv_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _diag():
            # potf2 + inversion always run at the panel (accumulation)
            # dtype — the sequential recurrences are the unstable half
            l11 = _potf2(panel_ref[...])
            inv_ref[...] = _inv_lower(l11)
            out_ref[...] = l11

        @pl.when(i > 0)
        def _sub():
            # trsm recast as GEMM against the cached inverse: A·(L⁻¹)ᵀ —
            # MXU operands at the compute dtype, fp32+ accumulation
            panel = panel_ref[...]
            inv_t = inv_ref[...].T
            if compute_dtype is not None:
                panel = panel.astype(compute_dtype)
                inv_t = inv_t.astype(compute_dtype)
            out_ref[...] = mxu_dot(panel, inv_t, out_ref.dtype)

    return kernel


def _make_syrk_kernel(compute_dtype=None):
    def kernel(panel_i_ref, panel_j_ref, c_ref, out_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(i >= j)
        def _update():
            pi = panel_i_ref[...]
            pj_t = panel_j_ref[...].T
            if compute_dtype is not None:
                pi = pi.astype(compute_dtype)
                pj_t = pj_t.astype(compute_dtype)
            out_ref[...] = c_ref[...] - mxu_dot(pi, pj_t, out_ref.dtype)

        @pl.when(i < j)
        def _copy():
            out_ref[...] = c_ref[...]

    return kernel


def _factor_panel(panel: jax.Array, block: int, interpret: bool,
                  compute_dtype=None) -> jax.Array:
    m = panel.shape[0]
    nt = m // block
    return pl.pallas_call(
        _make_panel_kernel(compute_dtype),
        grid=(nt,),
        in_specs=[pl.BlockSpec((block, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(panel.shape, panel.dtype),
        scratch_shapes=[vmem_scratch((block, block), panel.dtype)],
        interpret=interpret,
        name="cholesky_panel",
    )(panel)


def _syrk_update(trailing: jax.Array, panel: jax.Array, block: int,
                 interpret: bool, compute_dtype=None) -> jax.Array:
    m = trailing.shape[0]
    nt = m // block
    return pl.pallas_call(
        _make_syrk_kernel(compute_dtype),
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j: (i, 0)),
            pl.BlockSpec((block, block), lambda i, j: (j, 0)),
            pl.BlockSpec((block, block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(trailing.shape, trailing.dtype),
        interpret=interpret,
        name="cholesky_syrk",
    )(panel, panel, trailing)


@functools.partial(jax.jit, static_argnames=("block", "interpret",
                                             "compute_dtype", "accum_dtype"))
def cholesky_blocked(a: jax.Array, block: int = 256, *,
                     interpret: bool | None = None,
                     compute_dtype=None, accum_dtype=None) -> jax.Array:
    """Cholesky factor of SPD ``a`` (h×h) -> lower-triangular L (h×h).

    Mixed precision: the factorization state (panels, trailing matrix, the
    returned L) lives at ``accum_dtype`` — a 16-bit input is promoted, the
    potf2 recurrence never runs in bf16 — while ``compute_dtype`` (when
    given) feeds the syrk/trsm GEMM operands to the MXU at reduced
    precision with full-precision accumulation.  Defaults inherit
    ``a.dtype`` (bit-compatible with the pre-policy kernel).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from .packed_trsm import _resolve_dtypes
    cd, ad = _resolve_dtypes(a.dtype, compute_dtype, accum_dtype)
    a = a.astype(ad)
    cd_gemm = None if cd == ad else cd
    h = a.shape[-1]
    nt = -(-h // block)
    hp = nt * block
    if hp != h:
        # pad with identity on the trailing diagonal — keeps potf2 finite
        a = jnp.pad(a, ((0, hp - h), (0, hp - h)))
        a = a.at[h:, h:].set(jnp.eye(hp - h, dtype=a.dtype))

    out = a
    for j in range(nt):
        lo = j * block
        panel = jax.lax.dynamic_slice(out, (lo, lo), (hp - lo, block))
        panel = _factor_panel(panel, block, interpret, cd_gemm)
        out = jax.lax.dynamic_update_slice(out, panel, (lo, lo))
        if j + 1 < nt:
            sub = jax.lax.dynamic_slice(panel, (block, 0), (hp - lo - block, block))
            trailing = jax.lax.dynamic_slice(
                out, (lo + block, lo + block), (hp - lo - block, hp - lo - block))
            trailing = _syrk_update(trailing, sub, block, interpret, cd_gemm)
            out = jax.lax.dynamic_update_slice(out, trailing, (lo + block, lo + block))
    return jnp.tril(out[:h, :h])
