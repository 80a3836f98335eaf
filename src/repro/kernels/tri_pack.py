"""Pallas TPU kernels for tile-major triangular packing (paper §5, TPU form).

The pack/unpack are pure data-movement kernels: every grid step copies one
aligned ``B×B`` VMEM tile; the (i,j) ↔ packed-index maps are scalar-prefetched
so the index computation costs nothing on the compute units.  This is the
TPU analogue of the paper's recursive vectorization — alignment unit is the
128-lane tile instead of a cache line, and only the ``nt(nt+1)/2`` lower
tiles move (requirement (ii): no redundant interpolation work downstream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing

__all__ = ["pack_tril", "unpack_tril"]


def _pack_kernel(idx_ref, mat_ref, out_ref):
    p = pl.program_id(0)
    i = idx_ref[0, p]
    j = idx_ref[1, p]
    tile = mat_ref[...]
    b = tile.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    # Diagonal tiles keep only their lower triangle (alignment padding = 0).
    masked = jnp.where(rows >= cols, tile, jnp.zeros_like(tile))
    out_ref[0] = jnp.where(i == j, masked, tile)


def _unpack_kernel(pidx_ref, packed_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(i >= j)
    def _lower():
        out_ref[...] = packed_ref[0]

    @pl.when(i < j)
    def _upper():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pack_tril(mat: jax.Array, block: int = 128, *, interpret: bool | None = None) -> jax.Array:
    """Pack tril(mat) (h×h) into the tile-major packed vector (P,)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    h = mat.shape[-1]
    nt = packing.num_tiles(h, block)
    pad = nt * block - h
    if pad:
        mat = jnp.pad(mat, ((0, pad), (0, pad)))
    ii, jj = packing.tile_index_pairs(h, block)
    idx = jnp.asarray(np.stack([ii, jj]), jnp.int32)  # (2, P)
    n_blocks = len(ii)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, block), lambda p, idx: (idx[0, p], idx[1, p])),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda p, idx: (p, 0, 0)),
    )
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, block, block), mat.dtype),
        interpret=interpret,
        name="pack_tril",
    )(idx, mat)
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("h", "block", "interpret"))
def unpack_tril(vec: jax.Array, h: int, block: int = 128, *, interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`pack_tril`: (P,) -> (h, h) lower-triangular."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    nt = packing.num_tiles(h, block)
    ii, jj = packing.tile_index_pairs(h, block)
    # map (i, j) -> packed block index (0 for unused upper blocks)
    pmap = np.zeros((nt, nt), np.int32)
    for p, (i, j) in enumerate(zip(ii, jj)):
        pmap[i, j] = p
    pidx = jnp.asarray(pmap.reshape(-1), jnp.int32)
    packed = vec.reshape(-1, block, block)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((1, block, block), lambda i, j, pidx: (pidx[i * nt + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j, pidx: (i, j)),
    )
    out = pl.pallas_call(
        _unpack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nt * block, nt * block), vec.dtype),
        interpret=interpret,
        name="unpack_tril",
    )(pidx, packed)
    return out[:h, :h]
