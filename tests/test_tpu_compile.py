"""The main path's Pallas kernels compile for a TPU v5e chip at h=4096.

The chip is described, not attached: the TPU compiler that ships with
libtpu compiles each kernel for one device of a ``v5e:2x2`` topology, which
refuses what interpret mode accepts (unsupported primitives, VMEM
overflow).  Nothing runs.  The topology is described in a fixture, never at
import, so every test worker collects the same tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.kernels.chol_blocked import cholesky_blocked
from repro.kernels.packed_trsm import solve_packed
from repro.kernels.poly_interp import interp_solve
from repro.kernels.tri_pack import pack_tril
from repro.kernels.trsm import solve_lower_blocked

H = 4096
BLOCK = 128
DEGREE = 2
Q = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e device, compiled for as the chip runs: without x64 and
    without the persistent cache (a compile for a described chip cannot
    be read back from it)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel
    return compiled


PACKED = packing.num_tiles(H, BLOCK) * (packing.num_tiles(H, BLOCK) + 1) \
    // 2 * BLOCK * BLOCK


@pytest.mark.parametrize("block", [128, 256])
def test_cholesky_blocked_compiles(one_chip, block):
    _compile(functools.partial(cholesky_blocked, block=block,
                               interpret=False),
             _spec(one_chip, (H, H)))


def test_pack_tril_compiles(one_chip):
    _compile(functools.partial(pack_tril, block=BLOCK, interpret=False),
             _spec(one_chip, (H, H)))


def test_solve_packed_compiles(one_chip):
    _compile(lambda v, g: solve_packed(v, g, H, BLOCK, interpret=False),
             _spec(one_chip, (PACKED,)), _spec(one_chip, (H,)))


@pytest.mark.parametrize("q", [Q, 31])   # 31: the paper grid in one call
@pytest.mark.parametrize("matmul_precision", [None, "highest"])
@pytest.mark.parametrize("compute", [None, "bfloat16"])
def test_interp_solve_compiles(one_chip, compute, matmul_precision, q):
    # the engine traces its stages under "highest"; Mosaic refuses that
    # precision for bf16 operands, so the kernel must choose its own
    store = jnp.float32 if compute is None else jnp.bfloat16
    accum = None if compute is None else "float32"
    with jax.default_matmul_precision(matmul_precision):
        _compile(lambda t, lam, g: interp_solve(
                     t, lam, g, H, BLOCK, interpret=False,
                     compute_dtype=compute, accum_dtype=accum),
                 _spec(one_chip, (DEGREE + 1, PACKED), store),
                 _spec(one_chip, (q,)), _spec(one_chip, (H,)))


def test_solve_lower_blocked_compiles(one_chip):
    _compile(lambda l, g: solve_lower_blocked(l, g, 256, interpret=False),
             _spec(one_chip, (H, H)), _spec(one_chip, (H,)))
