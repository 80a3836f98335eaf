"""The main path's Pallas kernels compile for a TPU v5e chip at h=4096,
and the engine's sweep with its state stage divided over the four chips
of a v5e host compiles for them.

The chip is described, not attached: the TPU compiler that ships with
libtpu compiles each kernel for one device of a ``v5e:2x2`` topology, which
refuses what interpret mode accepts (unsupported primitives, VMEM
overflow), and the sharded sweep for all four.  Nothing runs.  The
topology is described in a fixture, never at import, so every test worker
collects the same tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import CVEngine, packing
from repro.core.engine import PiCholeskyStrategy
from repro.distributed import sharding as shardlib
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
def as_the_chip():
    """Compile as the chip runs: without x64 and without the persistent
    cache (a compile for a described chip cannot be read back from it)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, as_the_chip):
    """One v5e device."""
    return SingleDeviceSharding(topo.devices[0])


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


# ------------------------------------------- the sweep on a four-chip host

#: The sweep's h here: the divided program at h=8192 compiles on a host CPU
#: in about 90 s, at h=4096 in about 28 s.  Every term of both programs'
#: memory is of order h² (n = 4h rows), so a reading here is scaled to
#: h=8192 by (8192 / SWEEP_H)².
SWEEP_H = 4096
SCALE = (8192 / SWEEP_H) ** 2
HBM = 15.75 * 2**30          # what XLA lets a v5e program use


def _sweep_bytes(topo, monkeypatch, divided: bool) -> tuple:
    """(per-device argument bytes, temporary bytes, compiled text) of the fused
    sweep of the paper's grid (k=5, q=31 padded to 32, g=4, degree 2,
    block 128, f32, n=4h) on the (folds 1 × lams 4) mesh the engine
    builds for 5 folds on 4 chips.  Divided, each chip is handed its
    pairs' 2 folds of the train Hessians and no hold-out row; undivided,
    as before the state stage was divided, every chip runs all 20
    factorizations on every fold and scores them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k, h = 5, SWEEP_H
    mesh = shardlib.make_cv_mesh(k, topo.devices)
    assert dict(mesh.shape) == {"folds": 1, "lams": 4}
    eng = CVEngine(PiCholeskyStrategy(g=4, degree=2, block=128),
                   backend="pallas", precision="fp32", mesh=mesh)
    if not divided:
        monkeypatch.setattr(eng, "_state_division", lambda m: 1)
    repl = NamedSharding(mesh, PartitionSpec())
    n_f = 4 * h // k

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=repl)

    compiled = eng._lower_sweep(
        mesh, spec(k, h, h), spec(k, h), spec(k, n_f, h), spec(k, n_f),
        spec(32)).compile()
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
            compiled.as_text())


def test_divided_sweep_compiles_for_four_chips(topo, as_the_chip,
                                               monkeypatch):
    """At h=8192 it reads 5.34 GB a chip (0.54 of arguments, its pairs'
    2 folds of the train Hessians; 4.80 of temporaries); the bound leaves
    room on device 0 for the harness's two designs.  Handed every fold
    and the hold-out rows, the arguments read 2.42 GB."""
    args, temp, text = _sweep_bytes(topo, monkeypatch, divided=True)
    assert (args + temp) * SCALE <= 9e9, (args, temp)
    assert args * SCALE <= 0.6e9, args
    assert "all-to-all" in text and "all-gather" in text
    assert "tpu_custom_call" in text


def test_undivided_sweep_does_not_fit_a_chip_at_h8192(topo, as_the_chip,
                                                      monkeypatch):
    """Every chip repeating the whole state stage holds what one chip
    would: at h=8192, 16.79 GiB of 15.75 (refused at compile)."""
    args, temp, text = _sweep_bytes(topo, monkeypatch, divided=False)
    per_device = args + temp
    assert per_device * SCALE > HBM, per_device
    assert "all-to-all" not in text
    # the default mesh rule's estimate reads the same verdicts
    one_device = shardlib.sweep_bytes(5, 4, SWEEP_H, 4 * SWEEP_H, 4)
    assert one_device <= HBM < shardlib.sweep_bytes(5, 4, 8192, 4 * 8192, 4)
    assert 0.75 <= one_device / per_device <= 1.25


def test_scoring_compiles_for_one_chip(topo, as_the_chip, monkeypatch):
    """The divided sweep's solutions are scored on the designs' chip, at
    full matmul precision: at h=8192 its arguments are the 1.07 GB of
    hold-out rows and 5.2 MB of θ, with no temporaries."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k, h = 5, SWEEP_H
    n_f = 4 * h // k
    one = SingleDeviceSharding(topo.devices[0])
    eng = CVEngine(PiCholeskyStrategy(g=4, degree=2, block=128),
                   backend="pallas", precision="fp32")
    compiled = eng._score_fn().lower(
        _spec(one, (k, 32, h)), _spec(one, (k, n_f, h)),
        _spec(one, (k, n_f))).compile()
    mem = compiled.memory_analysis()
    least = 4 * k * (32 * h + n_f * h + n_f)   # before the chip's tiling
    assert least <= mem.argument_size_in_bytes <= 1.01 * least
    assert mem.temp_size_in_bytes == 0
    dots = [line for line in compiled.as_text().splitlines()
            if "cv.score" in line and "operand_precision" in line]
    assert dots and all("operand_precision={highest,highest}" in line
                        for line in dots)
