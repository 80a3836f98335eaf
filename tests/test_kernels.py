"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

All Pallas kernels run in interpret mode on CPU (the TPU lowering is the
same kernel body with real BlockSpecs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import packing, picholesky
from repro.kernels import ref
from repro.kernels.chol_blocked import cholesky_blocked
from repro.kernels.packed_trsm import solve_lower_packed, solve_packed
from repro.kernels.poly_interp import interp_factors, interp_solve
from repro.kernels.tri_pack import pack_tril, unpack_tril
from repro.kernels.trsm import solve_lower_blocked, solve_factor_sweep


def _spd(h, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2 * h, h), jnp.float32)
    a = x.T @ x + h * jnp.eye(h)
    return a.astype(dtype)


@pytest.mark.parametrize("h", [16, 24, 37, 64])
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_tri_pack_kernel(h, block, dtype):
    m = jax.random.normal(jax.random.PRNGKey(h), (h, h), jnp.float32).astype(dtype)
    v = pack_tril(m, block)
    np.testing.assert_allclose(v, ref.pack_tril(m, block), rtol=1e-6)
    back = unpack_tril(v, h, block)
    np.testing.assert_allclose(back, jnp.tril(m), rtol=1e-6)


@pytest.mark.parametrize("h,block", [(16, 8), (37, 8), (64, 16), (100, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_cholesky_kernel(h, block, dtype):
    a = _spd(h, dtype)
    l = cholesky_blocked(a, block=block)
    l_ref = ref.cholesky(a)
    tol = 5e-5 if dtype == jnp.float32 else 1e-10
    err = float(jnp.max(jnp.abs(l - l_ref)) / jnp.max(jnp.abs(l_ref)))
    assert err < tol


@given(h=st.sampled_from([16, 33, 48]), seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_cholesky_kernel_property(h, seed):
    """L Lᵀ must reconstruct A (system invariant, any SPD input)."""
    a = _spd(h, jnp.float64, seed)
    l = cholesky_blocked(a, block=8)
    np.testing.assert_allclose(l @ l.T, a, rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("h,block,q", [(32, 8, 1), (37, 8, 5), (64, 16, 31)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_trsm_kernel(h, block, q, dtype):
    a = _spd(h, dtype)
    l = jnp.linalg.cholesky(a)
    g = jax.random.normal(jax.random.PRNGKey(1), (h, q), jnp.float32).astype(dtype)
    tol = 1e-3 if dtype == jnp.float32 else 1e-9
    w = solve_lower_blocked(l, g, block)
    np.testing.assert_allclose(w, ref.solve_lower(l, g), rtol=tol, atol=tol)
    t = solve_lower_blocked(l, w, block, transpose=True)
    np.testing.assert_allclose(t, ref.solve_lower(l, w, transpose=True),
                               rtol=tol, atol=tol)


def test_solve_factor_sweep_kernel():
    h, q = 48, 7
    a = _spd(h, jnp.float32)
    lams = jnp.logspace(-2, 0, q)
    ls = jax.vmap(lambda lam: jnp.linalg.cholesky(a + lam * jnp.eye(h)))(lams)
    g = jax.random.normal(jax.random.PRNGKey(3), (h,), jnp.float32)
    thetas = solve_factor_sweep(ls, g, block=16)
    np.testing.assert_allclose(thetas, ref.solve_factor_sweep(ls, g),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("h,block,q", [(16, 8, 1), (37, 8, 5), (64, 16, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_packed_trsm_kernel(h, block, q, dtype):
    """Packed-domain trsm ≡ the pure-jnp packed oracle, both sweeps."""
    a = _spd(h, dtype)
    l = jnp.linalg.cholesky(a.astype(jnp.float64)).astype(dtype)
    vec = packing.pack_tril(l, block)
    g = jax.random.normal(jax.random.PRNGKey(1), (h, q),
                          jnp.float32).astype(dtype)
    tol = 1e-3 if dtype == jnp.float32 else 1e-9
    for transpose in (False, True):
        w = solve_lower_packed(vec, g, h, block, transpose=transpose)
        np.testing.assert_allclose(
            w, ref.solve_lower_packed(vec, g, h, block, transpose=transpose),
            rtol=tol, atol=tol)
    th = solve_packed(vec, g[:, 0], h, block)
    np.testing.assert_allclose(th, ref.solve_packed(vec, g[:, 0], h, block),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("h,block,degree", [(32, 8, 2), (48, 16, 3)])
def test_interp_solve_kernel(h, block, degree):
    """Fused Horner + packed substitution ≡ eval_packed → packed solve."""
    a = _spd(h, jnp.float64)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, degree + 3)
    model = picholesky.fit(a, sample, degree, block=block)
    lams = jnp.logspace(-2, 0, 9)
    g = jax.random.normal(jax.random.PRNGKey(5), (h,), jnp.float64)
    out = interp_solve(model.theta, lams, g, h, block, center=model.center)
    expect = ref.interp_solve(model.theta, lams, g, h, block,
                              center=model.center)
    np.testing.assert_allclose(out, expect, rtol=1e-8, atol=1e-8)
    # and against the exact dense solves at the sample nodes themselves,
    # where the interpolant passes through the data (g > degree fit is
    # least-squares, so compare interpolant-to-interpolant elsewhere)
    dense = model.eval_factor(lams)
    exact = jax.vmap(lambda l: ref.solve_lower(
        l, ref.solve_lower(l, g), transpose=True))(dense)
    np.testing.assert_allclose(out, exact, rtol=1e-6, atol=1e-8)


# (degree, h, block, m, rhs_per_lam, q, chunk, compute): h a multiple of
# the block and not (identity tail), m right-hand sides, q % chunk ≠ 0
LAM_BATCH_CASES = [
    (1, 32, 8, 1, False, 1, 1, None),
    (2, 32, 8, 1, False, 5, 2, None),
    (2, 37, 8, 3, False, 8, 3, None),
    (3, 37, 8, 1, True, 8, 5, None),
    (3, 32, 8, 3, True, 5, 3, None),
    (1, 37, 8, 3, True, 5, 2, None),
    (2, 48, 16, 1, False, 8, 3, "bfloat16"),
    (1, 37, 8, 3, True, 5, 2, "bfloat16"),
    (3, 32, 8, 1, True, 8, 3, "bfloat16"),
    (2, 37, 8, 1, False, 1, 1, "bfloat16"),
    (3, 37, 8, 3, False, 8, 5, "bfloat16"),
]


@pytest.mark.parametrize(
    "degree,h,block,m,rhs_per_lam,q,chunk,compute", LAM_BATCH_CASES)
def test_interp_solve_lam_batched(degree, h, block, m, rhs_per_lam, q,
                                  chunk, compute):
    """Every λ of a call shares each Θ read: the batched solve ≡ the
    reference (packed rows, then packed substitution), ≡ the same call
    one λ at a time, ≡ the grid in edge-padded chunks."""
    from repro.core.backends import ReferenceBackend
    from repro.core.precision import PRESETS
    from repro.distributed import sharding as shardlib

    a = _spd(h, jnp.float32)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, degree + 3)
    model = picholesky.fit(a, sample, degree, block=block)
    lams = jnp.logspace(-2, 0, q, dtype=jnp.float32)
    shape = ((q,) if rhs_per_lam else ()) + (h,) + ((m,) if m > 1 else ())
    g = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    if compute is None:
        theta, kw, policy, tol = model.theta.astype(jnp.float32), {}, \
            "fp32", 1e-5
    else:   # bf16 storage and MXU operands, f32 accumulation
        theta = model.theta.astype(jnp.bfloat16)
        kw = dict(compute_dtype=compute, accum_dtype="float32")
        policy, tol = "bf16_store", 3e-2

    def solve(lam, rhs):
        return interp_solve(theta, lam, rhs, h, block, center=model.center,
                            rhs_per_lam=rhs_per_lam, **kw)

    out = solve(lams, g)
    assert out.shape == (q,) + shape[int(rhs_per_lam):]
    assert out.dtype == jnp.float32
    expect = ReferenceBackend(precision=PRESETS[policy]).interp_solve(
        theta, lams, g, h=h, block=block, center=model.center,
        rhs_per_lam=rhs_per_lam)
    scale = float(jnp.max(jnp.abs(expect)))
    np.testing.assert_allclose(out, expect, rtol=0, atol=tol * scale)

    one = jnp.concatenate([solve(lams[i:i + 1],
                                 g[i:i + 1] if rhs_per_lam else g)
                           for i in range(q)])
    np.testing.assert_allclose(out, one, rtol=0, atol=1e-6 * scale)
    lam_c, _ = shardlib.chunk_lams(lams, chunk)
    g_c = (jnp.pad(g, [(0, lam_c.size - q)] + [(0, 0)] * (g.ndim - 1))
           if rhs_per_lam else None)
    chunked = jnp.concatenate([
        solve(lam_c[j], g_c[j * chunk:(j + 1) * chunk] if rhs_per_lam else g)
        for j in range(lam_c.shape[0])])[:q]
    np.testing.assert_allclose(out, chunked, rtol=0, atol=1e-6 * scale)


def test_interp_solve_splits_lams_beyond_its_vmem(monkeypatch):
    """More λs than one sweep's VMEM holds go through the kernel in
    chunks, with the same solutions."""
    from repro.kernels import poly_interp

    h, block, q = 29, 8, 7
    a = _spd(h, jnp.float32)
    model = picholesky.fit(a, picholesky.choose_sample_lambdas(1e-2, 1.0, 5),
                           2, block=block)
    theta = model.theta.astype(jnp.float32)
    lams = jnp.logspace(-2, 0, q, dtype=jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), (h,), jnp.float32)
    whole = interp_solve(theta, lams, g, h, block, center=model.center)
    budget = poly_interp.sweep_vmem_bytes(h, block, 3, 1, 2, jnp.float32,
                                          jnp.float32)
    monkeypatch.setattr(poly_interp, "SWEEP_VMEM_BYTES", budget)
    assert poly_interp.sweep_lam_chunk(h, block, q, 1, 2, jnp.float32,
                                       jnp.float32) == 3
    interp_solve.clear_cache()
    split = interp_solve(theta, lams, g, h, block, center=model.center)
    interp_solve.clear_cache()
    np.testing.assert_allclose(split, whole, rtol=0,
                               atol=1e-6 * float(jnp.max(jnp.abs(whole))))


@pytest.mark.parametrize("h,block,degree", [(32, 8, 2), (48, 16, 3)])
def test_poly_interp_kernel(h, block, degree):
    a = _spd(h, jnp.float32)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, degree + 3)
    model = picholesky.fit(a, sample, degree, block=block)
    lams = jnp.logspace(-2, 0, 9)
    out = interp_factors(model.theta, lams, h, block, center=model.center)
    expect = ref.interp_factors(model.theta, lams, h, block)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def test_end_to_end_kernel_pipeline():
    """chol kernel -> pack kernel -> fit -> fused interp -> trsm solve,
    matching the all-jnp pipeline."""
    h, block = 64, 16
    a = _spd(h, jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(9), (h,), jnp.float32)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, 5)
    eye = jnp.eye(h)
    factors = jax.vmap(lambda lam: cholesky_blocked(a + lam * eye, block=block)
                       )(sample)
    model = picholesky.fit(a, sample, 2, block=block, factors=factors)
    lams = jnp.logspace(-2, 0, 5)
    ls = interp_factors(model.theta, lams, h, block, center=model.center)
    thetas = solve_factor_sweep(ls, g, block=block)
    expect = jax.vmap(
        lambda lam: ref.solve_lower(
            jnp.linalg.cholesky(a + lam * eye),
            ref.solve_lower(jnp.linalg.cholesky(a + lam * eye), g),
            transpose=True))(lams)
    np.testing.assert_allclose(thetas, expect, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape", [(2, 32, 16, 4, 8, 8), (1, 64, 32, 8, 16, 16)])
def test_ssm_scan_kernel(shape):
    from repro.kernels.ssm_scan import ssm_scan
    b, s, di, n, chunk, dblk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    xc = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.3)
    d = jax.random.normal(ks[5], (di,))
    y_k, h_k = ssm_scan(xc, dt, bm, cm, a, d, chunk=chunk, di_block=dblk)
    y_r, h_r = ref.ssm_scan(xc, dt, bm, cm, a, d)
    np.testing.assert_allclose(y_k, y_r, atol=1e-4)
    np.testing.assert_allclose(h_k, h_r, atol=1e-4)
