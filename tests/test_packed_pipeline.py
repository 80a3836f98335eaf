"""Packed-domain factor pipeline: PackedFactor currency, packed triangular
solves, fused interpolant solves, and the chunked constant-memory λ sweep.

The acceptance contract for the streamed sweep lives here:
``test_sweep_peak_memory_independent_of_q`` asserts the jitted sweep's
live-buffer proxy (XLA ``temp_size_in_bytes``) does not grow with the λ-grid
size at fixed chunk, and the parity tests assert chunked == unchunked across
chunk sizes including q % chunk ≠ 0 and chunk > q.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine, packing, picholesky, solvers
from repro.core.backends import ReferenceBackend
from repro.distributed import sharding as shardlib
from repro.testing import strategies as props

# shared generators (repro.testing.strategies): SPD builder + backend
# constructor (kernel tiles sized 16 for this suite's h=64 problems)
_spd = props.spd_matrix


def _backend(name):
    return props.make_backend(name, block=16)


@pytest.fixture(scope="module")
def folds4():
    return props.regression_folds(h=64, n=400, k=4)


LAMS = props.log_grid(31)


# ------------------------------------------------------ PackedFactor currency


def test_packed_factor_round_trip_and_pytree():
    h, block = 37, 8
    l = jnp.linalg.cholesky(_spd(h))
    pf = packing.PackedFactor.from_dense(l, block)
    assert pf.vec.shape == (packing.packed_size(h, block),)
    np.testing.assert_allclose(pf.dense(), l, atol=1e-12)
    # pytree: static (h, block) survive flatten/unflatten and jit
    leaves, treedef = jax.tree.flatten(pf)
    pf2 = jax.tree.unflatten(treedef, leaves)
    assert (pf2.h, pf2.block) == (h, block)
    out = jax.jit(lambda p: p.vec.sum())(pf)
    np.testing.assert_allclose(out, pf.vec.sum())


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("h,block", [(32, 8), (37, 8), (64, 16)])
def test_solve_packed_matches_dense_solve(backend, h, block):
    """solve_packed ≡ dense solve_from_factor on both backends."""
    bk = _backend(backend)
    a = _spd(h)
    l = jnp.linalg.cholesky(a)
    g = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float64)
    pf = packing.PackedFactor.from_dense(l, block)
    dense = ReferenceBackend().solve_from_factor(l, g)
    np.testing.assert_allclose(solvers.solve_packed(pf, g, backend=bk),
                               dense, **props.parity_tol(1e-8, 1e-10))
    # the dispatch path: solve_from_factor on a PackedFactor never unpacks
    np.testing.assert_allclose(solvers.solve_from_factor(pf, g, backend=bk),
                               dense, **props.parity_tol(1e-8, 1e-10))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_solve_packed_batched_factors(backend):
    bk = _backend(backend)
    h, block, q = 32, 8, 5
    a = _spd(h)
    lams = jnp.logspace(-2, 0, q)
    ls = jax.vmap(lambda lam: jnp.linalg.cholesky(a + lam * jnp.eye(h)))(lams)
    g = jax.random.normal(jax.random.PRNGKey(3), (h,), jnp.float64)
    pf = packing.PackedFactor(vec=packing.pack_tril(ls, block), h=h,
                              block=block)
    out = solvers.solve_packed(pf, g, backend=bk)
    exp = jax.vmap(lambda l: ReferenceBackend().solve_from_factor(l, g))(ls)
    np.testing.assert_allclose(out, exp, **props.parity_tol(1e-8, 1e-10))


@given(h=props.heights(), block=props.blocks(), transpose=st.booleans())
@settings(max_examples=15, deadline=None)
def test_solve_lower_packed_property(h, block, transpose):
    """Packed sweep ≡ dense triangular solve for any shape, incl. h % B ≠ 0."""
    l = jnp.linalg.cholesky(_spd(h, seed=h))
    vec = packing.pack_tril(l, block)
    g = jnp.asarray(np.random.RandomState(h).randn(h, 3))
    out = packing.solve_lower_packed(vec, g, h, block, transpose=transpose)
    exp = jax.lax.linalg.triangular_solve(l, g, left_side=True, lower=True,
                                          transpose_a=transpose)
    np.testing.assert_allclose(out, exp, rtol=1e-8, atol=1e-8)


# ------------------------------------------------------ fused interp solves


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("h,block", [(37, 8), (64, 16)])
def test_interp_solve_matches_dense_route(backend, h, block):
    """Fused eval+solve ≡ the demoted dense route (eval_factor + trsm)."""
    bk = _backend(backend)
    a = _spd(h)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, 5)
    model = picholesky.fit(a, sample, 2, block=block)
    lams = jnp.logspace(-2, 0, 9)
    g = jax.random.normal(jax.random.PRNGKey(4), (h,), jnp.float64)
    out = solvers.solve_interpolant_sweep(model, lams, g, backend=bk)
    dense = model.eval_factor(lams)   # debug escape hatch
    exp = jax.vmap(lambda l: ReferenceBackend().solve_from_factor(l, g))(dense)
    np.testing.assert_allclose(out, exp, **props.parity_tol(1e-7, 1e-9))


def test_eval_factor_is_debug_escape_hatch():
    """eval_packed_factor stays packed; eval_factor unpacks equivalently."""
    h, block = 32, 8
    model = picholesky.fit(_spd(h), picholesky.choose_sample_lambdas(
        1e-2, 1.0, 4), 2, block=block)
    lams = jnp.logspace(-2, 0, 5)
    pf = model.eval_packed_factor(lams)
    assert isinstance(pf, packing.PackedFactor)
    assert pf.vec.shape == (5, packing.packed_size(h, block))
    np.testing.assert_allclose(pf.dense(), model.eval_factor(lams),
                               atol=1e-12)


def test_fit_consumes_packed_factors():
    h, block = 32, 8
    a = _spd(h)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, 4)
    ls = jax.vmap(lambda lam: jnp.linalg.cholesky(a + lam * jnp.eye(h))
                  )(sample)
    pf = packing.PackedFactor(vec=packing.pack_tril(ls, block), h=h,
                              block=block)
    m_dense = picholesky.fit(a, sample, 2, block=block, factors=ls)
    m_packed = picholesky.fit(a, sample, 2, block=block, factors=pf)
    np.testing.assert_allclose(m_packed.theta, m_dense.theta, atol=1e-12)
    with pytest.raises(ValueError, match="block"):
        picholesky.fit(a, sample, 2, block=16, factors=pf)


# ---------------------------------------- escape hatches vs dense oracle


@pytest.mark.parametrize("h,block", props.PACKED_SHAPES)
def test_dense_escape_hatch_non_tile_multiple(h, block):
    """PackedFactor.dense() at sizes that are NOT a multiple of the tile
    (incl. h < block): round-trips the exact factor and solve_packed_ref
    matches a dense ``jnp.linalg`` oracle, single and multi RHS."""
    a = _spd(h, seed=h)
    l = jnp.linalg.cholesky(a)
    pf = packing.PackedFactor.from_dense(l, block)
    np.testing.assert_allclose(pf.dense(), l, atol=1e-12)
    rng = np.random.RandomState(h)
    g1 = jnp.asarray(rng.randn(h))
    gq = jnp.asarray(rng.randn(h, 3))
    np.testing.assert_allclose(
        packing.solve_packed_ref(pf.vec, g1, h, block),
        jnp.linalg.solve(a, g1), rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(
        packing.solve_packed_ref(pf.vec, gq, h, block),
        jnp.linalg.solve(a, gq), rtol=1e-8, atol=1e-10)


def test_eval_factor_non_tile_multiple_vs_dense_fit():
    """The interpolant's dense escape hatch agrees with a dense-domain
    polynomial fit when h % block ≠ 0 (padding columns must not leak)."""
    h, block = 21, 8
    a = _spd(h)
    sample = picholesky.choose_sample_lambdas(1e-2, 1.0, 5)
    model = picholesky.fit(a, sample, 2, block=block)
    lams = jnp.logspace(-2, 0, 4)
    dense = model.eval_factor(lams)
    assert dense.shape == (4, h, h)
    # oracle: fit each dense entry directly (full-matrix vectorization)
    ls = jax.vmap(lambda lam: jnp.linalg.cholesky(a + lam * jnp.eye(h))
                  )(sample)
    v = picholesky.vandermonde(sample, 2)
    theta = jnp.linalg.solve(v.T @ v, v.T @ ls.reshape(5, -1))
    expect = (picholesky.vandermonde(lams, 2) @ theta).reshape(4, h, h)
    np.testing.assert_allclose(dense, jnp.tril(expect),
                               **props.parity_tol(1e-7, 1e-9))


def test_packed_factor_vec_size_validated():
    """A vec whose length disagrees with (h, block) fails at construction,
    not deep inside a tile reshape."""
    good = packing.PackedFactor(vec=jnp.zeros(packing.packed_size(32, 8)),
                                h=32, block=8)
    assert good.n_blocks == 10
    with pytest.raises(ValueError, match="packed_size"):
        packing.PackedFactor(vec=jnp.zeros(17), h=32, block=8)
    # non-array leaves (specs/placeholders from tree ops) must still pass
    from jax.sharding import PartitionSpec
    pf = jax.tree.map(lambda _: PartitionSpec("folds"), good)
    assert isinstance(pf, packing.PackedFactor)


# ------------------------------------------------- chunked λ-sweep parity


@pytest.mark.parametrize("chunk", [1, 5, 7, 16, 31, 40, 64])
def test_chunked_sweep_matches_unchunked(folds4, chunk):
    """Chunked vs unchunked error grids agree bitwise-tolerantly across
    chunk sizes, including q % chunk ≠ 0 (5, 7, 16) and chunk > q (40, 64)."""
    strat = lambda: engine.PiCholeskyStrategy(g=4, block=16)  # noqa: E731
    base = engine.CVEngine(strat(), lam_chunk=None).run(folds4, LAMS)
    r = engine.CVEngine(strat(), lam_chunk=chunk).run(folds4, LAMS)
    np.testing.assert_allclose(r.errors, base.errors,
                               **props.parity_tol(1e-10, 1e-12))
    props.assert_selection_close(r.errors, base.errors)
    assert r.extras["engine"]["lam_chunk"] == chunk


@pytest.mark.parametrize("name,params", [
    ("exact", {}),
    ("picholesky_warmstart", dict(block=16, g_rest=3)),
    ("svd", dict(mode="truncated", k_trunc=16)),
    ("pinrmse", {}),
])
def test_chunking_is_strategy_agnostic(folds4, name, params):
    """Every built-in strategy is λ-elementwise, so streaming is exact."""
    base = engine.CVEngine(engine.make_strategy(name, **params),
                           lam_chunk=None).run(folds4, LAMS)
    r = engine.CVEngine(engine.make_strategy(name, **params),
                        lam_chunk=7).run(folds4, LAMS)
    np.testing.assert_allclose(r.errors, base.errors,
                               **props.parity_tol(1e-10, 1e-12))


def test_chunked_sweep_on_mesh(folds4):
    """Chunking composes with the folds × lams shard_map (per-shard chunks;
    conftest forces 4 host devices)."""
    strat = lambda: engine.PiCholeskyStrategy(g=4, block=16)  # noqa: E731
    base = engine.CVEngine(strat(), lam_chunk=None).run(folds4, LAMS)
    r = engine.CVEngine(strat(), mesh="auto", lam_chunk=3).run(folds4, LAMS)
    assert r.extras["engine"]["mesh"] is not None
    np.testing.assert_allclose(r.errors, base.errors,
                               **props.parity_tol(1e-8, 1e-12))


def test_chunk_lams_helper():
    lams = jnp.arange(7.0)
    chunks, n = shardlib.chunk_lams(lams, 3)
    assert chunks.shape == (3, 3) and n == 7
    np.testing.assert_allclose(chunks[-1], [6.0, 6.0, 6.0])  # edge padding
    chunks, n = shardlib.chunk_lams(lams, 16)                # chunk > q
    assert chunks.shape == (1, 16) and n == 7
    with pytest.raises(ValueError, match="positive"):
        shardlib.chunk_lams(lams, 0)


def test_auto_chunk_sized_to_vmem_budget():
    eng = engine.CVEngine(engine.PiCholeskyStrategy(g=4, block=16))
    # the auto chunk budgets at the policy's STORAGE dtype: bf16 storage
    # halves the per-λ bytes and doubles the chunk
    store = props.active_precision().store_dtype(jnp.float64)
    per_lam = packing.packed_nbytes(64, 16, store)
    assert eng._resolve_chunk(1024, 64, jnp.float64) == \
        engine.LAM_CHUNK_BUDGET_BYTES // per_lam
    assert engine.CVEngine("exact", lam_chunk=None)._resolve_chunk(
        1024, 64, jnp.float64) is None
    assert engine.CVEngine("exact", lam_chunk=12)._resolve_chunk(
        1024, 64, jnp.float64) == 12
    with pytest.raises(ValueError, match="positive"):
        engine.CVEngine("exact", lam_chunk=-1)._resolve_chunk(
            1024, 64, jnp.float64)


@pytest.mark.parametrize("precision", ["fp32", "bf16_refined", "bf16_store"])
def test_auto_chunk_follows_the_lam_stage(precision):
    """``lam_chunk='auto'`` counts what the λ stage holds per λ.  The
    Pallas ``interp_solve`` builds no factor, only VMEM columns, so the
    paper grid (h=4096, block 128, q=31) is one chunk; a shard under the
    kernel's cap is taken whole.  The reference path builds (chunk, P)
    rows, and the exact strategy dense factors: both keep the P rule."""
    h, block, f32 = 4096, 128, jnp.float32
    strat = engine.PiCholeskyStrategy(g=4, block=block)
    pallas = engine.CVEngine(strat, backend="pallas", precision=precision)
    assert pallas._resolve_chunk(31, h, f32) == 31
    cap = pallas._resolve_chunk(10_000, h, f32)
    assert 31 <= cap < 10_000
    if precision != "fp32":   # bf16 storage: more λs per VMEM
        fp32 = engine.CVEngine(strat, backend="pallas", precision="fp32")
        assert cap > fp32._resolve_chunk(10_000, h, f32)
    assert pallas._resolve_chunk(7, h, f32) == 7
    store = engine.CVEngine(strat, precision=precision)._prec.store_dtype(f32)
    p_rule = shardlib.auto_lam_chunk(h, block, store,
                                     engine.LAM_CHUNK_BUDGET_BYTES)
    ref = engine.CVEngine(strat, backend="reference", precision=precision)
    assert ref._resolve_chunk(31, h, f32) == p_rule == 1
    exact = engine.CVEngine("exact", backend="pallas", precision=precision)
    assert exact._resolve_chunk(31, h, f32) == p_rule


def test_lam_chunk_resolved_recorded(folds4):
    """Each entry point that records ``lam_chunk`` records the λs one
    λ-stage call took beside it."""
    from repro.core.factor_cache import FactorCache

    def resolved(res):
        return res.extras["engine"]["lam_chunk_resolved"]

    strat = engine.PiCholeskyStrategy(g=4, block=16)
    eng = engine.CVEngine(strat, lam_chunk=7)
    assert resolved(eng.run(folds4, LAMS)) == 7
    assert resolved(eng.run_async(folds4, LAMS)) == 7
    assert resolved(eng.search(folds4, LAMS)) == 7     # waves of min(8, 7)
    assert resolved(engine.CVEngine(strat, lam_chunk=None
                                    ).run(folds4, LAMS)) == 31
    cached = engine.CVEngine(strat, cache=FactorCache())
    assert cached._resolve_chunk(31, 64, jnp.float64) == 31
    (res,) = cached.run_batch([(folds4, LAMS)])
    assert resolved(res) == 31


# ------------------------------------------- constant-memory acceptance


def test_sweep_peak_memory_independent_of_q(folds4):
    """Acceptance: at fixed chunk, the λ sweep's peak device memory is
    independent of q (q=64 vs q=1024), up to the O(q) bookkeeping of the
    λ grid / error outputs themselves (≤ 64 B per extra λ — no h² term).
    The unchunked sweep at q=1024 is an order of magnitude above it."""
    strat = lambda: engine.PiCholeskyStrategy(g=4, block=16)  # noqa: E731
    chunked = engine.CVEngine(strat(), lam_chunk=16, donate=False)
    t64 = chunked.sweep_temp_bytes(folds4, jnp.logspace(-3, 2, 64))
    t1024 = chunked.sweep_temp_bytes(folds4, jnp.logspace(-3, 2, 1024))
    assert abs(t1024 - t64) <= 64 * (1024 - 64), (t64, t1024)

    dense = engine.CVEngine(strat(), lam_chunk=None, donate=False)
    t_dense = dense.sweep_temp_bytes(folds4, jnp.logspace(-3, 2, 1024))
    assert t_dense > 10 * t1024, (t_dense, t1024)
