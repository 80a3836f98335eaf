"""CVEngine: strategy parity vs the host-loop oracles, sharded-mesh parity
on the 4-virtual-device host platform, backend switching, and the driver
compatibility layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cv, cv_host, engine
from repro.distributed import sharding as shardlib
from repro.testing import strategies as props

# fold problems come from the shared generators (repro.testing.strategies)


@pytest.fixture(scope="module")
def folds5():
    return props.regression_folds(h=128, n=400, k=5)


@pytest.fixture(scope="module")
def folds4():
    return props.regression_folds(h=128, n=400, k=4)


LAMS = props.log_grid(31)


def _assert_result_close(a, b, rtol=1e-4):
    np.testing.assert_allclose(a.errors, b.errors, rtol=rtol)
    assert a.best_lam == pytest.approx(b.best_lam, rel=rtol)


# ------------------------------------------------- parity vs host oracles


def test_exact_matches_host_oracle(folds5):
    r = engine.CVEngine("exact").run(folds5, LAMS)
    _assert_result_close(r, cv_host.host_cv_exact_cholesky(folds5, LAMS))
    assert r.n_exact_chol == 5 * 31


def test_picholesky_matches_host_oracle(folds5):
    strat = engine.PiCholeskyStrategy(g=4, block=32)
    r = engine.CVEngine(strat).run(folds5, LAMS)
    _assert_result_close(r, cv_host.host_cv_picholesky(folds5, LAMS, g=4,
                                                       block=32))
    assert r.n_exact_chol == 5 * 4


@pytest.mark.parametrize("mode,k_trunc", [("full", 0), ("truncated", 32)])
def test_svd_matches_host_oracle(folds5, mode, k_trunc):
    strat = engine.SVDStrategy(mode=mode, k_trunc=k_trunc)
    r = engine.CVEngine(strat).run(folds5, LAMS)
    _assert_result_close(r, cv_host.host_cv_svd(folds5, LAMS, mode=mode,
                                                k_trunc=k_trunc))


def test_randomized_svd_matches_host_oracle(folds5):
    key = jax.random.PRNGKey(2)
    strat = engine.SVDStrategy(mode="randomized", k_trunc=32, key=key)
    r = engine.CVEngine(strat).run(folds5, LAMS)
    _assert_result_close(r, cv_host.host_cv_svd(folds5, LAMS,
                                                mode="randomized",
                                                k_trunc=32, key=key))


def test_pinrmse_matches_host_oracle(folds5):
    strat = engine.PinrmseStrategy(g=4, degree=2)
    r = engine.CVEngine(strat).run(folds5, LAMS)
    _assert_result_close(r, cv_host.host_cv_pinrmse(folds5, LAMS, g=4))


def test_warmstart_selects_exact_lambda(folds5):
    """No host oracle (the engine's metric-ridge refresh replaced the broken
    host version) — the contract is selection parity with exact CV at a
    fraction of the factorizations."""
    r_exact = engine.CVEngine("exact").run(folds5, LAMS)
    strat = engine.PiCholeskyWarmstart(g_first=4, g_rest=3, block=32)
    r_warm = engine.CVEngine(strat).run(folds5, LAMS)
    i_e = int(np.argmin(r_exact.errors))
    i_w = int(np.argmin(r_warm.errors))
    assert abs(i_e - i_w) <= 1
    assert r_warm.n_exact_chol < r_exact.n_exact_chol / 5


# ------------------------------------------------------- sharded execution


def test_host_platform_has_four_devices():
    """conftest forces --xla_force_host_platform_device_count=4."""
    assert len(jax.devices()) >= 4


@pytest.mark.parametrize("name,params", [
    ("exact", {}),
    ("picholesky", dict(block=32)),
    ("picholesky_warmstart", dict(block=32, g_rest=3)),
    ("svd", dict(mode="truncated", k_trunc=32)),
    ("pinrmse", {}),
])
def test_strategies_match_on_auto_mesh(folds4, name, params):
    """Every strategy, sharded over the 4-device (folds × lams) mesh,
    reproduces the single-device sweep (acceptance: rtol 1e-4)."""
    single = engine.CVEngine(engine.make_strategy(name, **params)).run(
        folds4, LAMS)
    sharded = engine.CVEngine(engine.make_strategy(name, **params),
                              mesh="auto").run(folds4, LAMS)
    np.testing.assert_allclose(sharded.errors, single.errors, rtol=1e-4)
    assert sharded.best_lam == pytest.approx(single.best_lam, rel=1e-4)
    assert sharded.extras["engine"]["mesh"] is not None


def test_two_by_two_mesh_pads_lambda_grid(folds4):
    """2×2 mesh: λ grid (31) is padded to 32 for the λ axis and sliced back."""
    mesh = shardlib.make_cv_mesh(2)
    assert dict(mesh.shape) == {shardlib.CV_FOLD_AXIS: 2,
                                shardlib.CV_LAM_AXIS: 2}
    strat = engine.PiCholeskyStrategy(g=4, block=32)
    r = engine.CVEngine(strat, mesh=mesh).run(folds4, LAMS)
    base = engine.CVEngine(engine.PiCholeskyStrategy(g=4, block=32)).run(
        folds4, LAMS)
    assert r.errors.shape == (31,)
    np.testing.assert_allclose(r.errors, base.errors, rtol=1e-4)


def test_indivisible_fold_axis_raises(folds5):
    mesh = shardlib.make_cv_mesh(2)   # fold axis 2, but k=5
    with pytest.raises(ValueError, match="not divisible"):
        engine.CVEngine("exact", mesh=mesh).run(folds5, LAMS)


def test_cv_axis_sizes():
    assert shardlib.cv_axis_sizes(4, 4) == (4, 1)
    assert shardlib.cv_axis_sizes(5, 4) == (1, 4)
    assert shardlib.cv_axis_sizes(6, 4) == (2, 2)


# -------------------------------------------------------- backend switching


def test_pallas_backend_matches_reference(folds4):
    lams = jnp.logspace(-2, 1, 7)
    for strat in (lambda: engine.ExactCholesky(),
                  lambda: engine.PiCholeskyStrategy(g=4, block=16)):
        r_ref = engine.CVEngine(strat(), backend="reference").run(folds4, lams)
        r_pal = engine.CVEngine(strat(), backend="pallas", block=16).run(
            folds4, lams)
        np.testing.assert_allclose(r_pal.errors, r_ref.errors, rtol=1e-6)


def test_auto_backend_is_reference_off_tpu():
    from repro.core.backends import resolve_backend
    assert resolve_backend("auto").name == "reference"  # CPU test platform
    assert resolve_backend(None).name == "reference"
    assert resolve_backend("pallas").name == "pallas"
    with pytest.raises(ValueError):
        resolve_backend("no-such-backend")


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_sweep_traces_every_matmul_at_full_precision(backend):
    """On a TPU an f32 matmul at the default precision is one bf16 pass: the
    engine's own matmuls and the kernels' f32 MXU products must all ask for
    full precision (interpret mode shows the kernels' products too)."""
    folds = props.regression_folds(h=32, n=128, k=4, dtype=jnp.float32)
    eng = engine.CVEngine(engine.PiCholeskyStrategy(g=4, block=16),
                          backend=backend, block=16)
    h_tr, g_tr = eng._split(folds.hess, folds.grad, folds.fold_hess,
                            folds.fold_grad)
    text = eng._sweep_fn(None).lower(
        h_tr, g_tr, folds.x_folds, folds.y_folds,
        jnp.logspace(-2, 1, 7, dtype=jnp.float32)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all("HIGHEST" in line for line in dots), dots


# ------------------------------------------------------ compatibility layer


def test_drivers_are_engine_wrappers(folds5):
    """cv_* wrappers return engine results (metadata present) identical to a
    directly constructed engine."""
    r = cv.cv_picholesky(folds5, LAMS, g=4, block=32)
    meta = r.extras["engine"]
    assert meta["strategy"] == "picholesky"
    assert meta["backend"] == "reference"
    direct = engine.CVEngine(engine.PiCholeskyStrategy(g=4, block=32)).run(
        folds5, LAMS)
    np.testing.assert_allclose(r.errors, direct.errors, rtol=1e-12)


def test_driver_engine_cache_reused(folds5):
    cv.cv_exact_cholesky(folds5, LAMS)
    n = len(cv._ENGINES)
    cv.cv_exact_cholesky(folds5, LAMS)
    assert len(cv._ENGINES) == n


def test_strategy_registry_round_trip():
    for name in engine.STRATEGIES:
        assert engine.make_strategy(name).name == name
    with pytest.raises(ValueError, match="unknown strategy"):
        engine.make_strategy("nope")


def test_custom_strategy_plugs_in(folds4):
    """The CVStrategy seam: a user strategy (here: exact solve via jnp.solve
    instead of Cholesky) runs through the same engine machinery, sharded."""

    class DirectSolve(engine.StrategyBase):
        name = "direct"

        def n_exact_chol(self, k, q):
            return 0

        def fold_errors(self, state, f_idx, h_tr_f, g_tr_f, x_f, y_f, lams,
                        aux, bk):
            eye = jnp.eye(h_tr_f.shape[-1], dtype=h_tr_f.dtype)

            def theta(lam):
                return jnp.linalg.solve(h_tr_f + lam * eye, g_tr_f)

            thetas = jax.vmap(theta)(lams)
            return jax.vmap(lambda t: engine.holdout_nrmse(t, x_f, y_f))(
                thetas)

    r = engine.CVEngine(DirectSolve(), mesh="auto").run(folds4, LAMS)
    r_exact = engine.CVEngine("exact", mesh="auto").run(folds4, LAMS)
    np.testing.assert_allclose(r.errors, r_exact.errors, rtol=1e-8)
