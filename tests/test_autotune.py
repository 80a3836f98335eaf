"""Roofline-guided autotuner: lattice legality, zero-execution scoring,
tuned-vs-untuned parity, tuning-cache hits and cross-process persistence,
and the serving layer's tune-once-per-geometry contract."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.backends import (CountingBackend, PallasBackend,
                                 ReferenceBackend, resolve_backend,
                                 retile_backend)
from repro.core.engine import CVEngine, PiCholeskyStrategy
from repro.core.folds import make_folds
from repro.distributed import autotune
from repro.distributed import sharding as shardlib


def _problem(h=24, n=240, k=4, q=16, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    folds = make_folds(x, y, k)
    lams = jnp.logspace(-3, 1, q, dtype=jnp.float32)
    return folds, lams


# ----------------------------------------------------------------- lattice


def test_lattice_default_first_and_legal():
    default = autotune.TunedConfig(block=32, lam_chunk=4, mesh_shape=None,
                                   source="default")
    cands = autotune.candidate_lattice(
        h=24, k=4, q=16, n_devices=4, default=default,
        blocks=(8, 16, 32), auto_chunk=lambda block, q_loc: min(
            q_loc, shardlib.auto_lam_chunk(24, block, jnp.float32,
                                           64 * 1024)))
    assert cands[0] is default
    keys = [c.key() for c in cands]
    assert len(keys) == len(set(keys))          # deduped
    for c in cands:
        assert 1 <= c.lam_chunk <= 16
        if c.mesh_shape is not None:
            n_fold, n_lam = c.mesh_shape
            assert n_fold * n_lam == 4
            assert 4 % n_fold == 0              # fold axis divides k


def test_lattice_mesh_candidates_respect_fold_divisibility():
    # k=3 on 4 devices: only fold axes 1 divide both → (1,4) (plus None)
    default = autotune.TunedConfig(block=32, lam_chunk=4)
    cands = autotune.candidate_lattice(
        h=16, k=3, q=8, n_devices=4, default=default, blocks=(32,),
        chunks=(4,))
    shapes = {c.mesh_shape for c in cands}
    assert shapes == {None, (1, 4)}
    assert shardlib.mesh_shape_candidates(3, 4) == [(1, 4)]
    assert shardlib.mesh_shape_candidates(4, 4) == [(1, 4), (2, 2), (4, 1)]


def test_chunk_ladder_spans_auto_value():
    ladder = autotune.chunk_ladder(8, 64)
    assert 8 in ladder
    assert any(c < 8 for c in ladder) and any(c > 8 for c in ladder)
    assert all(1 <= c <= 64 for c in ladder)
    assert autotune.chunk_ladder(1, 1) == (1,)   # clipped, never empty


# ----------------------------------------- scoring is compile-time only


def test_tune_zero_candidate_executions():
    """Every candidate is AOT lowered+compiled, but NONE executes: a
    factorization routed through a host callback would fire the callback
    on execution — lowering alone must leave the counter at zero."""
    calls = dict(n=0)

    def host_chol(a):
        calls["n"] += 1
        return np.linalg.cholesky(a)

    def chol_fn(a):
        return jax.pure_callback(
            host_chol, jax.ShapeDtypeStruct(a.shape, a.dtype), a,
            vmap_method="sequential")

    folds, lams = _problem()
    strat = PiCholeskyStrategy(block=32, chol_fn=chol_fn)
    eng = CVEngine(strat, backend="reference")
    cache = autotune.TuningCache()
    cfg = autotune.tune(eng, folds, lams, cache=cache, blocks=(32, 64),
                        mesh_shapes=[None])
    assert calls["n"] == 0                       # nothing ran
    assert cache.lowerings >= 2                  # but candidates compiled
    assert cfg.source == "tuned"
    assert np.isfinite(cfg.predicted_s) and cfg.predicted_s > 0
    # scored candidates all carry finite predictions, chosen is the argmin
    default = autotune.default_config(eng, folds, 16)
    scored = autotune.score_candidates(
        eng, folds, lams, autotune.candidate_lattice(
            h=24, k=4, q=16, n_devices=len(jax.devices()), default=default,
            blocks=(32, 64), mesh_shapes=[None],
            auto_chunk=lambda block, q_loc: eng._auto_chunk(
                q_loc, 24, jnp.float32, block)))
    assert calls["n"] == 0
    assert min(s.predicted_s for s in scored) == pytest.approx(
        cfg.predicted_s)


# ------------------------------------------------------------ result parity


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_tuned_sweep_bitwise_vs_untuned(backend):
    """With the mesh pinned and every lattice block ≥ h (single padded
    tile), tuning may change tiles/chunks but the swept errors are
    BIT-identical to the untuned engine on both backends."""
    folds, lams = _problem()
    kw = dict(block=32) if backend == "pallas" else {}
    eng = CVEngine("picholesky", backend=backend, tune="auto",
                   tune_lattice=dict(blocks=(32, 64), mesh_shapes=[None]),
                   **kw)
    base = CVEngine("picholesky", backend=backend, **kw)
    r_t = eng.run(folds, lams)
    r_b = base.run(folds, lams)
    np.testing.assert_array_equal(np.asarray(r_t.errors),
                                  np.asarray(r_b.errors))
    tune_info = r_t.extras["engine"]["tune"]
    assert tune_info["source"] == "tuned"
    assert tune_info["block"] in (32, 64)


def test_tuned_mesh_selection_allclose_and_same_argmin():
    """Free mesh dimension: the tuner may pick a sharded layout; results
    stay allclose (same tolerance as the engine's own mesh parity tests)
    and select the identical λ*."""
    folds, lams = _problem(h=16, n=160, k=4, q=8)
    eng = CVEngine("picholesky", backend="reference", tune="auto",
                   tune_lattice=dict(blocks=(16, 32)))
    base = CVEngine("picholesky", backend="reference")
    r_t = eng.run(folds, lams)
    r_b = base.run(folds, lams)
    np.testing.assert_allclose(np.asarray(r_t.errors),
                               np.asarray(r_b.errors), rtol=1e-4)
    assert r_t.best_lam == r_b.best_lam
    ms = r_t.extras["engine"]["tune"]["mesh_shape"]
    if ms is not None:
        assert ms[0] * ms[1] == len(jax.devices())


def test_default_always_candidate_ties_resolve_to_default():
    """Pinning the lattice to exactly the default config returns the
    default configuration (strict < keeps the first, default-first
    element on ties)."""
    folds, lams = _problem()
    eng = CVEngine("picholesky", backend="reference")
    default = autotune.default_config(eng, folds, int(lams.shape[0]))
    cfg = autotune.tune(eng, folds, lams, blocks=(default.block,),
                        chunks=(default.lam_chunk,),
                        mesh_shapes=[default.mesh_shape])
    assert cfg.key() == default.key()


# ------------------------------------------------------------ tuning cache


def test_tune_cache_hit_skips_lowering():
    folds, lams = _problem()
    cache = autotune.TuningCache()
    eng = CVEngine("picholesky", backend="reference", tune="auto",
                   tune_cache=cache,
                   tune_lattice=dict(blocks=(32,), mesh_shapes=[None]))
    r1 = eng.run(folds, lams)
    n_low = cache.lowerings
    assert n_low > 0 and cache.misses == 1
    r2 = eng.run(folds, lams)
    assert cache.lowerings == n_low              # no re-lowering at all
    assert cache.hits == 1
    assert r2.extras["engine"]["tune"]["source"] == "cache"
    np.testing.assert_array_equal(np.asarray(r1.errors),
                                  np.asarray(r2.errors))
    # a DIFFERENT geometry is a miss, not a false hit
    folds2, lams2 = _problem(h=16, n=160)
    eng.run(folds2, lams2)
    assert cache.misses == 2
    assert cache.lowerings > n_low


def test_tuning_cache_persists_via_checkpoint_manager(tmp_path):
    folds, lams = _problem()
    cache = autotune.TuningCache()
    eng = CVEngine("picholesky", backend="reference", tune="auto",
                   tune_cache=cache,
                   tune_lattice=dict(blocks=(32, 64), mesh_shapes=[None]))
    eng.run(folds, lams)
    cache.save(str(tmp_path))
    # fresh process stand-in: a new cache object loaded from disk
    cache2 = autotune.TuningCache.load(str(tmp_path))
    assert len(cache2) == 1
    assert cache2.configs == cache.configs       # TunedConfig is frozen/eq
    eng2 = CVEngine("picholesky", backend="reference", tune="auto",
                    tune_cache=cache2,
                    tune_lattice=dict(blocks=(32, 64), mesh_shapes=[None]))
    eng2.run(folds, lams)
    assert cache2.hits == 1 and cache2.lowerings == 0
    # save is idempotent/atomic: a second save supersedes the step
    cache2.save(str(tmp_path))
    assert len(autotune.TuningCache.load(str(tmp_path))) == 1


def test_tuning_cache_load_missing_dir_is_empty(tmp_path):
    cache = autotune.TuningCache.load(str(tmp_path / "nope"))
    assert len(cache) == 0


def test_explicit_tuned_config_pins_configuration():
    folds, lams = _problem()
    cfg = autotune.TunedConfig(block=32, lam_chunk=4, mesh_shape=None)
    eng = CVEngine("picholesky", backend="reference", tune=cfg)
    r = eng.run(folds, lams)
    info = r.extras["engine"]["tune"]
    assert (info["block"], info["lam_chunk"]) == (32, 4)
    derived = eng._apply_tuned(cfg)
    assert derived.strategy.block == 32 and derived.lam_chunk == 4
    assert derived.tune is False                 # recursion guard


@pytest.mark.parametrize("derivation", ["tuned", "interpolant"])
def test_derived_engine_carries_every_other_field(derivation):
    """A derived engine (the tuned configuration's, or another
    interpolant's) changes what its derivation sets and carries every
    other field of the engine it derives from."""
    from repro.core.factor_cache import FactorCache
    from repro.core.sketch import SketchPlan
    cfg = autotune.TunedConfig(block=32, lam_chunk=4, mesh_shape=None)
    eng = CVEngine("picholesky", backend="reference", donate=False,
                   block=16, lam_chunk=5, cache=FactorCache(),
                   reuse="covering", cache_anchors=True, precision="fp32",
                   tune=cfg, tune_cache=autotune.TuningCache(),
                   tune_lattice=dict(blocks=(32,)),
                   sketch=SketchPlan(m=64))
    if derivation == "tuned":
        derived = eng._apply_tuned(cfg)
        changed = {"strategy", "backend", "mesh", "block", "lam_chunk"}
    else:
        derived = eng.with_interpolant(1, "centered")
        changed = {"strategy", "backend"}
    assert derived.tune is False                 # recursion guard
    assert derived._prec == eng._prec
    for field in dataclasses.fields(CVEngine):
        if field.name not in changed | {"tune"}:
            assert getattr(derived, field.name) == getattr(eng, field.name), \
                field.name


# ---------------------------------------------------------- backend retile


def test_retile_backend_variants():
    pb = retile_backend(PallasBackend(), chol_block=64)
    assert (pb.chol_block, pb.trsm_block) == (64, 256)
    rb = ReferenceBackend()
    assert retile_backend(rb, chol_block=64) is rb   # no kernel tiles
    cb = CountingBackend(PallasBackend())
    cb.by_stage["unstaged"] = {"cholesky": 3}
    cb2 = retile_backend(cb, chol_block=64, trsm_block=32)
    assert cb2 is not cb
    assert cb2.inner.chol_block == 64 and cb2.inner.trsm_block == 32
    assert cb2.by_stage is cb.by_stage           # counters shared, not forked
    assert resolve_backend("pallas", chol_block=64).chol_block == 64
    assert resolve_backend(cb, trsm_block=128).inner.trsm_block == 128


# -------------------------------------------------------------- serving


def test_server_tunes_once_per_geometry():
    from repro.serving.server import CVSweepServer, ServerConfig, SweepRequest

    folds, lams = _problem()
    srv = CVSweepServer(
        PiCholeskyStrategy(block=32), "reference",
        config=ServerConfig(
            tune="auto",
            tune_lattice=dict(blocks=(32, 64), mesh_shapes=[None])))
    for tenant in ("a", "b", "c"):
        srv.submit(SweepRequest(tenant=tenant, folds=folds, lams=lams))
    srv.drain()
    stats = srv.stats["tuning"]
    assert stats["entries"] == 1                 # one geometry, one verdict
    assert stats["misses"] == 1
    n_low = stats["lowerings"]
    # same geometry again: pure cache hit, zero new lowerings
    srv.submit(SweepRequest(tenant="a", folds=folds, lams=lams))
    srv.drain()
    assert srv.stats["tuning"]["lowerings"] == n_low
    assert srv.stats["tuning"]["hits"] >= 1
    assert len(srv.take_responses("a")) == 2
