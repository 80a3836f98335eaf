"""The divided state stage: under a mesh whose λ axis holds several
devices, the (fold, anchor) factorizations are dealt out over them and
exchanged, and the curve is the one-device engine's.

Runs on the 4 host devices ``conftest.py`` forces, at h=256, block 64.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import CVEngine, FactorCache
from repro.core.engine import PiCholeskyStrategy
from repro.distributed import sharding as shardlib
from repro.testing.strategies import regression_folds

H, BLOCK = 256, 64
LAMS = jnp.logspace(-3, 0, 9)


def _folds(k):
    return regression_folds(h=H, n=4 * H, k=k)


def _engine(g, degree=2, **kw):
    return CVEngine(PiCholeskyStrategy(g=g, degree=degree, block=BLOCK),
                    backend="reference", **kw)


@pytest.mark.parametrize("k,g", [(5, 4), (3, 3)])   # 20 on 4; 9 padded to 12
def test_divided_curve_is_the_one_device_curve(k, g):
    folds = _folds(k)
    one = _engine(g).run(folds, LAMS)
    div = _engine(g, mesh="auto").run(folds, LAMS)
    assert div.extras["engine"]["mesh"] == {"folds": 1, "lams": 4}
    np.testing.assert_allclose(div.errors, one.errors, rtol=1e-12,
                               atol=1e-14)
    assert div.best_lam == one.best_lam
    lay = shardlib.PairLayout(k, g, 4, H, BLOCK)
    assert div.extras["engine"]["shard"] == dict(
        devices=4, pairs_per_device=math.ceil(k * g / 4),
        exchange_bytes=lay.exchange_bytes(2, 8, 8), inputs="by_pair",
        placed_bytes=3 * (lay.device_folds().shape[1] * H * H + k * H) * 8)
    assert one.extras["engine"]["shard"] == dict(
        devices=1, pairs_per_device=k * g, exchange_bytes=0,
        inputs="replicated", placed_bytes=0)


def test_fold_axis_that_tiles_the_folds_keeps_fold_sharding():
    folds = _folds(4)
    res = _engine(4, mesh="auto").run(folds, LAMS)
    assert res.extras["engine"]["mesh"] == {"folds": 4, "lams": 1}
    n_f = folds.x_folds.shape[1]      # each device its own fold, whole
    assert res.extras["engine"]["shard"] == dict(
        devices=4, pairs_per_device=4, exchange_bytes=0,
        inputs="replicated", placed_bytes=3 * (H * H + H + n_f * H + n_f) * 8)


@pytest.mark.parametrize("k,g,n", [(5, 4, 4), (3, 3, 4), (4, 4, 2),
                                   (7, 2, 4), (2, 3, 4)])
def test_each_pair_is_factorized_and_no_device_runs_more_than_its_share(
        k, g, n):
    lay = shardlib.PairLayout(k, g, n, H, BLOCK)
    assert lay.per_device == math.ceil(k * g / n)
    seen = set()
    for j in range(n):
        fold, anchor = lay.pairs(jnp.asarray(j))
        assert fold.shape == (lay.per_device,)
        seen |= set(zip(np.asarray(fold).tolist(),
                        np.asarray(anchor).tolist()))
    assert seen == {(f, a) for f in range(k) for a in range(g)}
    assert lay.slab * n >= lay.tiles * BLOCK ** 2
    assert lay.slab % BLOCK ** 2 == 0
    # each device's folds: exactly those its pairs read, in order, the row
    # padded with its last fold
    rows = lay.device_folds()
    assert rows.shape[0] == n
    for j in range(n):
        fold, _ = lay.pairs(jnp.asarray(j))
        fold = np.asarray(fold)
        assert set(rows[j]) == set(fold.tolist())
        np.testing.assert_array_equal(rows[j][fold - fold[0]], fold)


def test_five_folds_on_four_devices_read_two_folds_each():
    rows = shardlib.PairLayout(5, 4, 4, H, BLOCK).device_folds()
    np.testing.assert_array_equal(rows, [[0, 1], [1, 2], [2, 3], [3, 4]])


def _pair_sweep_text(eng, folds, compiled=False):
    mesh = eng._resolve_mesh(folds)
    h_tr, g_tr = eng._split(folds.hess, folds.grad, folds.fold_hess,
                            folds.fold_grad)
    lowered = eng._lower_sweep(mesh, h_tr, g_tr, folds.x_folds,
                               folds.y_folds, jnp.logspace(-3, 0, 12))
    return lowered.compile().as_text() if compiled else lowered.as_text()


def test_divided_sweep_factorizes_only_its_pairs_per_device():
    """The compiled per-device program factorizes a (⌈k·g/4⌉, h, h)
    batch, where the undivided one factorizes all k·g pairs; it is handed
    each device's 2 folds of the train Hessians, not all 5."""
    text = _pair_sweep_text(_engine(4, mesh="auto"), _folds(5))
    assert f"tensor<4x2x{H}x{H}xf64>" in text
    assert f"tensor<5x{H}x{H}xf64>" in text
    assert f"tensor<20x{H}x{H}xf64>" not in text
    assert "all_to_all" in text and "all_gather" in text


def test_cached_path_gives_the_fused_curve():
    folds = _folds(5)
    fused = _engine(4).run(folds, LAMS)
    cache = FactorCache()
    cold = _engine(4, mesh="auto", cache=cache, cache_anchors=True)
    first = cold.run(folds, LAMS)
    again = cold.run(folds, LAMS)
    assert (first.extras["engine"]["cache"]["status"],
            again.extras["engine"]["cache"]["status"]) == ("miss", "hit")
    for res in (first, again):
        np.testing.assert_allclose(res.errors, fused.errors, rtol=1e-12,
                                   atol=1e-14)
    assert again.extras["engine"]["shard"]["pairs_per_device"] == 0
    # the anchors the divided stage cached, put back in (fold, anchor)
    # order, refit another degree's Θ with no factorization
    refit = _engine(4, degree=1, mesh="auto", cache=cache,
                    cache_anchors=True).run(folds, LAMS)
    assert refit.extras["engine"]["cache"]["status"] == "refit"
    np.testing.assert_allclose(refit.errors,
                               _engine(4, degree=1).run(folds, LAMS).errors,
                               rtol=1e-12, atol=1e-14)


def test_staged_sweep_divides_the_state_too():
    folds = _folds(5)
    res = _engine(4, mesh="auto", lam_chunk=4).run_async(folds, LAMS)
    np.testing.assert_allclose(res.errors, _engine(4).run(folds, LAMS).errors,
                               rtol=1e-12, atol=1e-14)
    shard = res.extras["engine"]["shard"]
    assert shard["pairs_per_device"] == 5
    # its state stage is handed each device's 2 folds, as the fused sweep
    assert shard["inputs"] == "by_pair"
    assert shard["placed_bytes"] == 3 * (2 * H * H + 5 * H) * 8


V5E_LIMIT = 16_909_336_064          # bytes_limit of a v5e chip
FOLD_DATA = {4096: 671_088_640, 8192: 2_684_354_560}   # a design's, f32


def _sweep(h):
    return shardlib.sweep_bytes(5, 4, h, 4 * h, 4)     # k=5, g=4, n=4h, f32


@pytest.mark.parametrize("h,designs,one_device", [
    (4096, 0, True), (4096, 2, True),    # paper_h4096, with its mix's designs
    (8192, 0, False), (8192, 2, False),  # paper_h8192_4chip
    (7680, 0, False),   # near the limit: 16.3 GB at 3 factors a pair
])
def test_default_mesh_rule_against_a_16_gb_device(h, designs, one_device):
    free = V5E_LIMIT - designs * FOLD_DATA.get(h, 0)
    assert (_sweep(h) <= free) == one_device


def test_free_bytes_leave_out_what_is_in_use():
    class Device:
        def __init__(self, stats):
            self.memory_stats = lambda: stats
    assert shardlib.device_bytes_free(Device(None)) is None
    assert shardlib.device_bytes_free(Device({"bytes_in_use": 5})) is None
    assert shardlib.device_bytes_free(
        Device({"bytes_limit": V5E_LIMIT})) == V5E_LIMIT
    assert shardlib.device_bytes_free(Device(
        {"bytes_limit": V5E_LIMIT, "bytes_in_use": 2 * FOLD_DATA[8192]})) \
        == V5E_LIMIT - 2 * FOLD_DATA[8192]


def test_default_mesh_follows_the_device_limit(monkeypatch):
    folds = _folds(5)
    eng = _engine(4)
    assert eng._resolve_mesh(folds) is None           # the CPU: no limit
    k, n_f, h = folds.x_folds.shape
    need = shardlib.sweep_bytes(k, 4, h, k * n_f, 8)
    monkeypatch.setattr(shardlib, "device_bytes_free", lambda d: need)
    assert eng._resolve_mesh(folds) is None
    monkeypatch.setattr(shardlib, "device_bytes_free", lambda d: need - 1)
    mesh = eng._resolve_mesh(folds)
    assert dict(mesh.shape) == {"folds": 1, "lams": 4}
    res = eng.run(folds, LAMS)
    assert res.extras["engine"]["shard"]["pairs_per_device"] == 5


def test_run_async_reports_the_mesh_its_sweep_ran_on(monkeypatch):
    """The device's free bytes change once the sweep has run: the result
    names the mesh the sweep was planned and run on, not a second
    reading's."""
    folds = _folds(5)
    reads = []

    def free(device):
        reads.append(device)
        return 1 if len(reads) == 1 else None   # later: no limit, one device
    monkeypatch.setattr(shardlib, "device_bytes_free", free)
    res = _engine(4, lam_chunk=4).run_async(folds, LAMS)
    meta = res.extras["engine"]
    assert meta["mesh"] == {"folds": 1, "lams": 4}
    assert meta["shard"]["devices"] == 4
    assert meta["shard"]["pairs_per_device"] == 5
    assert meta["lam_chunk_resolved"] == 1      # a chunk of 4 over 4 devices
    assert len(reads) == 1


def test_anchor_exchange_scope_reaches_the_collectives():
    text = _pair_sweep_text(_engine(4, mesh="auto"), _folds(5),
                            compiled=True)
    ops = [line for line in text.splitlines()
           if " all-to-all(" in line or " all-gather(" in line]
    assert ops and all("cv.anchor_exchange" in line for line in ops)


def test_batch_and_search_run_on_the_default_mesh(monkeypatch):
    """A device too small for the one-device sweep: ``run_batch`` runs its
    problems one by one on the mesh instead of stacking them on one
    device, and ``search`` divides its state stage too."""
    folds = _folds(5)
    monkeypatch.setattr(shardlib, "device_bytes_free", lambda d: 1)
    eng = _engine(4, cache=FactorCache())
    batch = eng.run_batch([(folds, LAMS), (folds, LAMS)])
    assert [r.extras["engine"]["cache"]["status"] for r in batch] == \
        ["miss", "hit"]
    assert batch[0].extras["engine"]["mesh"] == {"folds": 1, "lams": 4}
    assert "batch" not in batch[0].extras["engine"]
    one = _engine(4).run(folds, LAMS)
    np.testing.assert_allclose(batch[1].errors, one.errors, rtol=1e-12,
                               atol=1e-14)
    found = _engine(4).search(folds, LAMS)
    assert found.extras["engine"]["shard"]["pairs_per_device"] == 5
    assert found.extras["engine"]["mesh"] == {"folds": 1, "lams": 4}


def _mesh(rows, n):
    return Mesh(np.asarray(jax.devices()[:rows * n]).reshape(rows, n),
                (shardlib.CV_FOLD_AXIS, shardlib.CV_LAM_AXIS))


@pytest.mark.parametrize("k,rows,n", [(5, 1, 4), (4, 2, 2)])
def test_each_device_is_handed_its_pairs_folds_and_no_holdout_row(
        monkeypatch, k, rows, n):
    folds = _folds(k)
    mesh = _mesh(rows, n)
    eng = _engine(4, mesh=mesh)
    handed = {}

    def spy(name, fn):
        def call(*args):
            handed[name] = args
            return fn(*args)
        return call

    solve, score = eng._pair_sweep_fn(mesh), eng._score_fn()
    monkeypatch.setattr(eng, "_pair_sweep_fn", lambda m: spy("sweep", solve))
    monkeypatch.setattr(eng, "_score_fn", lambda: spy("score", score))
    div = eng.run(folds, LAMS)
    one = _engine(4).run(folds, LAMS)
    np.testing.assert_allclose(div.errors, one.errors, rtol=1e-12,
                               atol=1e-14)
    assert div.best_lam == one.best_lam

    # the four-device program: each device's block of the train Hessians
    # is its pairs' folds of its fold row, and no hold-out row is handed
    h_pairs, g_tr, _ = handed["sweep"]
    lay = shardlib.PairLayout(k // rows, 4, n, H, BLOCK)
    local = lay.device_folds()
    hess, fold_hess = np.asarray(folds.hess), np.asarray(folds.fold_hess)
    for shard in h_pairs.addressable_shards:
        r, j = map(int, np.argwhere(mesh.devices == shard.device)[0])
        want = hess - fold_hess[r * (k // rows) + local[j]]
        np.testing.assert_array_equal(np.asarray(shard.data)[0], want)
    assert len(h_pairs.addressable_shards) == 4
    # scoring: every array it is handed lives on the designs' device
    home = folds.x_folds.devices()
    assert all(a.devices() == home for a in handed["score"])

    shard = div.extras["engine"]["shard"]
    assert shard["inputs"] == "by_pair"
    assert shard["placed_bytes"] == 3 * (local.shape[1] * H * H
                                         + k // rows * H) * 8
    assert shard["pairs_per_device"] == lay.per_device


def test_refining_policy_keeps_every_fold_on_every_device():
    """Under ``bf16_refined`` the λ stage reads each fold's train Hessian
    on every λ shard, so the sweep keeps them whole on every device."""
    folds = _folds(5)
    div = _engine(4, mesh="auto", precision="bf16_refined").run(folds, LAMS)
    one = _engine(4, precision="bf16_refined").run(folds, LAMS)
    shard = div.extras["engine"]["shard"]
    assert shard["inputs"] == "replicated"
    n_f = folds.x_folds.shape[1]
    assert shard["placed_bytes"] == 3 * 5 * (H * H + H + n_f * H + n_f) * 8
    np.testing.assert_allclose(div.errors, one.errors, rtol=1e-12,
                               atol=1e-14)
    assert div.best_lam == one.best_lam
