"""chip_smoke.py's phases at tiny sizes on CPU, with the Pallas kernels in
interpret mode: control flow, the oracle comparison and the last line.
Its ``main`` must refuse a CPU device."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib

import jax
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

H, BLOCK = 64, 16


@pytest.fixture(scope="module")
def paper():
    folds, lams = cs.paper_problem(H, 5)
    return folds, lams, cs.Oracle(folds, lams)


@pytest.mark.parametrize("precision", ["fp32", "bf16_refined"])
def test_engine_phase_paths_agree_with_oracle(paper, precision):
    folds, lams, oracle = paper
    picks = cs.engine_phase(folds, lams, oracle, precision, block=BLOCK,
                            backend="pallas", interpret=True)
    assert picks["fused"] == picks["cached"]
    for lam in picks.values():
        assert oracle.regret(lam) <= cs.MAX_REGRET


def test_engine_phase_refuses_interpreted_kernels(paper):
    # on CPU every kernel runs interpreted, which the chip run must refuse
    folds, lams, oracle = paper
    with pytest.raises(cs.SmokeError, match="interpret"):
        cs.engine_phase(folds, lams, oracle, "fp32", block=BLOCK,
                        backend="pallas", interpret=False)


def test_engine_phase_refuses_other_backend(paper):
    folds, lams, oracle = paper
    with pytest.raises(cs.SmokeError, match="backend"):
        cs.engine_phase(folds, lams, oracle, "fp32", block=BLOCK,
                        backend="reference")


def test_oracle_regret_off_grid(paper):
    _, lams, oracle = paper
    best = oracle.curve.best_lam
    assert oracle.regret(best) == 0.0
    assert oracle.regret(float(lams[0])) >= 0.0
    # an off-grid λ is evaluated by the oracle itself
    assert oracle.regret(best * 1.01) > -1e-3


def test_server_phase_matches_solo_runs():
    resps = cs.server_phase(H, block=BLOCK, backend="pallas")
    assert len(resps) == 6
    assert {r.tenant for r in resps} == {"tenant-0", "tenant-1", "tenant-2"}
    assert any(r.status == "hit" for r in resps)


def test_sharded_phase_on_virtual_devices():
    if len(jax.devices()) != 4:
        pytest.skip("needs the 4 virtual CPU devices conftest.py sets up")
    mesh = cs.sharded_phase(H, 4, block=BLOCK, backend="pallas")
    assert mesh == {"folds": 4, "lams": 1}


def test_last_line_format():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = cs.last_line(dev)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": dev}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_exits_nonzero_on_cpu(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert cs.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_dir(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    from repro.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert enable_compile_cache(tmp_path / "fixed") == \
            str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache(tmp_path / "fixed") == \
            os.fspath(tmp_path / "fixed")
        assert jax.config.jax_compilation_cache_dir == \
            os.fspath(tmp_path / "fixed")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
