"""A whole run, with the device check skipped, at a CPU size: sound, it is
correct; with the timed path broken underneath, ``correct`` comes out
false.  The faults are those a CV cell can have: an answer altered where
it is produced (a curve scaled, or λ* picked off the curve's argmin), and
half of the batch (the folds) left out with the mean taken over the
rest."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import PEAK

from bench import run

CELLS = ["paper_h4096_cold", "paper_h4096_targets", "cv_service_zipf"]
SEED = 2**31 + 4242


def run_small(spec):
    return run.run_cell(spec, SEED, 2.0, False, t_start=time.perf_counter(),
                        dev=None, peak=PEAK,
                        log=lambda msg: None)


def alter_answers(monkeypatch):
    from repro.core import engine
    orig = engine._errors_from_thetas
    monkeypatch.setattr(engine, "_errors_from_thetas",
                        lambda *a: orig(*a) * 1.01)


def pick_next_lam(monkeypatch):
    from repro.core import folds
    orig = folds.CVResult.from_errors

    def from_errors(lams, errors, n_exact, **extras):
        res = orig(lams, errors, n_exact, **extras)
        i = int(np.argmin(np.abs(np.asarray(lams) - res.best_lam)))
        res.best_lam = float(np.asarray(lams)[(i + 1) % len(lams)])
        return res

    monkeypatch.setattr(folds.CVResult, "from_errors",
                        staticmethod(from_errors))


def leave_out_half(monkeypatch):
    from repro.core.engine import CVEngine

    def halved(make):
        def make_fn(self, mesh):
            fn = make(self, mesh)

            def run_fn(*args):
                errs = fn(*args)                      # (k, q)
                keep = errs[: (errs.shape[0] + 1) // 2].mean(0)
                return jnp.broadcast_to(keep, errs.shape)
            return run_fn
        return make_fn

    monkeypatch.setattr(CVEngine, "_sweep_fn", halved(CVEngine._sweep_fn))
    monkeypatch.setattr(CVEngine, "_replay_fn", halved(CVEngine._replay_fn))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(small, workload):
    out = run_small(small(workload))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [alter_answers, pick_next_lam,
                                   leave_out_half])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(small, monkeypatch, workload, fault):
    fault(monkeypatch)
    out = run_small(small(workload))
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
