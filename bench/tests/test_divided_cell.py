"""The four-chip cell at a CPU size: a whole run of ``paper_h8192_4chip``'s
configuration at h=256 on 4 host devices, the device check skipped, is
correct against the plain reference; with one fold left out of the mean it
is not.

The run needs the 4 host devices before JAX starts, so it runs in a child
process.  There the device reports too little free memory for the
one-device sweep, so the engine's default mesh is every device, as on the
chip at h=8192: folds 1 × lams 4, the state stage divided.
"""
import json
import os
import pathlib
import subprocess
import sys

from conftest import PEAK

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 4243
#: at h=256 the program reads about 1e-6 and the control above 1e-5 (see
#: test_control.py); a limit between them, for this test only
SMALL_LIMIT = 5e-6

CHILD = """
import json, sys, time
sys.path[:0] = [{src!r}, {root!r}, {bench!r}]
import jax.numpy as jnp
from bench import run
from repro.core.engine import CVEngine
from repro.distributed import sharding

sharding.device_bytes_free = lambda device: 1   # the sweep fits no device
shards = []
engine_run = CVEngine.run


def run_and_count(self, *args, **kw):
    res = engine_run(self, *args, **kw)
    shards.append(dict(res.extras["engine"]["shard"],
                       mesh=res.extras["engine"]["mesh"]))
    return res
CVEngine.run = run_and_count
if {fault!r}:
    make = CVEngine._sweep_fn

    def one_fold_left_out(self, mesh):
        fn = make(self, mesh)

        def run_fn(*args):
            errs = fn(*args)                      # (k, q)
            return jnp.broadcast_to(errs[1:].mean(0), errs.shape)
        return run_fn
    CVEngine._sweep_fn = one_fold_left_out
spec = json.loads({spec!r})
out = run.run_cell(spec, {seed!r}, 2.0, False, t_start=time.perf_counter(),
                   dev=None, peak={peak!r}, log=lambda msg: None)
print(json.dumps(dict(out, shards=shards)))
"""


def small_spec() -> dict:
    bench = ROOT / "bench"
    cfg = json.loads((bench / "configs" / "paper_h8192_4chip.json")
                     .read_text())
    mix = json.loads((bench / "traffic" / "cold_designs.json").read_text())
    cfg.update(h=256, block=64, grid=dict(cfg["grid"], q=7))
    cfg["limits"]["curve_gap"] = SMALL_LIMIT
    return dict(cell=dict(name="paper_h8192_4chip_cold",
                          config="paper_h8192_4chip",
                          traffic="cold_designs", chips=4),
                config=cfg, mix=mix, per_layer=[],
                end_to_end=[dict(name=m, unit=u) for m, u in
                            (("cv_s", "s"), ("setup_s", "s"))])


def run_on_four_devices(fault: bool) -> dict:
    code = CHILD.format(src=str(ROOT / "src"), root=str(ROOT),
                        bench=str(ROOT / "bench"), fault=fault,
                        spec=json.dumps(small_spec()), seed=SEED, peak=PEAK)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices_is_correct():
    out = run_on_four_devices(fault=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # the state stage was divided: 5 of the 20 pairs a device, and each
    # device received the others' slabs
    assert out["shards"] and all(
        s["mesh"] == {"folds": 1, "lams": 4} and s["pairs_per_device"] == 5
        and s["exchange_bytes"] > 0 for s in out["shards"])


def test_one_fold_left_out_is_not_correct():
    out = run_on_four_devices(fault=True)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["curve_gap"]
    assert gap["value"] > gap["limit"]

