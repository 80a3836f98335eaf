"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT, ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


#: cell -> (configuration, traffic mix, its end-to-end metric)
CELLS = {"paper_h4096_cold": ("paper_h4096", "cold_designs", "cv_s"),
         "paper_h4096_targets": ("paper_h4096", "warm_targets", "cv_s"),
         "cv_service_zipf": ("cv_service_h2048", "zipf_open", "req_p90_s")}


def small_spec(workload: str, h: int = 256) -> dict:
    """A cell, from its configuration and mix files, cut to a CPU-sized
    design."""
    config, traffic, metric = CELLS[workload]
    bench = ROOT / "bench"
    cfg = json.loads((bench / "configs" / f"{config}.json").read_text())
    mix = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    cfg.update(h=h, block=128, grid=dict(cfg["grid"], q=7))
    if mix["loop"] == "open":
        mix.update(designs=3, rate_per_s=4.0,
                   grids=[dict(g, q=q) for g, q in zip(mix["grids"], (5, 7))])
        cfg["server"]["max_batch"] = 2
    if mix.get("targets", 1) > 1:
        mix["targets"] = 3
    unit = {"cv_s": "s", "req_p90_s": "s", "setup_s": "s"}
    return dict(cell=dict(name=workload, config=config, traffic=traffic,
                          chips=1),
                config=cfg, mix=mix, per_layer=[],
                end_to_end=[dict(name=m, unit=unit[m])
                            for m in (metric, "setup_s")])


@pytest.fixture
def small():
    return small_spec
