"""Shared benchmark utilities: timing, CSV emission, problem construction."""
from __future__ import annotations

import os
import time
from typing import Callable

import jax
import jax.numpy as jnp

# benchmark scale: paper uses h up to 16384; this container is 1-core CPU,
# so default sizes are scaled down. REPRO_BENCH_SCALE=paper restores larger h.
SCALE = os.environ.get("REPRO_BENCH_SCALE", "ci")
SIZES = {"ci": [256, 512], "mid": [512, 1024, 2048],
         "paper": [1024, 2048, 4096]}[SCALE]

# REPRO_BENCH_SMOKE=1: tiny problems — CI runs the roofline bench this way
# to check that it runs, not to measure anything.
SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def timeit(fn: Callable, *args, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def ridge_problem(h: int, n: int | None = None, seed: int = 0):
    from repro.data import make_regression_dataset
    n = n or max(2 * h, 512)
    x, y = make_regression_dataset(jax.random.PRNGKey(seed), n, h,
                                   dtype=jnp.float64)
    return x, y
