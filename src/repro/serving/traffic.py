"""Deterministic synthetic multi-tenant traffic.

A seeded Zipf mix over a small population of distinct ridge problems:
request r draws problem p with probability ∝ 1/rank(p)^a — a few hot
Hessians dominate (they are the cache's amortization opportunity) with a
long cold tail — then draws a λ grid from a palette of sizes over the
*same* decades (identical anchors → cross-tenant sharing) plus an
optional shifted range (different anchors → admission into a separate
group).  Tenants round-robin over the request stream, so hot problems
are shared across tenants by construction.

Everything is a pure function of :class:`TrafficConfig` — the serving
tests replay the exact same stream.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.testing import strategies as props

from .server import SweepRequest

__all__ = ["TrafficConfig", "make_traffic"]


@dataclasses.dataclass(frozen=True)
class TrafficConfig:
    """Knobs of the synthetic workload (all defaults CPU-sized).

    n_problems distinct fold datasets are ranked by popularity; Zipf
    exponent ``zipf_a`` sets how hot the head is (higher = hotter).
    ``grid_sizes`` λ grids span the canonical test decades so they share
    anchors; a ``shifted_grid_every``-th request instead sweeps a shifted
    range (distinct anchors — exercises multi-group admission).
    """

    n_requests: int = 48
    n_tenants: int = 6
    n_problems: int = 8
    h: int = 32
    n: int = 256
    k: int = 4
    zipf_a: float = 1.2
    seed: int = 0
    dtype: str = "float64"
    grid_sizes: Tuple[int, ...] = (17, 25, 33)
    shifted_grid_every: int = 0      # 0 disables the shifted-range grids
    precision: Optional[str] = None


def zipf_weights(n: int, a: float) -> np.ndarray:
    """Normalized rank-popularity weights w_r ∝ 1/r^a, r = 1..n."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def make_traffic(cfg: TrafficConfig) -> List[SweepRequest]:
    """The request stream for ``cfg`` — deterministic in ``cfg.seed``."""
    import jax.numpy as jnp

    rng = np.random.default_rng(cfg.seed)
    dtype = jnp.dtype(cfg.dtype)
    problems = [props.regression_folds(h=cfg.h, n=cfg.n, k=cfg.k,
                                       seed=1000 * (cfg.seed + 1) + p,
                                       dtype=dtype)
                for p in range(cfg.n_problems)]
    grids = [props.log_grid(q) for q in cfg.grid_sizes]
    lo, hi = props.DEFAULT_GRID_RANGE
    shifted = props.log_grid(cfg.grid_sizes[0], lo + 1.0, hi + 1.0)

    picks = rng.choice(cfg.n_problems, size=cfg.n_requests,
                       p=zipf_weights(cfg.n_problems, cfg.zipf_a))
    grid_picks = rng.integers(0, len(grids), size=cfg.n_requests)
    reqs = []
    for r in range(cfg.n_requests):
        lams = (shifted if cfg.shifted_grid_every
                and (r + 1) % cfg.shifted_grid_every == 0
                else grids[int(grid_picks[r])])
        reqs.append(SweepRequest(
            tenant=f"tenant-{r % cfg.n_tenants}",
            folds=problems[int(picks[r])], lams=lams,
            precision=cfg.precision))
    return reqs
