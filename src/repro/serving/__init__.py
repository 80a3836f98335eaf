"""CV-as-a-service: the multi-tenant ridge-CV sweep server.

The paper's economics are amortization — a handful of anchor
factorizations serve an entire λ sweep — and this package is the layer
that amortizes *across tenants*: a request queue
(:class:`~repro.serving.server.CVSweepServer`) admits compatible
problems into one stacked ``fold_state`` dispatch
(:meth:`~repro.core.engine.CVEngine.run_batch`) and serves overlapping
Hessians from one shared content-addressed
:class:`~repro.core.factor_cache.FactorCache`, with per-tenant stat
partitioning and result isolation.

:mod:`~repro.serving.traffic` generates the deterministic Zipf-mix
synthetic workload the serving tests replay.
"""
from .server import CVSweepServer, ServerConfig, SweepRequest, SweepResponse
from .traffic import TrafficConfig, make_traffic

__all__ = [
    "CVSweepServer", "ServerConfig", "SweepRequest", "SweepResponse",
    "TrafficConfig", "make_traffic",
]
