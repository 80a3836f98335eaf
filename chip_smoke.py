"""Smoke run of the CV engine and the sweep server on a TPU.

    python chip_smoke.py             # one chip: paper configuration + server
    python chip_smoke.py --chips 4   # four chips: the fold-sharded sweep only

One process.  With no option it runs, in order:

1. device check — the first device must be a TPU (there is no CPU path);
2. the paper configuration (``repro.configs.picholesky``) at h=4096,
   n=4h, k=5, q=31, g=4, degree 2, block 128, through ``CVEngine`` with
   ``backend="auto"`` under ``fp32`` and ``bf16_refined``: the fused sweep
   (``run`` without a cache), the cached sweep (``run`` twice on one
   ``FactorCache``; the second is a hit with no Cholesky) and the staged
   sweep (``search``).  Each is held against the plain f32 oracle,
   ``CVEngine("exact", backend="reference")`` at the highest matmul
   precision: the picked λ may cost at most 1e-3 relative hold-out error
   on the oracle's curve, and the three paths must pick the same λ;
3. the server — six requests from three tenants at h=1024, f32, through
   one ``CVSweepServer``; every response's λ* must equal a solo
   ``CVEngine.run`` of the same problem.

``--chips 4`` runs only the fold-sharded sweep (h=4096, k=4, so the fold
axis takes all four chips) and the same problem unsharded on device 0.

The data are ``make_regression_dataset``'s degree-2 random polynomial
features of ``2·√h`` raw inputs, whose monomials outnumber 2h, so the
design has full column rank (the default 64 inputs span only 2145
monomials: at h=4096 the Hessian would have 1951 zero eigenvalues).  The
features are scaled by 1/√n, so λ is per sample and the paper's grid
[1e-3, 1] brackets the hold-out optimum.  Seconds printed include
compilation and are first observations, not metrics.  The last line of a
passing run is one JSON object naming the device; a failing run exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.picholesky import CONFIG  # noqa: E402
from repro.core import CVEngine, FactorCache, make_folds  # noqa: E402
from repro.core.engine import PiCholeskyStrategy  # noqa: E402
from repro.data import make_regression_dataset  # noqa: E402
from repro.serving import CVSweepServer, TrafficConfig, make_traffic  # noqa: E402

PAPER_H = 4096
SERVER_H = 1024
MAX_REGRET = 1e-3          # relative hold-out error of λ* on the oracle curve
MAX_SHARD_DIFF = 1e-5      # relative curve difference, sharded vs unsharded


class SmokeError(RuntimeError):
    """A phase's result is wrong."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


class KernelLog:
    """Counts the Pallas kernels traced inside the block by their
    ``interpret`` flag.  Jit caches are cleared on entry so every kernel of
    the block is traced again and counted."""

    def __enter__(self):
        from jax.experimental import pallas as pl
        self._pl, self._orig = pl, pl.pallas_call
        self.interpret = collections.Counter()

        def recording(*args, **kwargs):
            self.interpret[bool(kwargs.get("interpret", False))] += 1
            return self._orig(*args, **kwargs)

        jax.clear_caches()
        pl.pallas_call = recording
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig
        return False


def device_check(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is ``chips`` TPUs."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's first device is {dev['platform']!r}")
    check(dev["count"] == chips,
          f"expected {chips} chip(s), JAX sees {dev['count']}")
    return dev


def paper_problem(h: int, k: int, seed: int = 0):
    """(folds, λ grid) of the paper configuration at width ``h``: n = 4h
    rows in f32 of full column rank, scaled by 1/√n, and q log-spaced λ in
    [lam_lo, lam_hi]."""
    n = 4 * h
    x, y = make_regression_dataset(jax.random.PRNGKey(seed), n, h,
                                   raw_dim=2 * math.isqrt(h),
                                   dtype=jnp.float32)
    x = x / jnp.sqrt(jnp.float32(n))
    lams = jnp.logspace(math.log10(CONFIG.lam_lo), math.log10(CONFIG.lam_hi),
                        CONFIG.n_lambdas, dtype=jnp.float32)
    return make_folds(x, y, k), lams


def paper_strategy(block: int) -> PiCholeskyStrategy:
    return PiCholeskyStrategy(g=CONFIG.g_samples, degree=CONFIG.degree,
                              block=block)


class Oracle:
    """Exact per-λ Cholesky through ``jnp.linalg`` in f32 at the highest
    matmul precision: the curve every path is held against."""

    def __init__(self, folds, lams):
        self.folds = folds
        self.engine = CVEngine("exact", backend="reference",
                               precision="fp32")
        t0 = time.perf_counter()
        self.curve = self._run(lams)
        say(f"[oracle] exact/reference fp32: λ*={self.curve.best_lam:.6g} "
            f"error={self.curve.best_error:.7g} "
            f"({time.perf_counter() - t0:.1f} s first run)")

    def _run(self, lams):
        with jax.default_matmul_precision("highest"):
            return self.engine.run(self.folds, lams)

    def error_at(self, lam: float) -> float:
        on_grid = np.flatnonzero(self.curve.lams == np.float32(lam))
        if on_grid.size:
            return float(self.curve.errors[on_grid[0]])
        lam = jnp.asarray([lam], self.curve.lams.dtype)
        return float(self._run(lam).errors[0])

    def regret(self, lam: float) -> float:
        best = self.curve.best_error
        return (self.error_at(lam) - best) / best


def engine_phase(folds, lams, oracle: Oracle, precision: str, *,
                 block: int = CONFIG.block, backend: str = "auto",
                 expect_backend: str = "pallas",
                 interpret: bool = False) -> dict:
    """The fused, cached and staged sweeps of one problem under one
    precision policy, each checked against ``oracle``.  ``interpret`` is
    the mode every traced kernel must have run in.  Returns the λ* of each
    path."""
    tag = f"[paper {precision}]"
    strategy = paper_strategy(block)

    def engine(**kw):
        return CVEngine(strategy, backend=backend, precision=precision, **kw)

    def timed(fn):
        t0 = time.perf_counter()
        res = fn()
        return res, time.perf_counter() - t0

    with KernelLog() as log:
        fused, t_fused = timed(lambda: engine().run(folds, lams))
        cached_eng = engine(cache=FactorCache())
        cold, t_cold = timed(lambda: cached_eng.run(folds, lams))
        warm, t_warm = timed(lambda: cached_eng.run(folds, lams))
        staged, t_staged = timed(lambda: engine().search(folds, lams))

    for res in (fused, cold, warm, staged):
        check(res.extras["engine"]["backend"] == expect_backend,
              f"{tag} engine resolved backend "
              f"{res.extras['engine']['backend']!r}, not {expect_backend!r}")
    n_kernels = sum(log.interpret.values())
    say(f"{tag} backend={fused.extras['engine']['backend']} "
        f"kernels traced={n_kernels} interpret=True: "
        f"{log.interpret[True]}")
    if expect_backend == "pallas":
        check(log.interpret[interpret] > 0 and
              log.interpret[not interpret] == 0,
              f"{tag} kernels traced by interpret flag: "
              f"{dict(log.interpret)}; want all interpret={interpret}")

    cold_info, warm_info = (cold.extras["engine"]["cache"],
                            warm.extras["engine"]["cache"])
    check(cold_info["status"] == "miss",
          f"{tag} first cached run: {cold_info['status']}, not a miss")
    check(warm_info["status"] == "hit" and warm.n_exact_chol == 0,
          f"{tag} second cached run: {warm_info['status']} with "
          f"{warm.n_exact_chol} Choleskys, not a hit with 0")

    ref = oracle.curve
    for name, res, secs in (("fused", fused, t_fused),
                            ("cached cold", cold, t_cold),
                            ("cached warm", warm, t_warm)):
        diff = float(np.max(np.abs(res.errors - ref.errors) / ref.errors))
        status = (res.extras["engine"]["cache"] or {}).get("status", "none")
        r = oracle.regret(res.best_lam)
        say(f"{tag} {name}: λ*={res.best_lam:.6g} regret={r:.3g} "
            f"max relative |curve-oracle|={diff:.3g} "
            f"n_chol={res.n_exact_chol} cache={status} {secs:.1f} s")
        check(r <= MAX_REGRET, f"{tag} {name} regret {r:.3g} > {MAX_REGRET}")
        check(res.best_lam == fused.best_lam,
              f"{tag} {name} λ*={res.best_lam} != fused λ*={fused.best_lam}")

    search = staged.extras["engine"]["search"]
    r = oracle.regret(staged.best_lam)
    # search refines over log λ off the grid: it agrees with the grid when
    # its λ* is within half a grid step plus its final bracket of it
    step = float(np.log10(ref.lams[1] / ref.lams[0]))
    off = abs(math.log10(staged.best_lam / fused.best_lam))
    say(f"{tag} staged (search): λ*={staged.best_lam:.6g} regret={r:.3g} "
        f"|log10 λ*/fused λ*|={off:.3g} waves={search['waves']} "
        f"evaluated={search['lams_evaluated']} n_chol={staged.n_exact_chol} "
        f"{t_staged:.1f} s")
    check(r <= MAX_REGRET, f"{tag} staged regret {r:.3g} > {MAX_REGRET}")
    check(off <= step / 2 + search["interval_decades"],
          f"{tag} staged λ*={staged.best_lam} is {off:.3g} decades from "
          f"the fused λ*={fused.best_lam}")
    say(f"{tag} seconds above include compilation: first observations, "
        f"not metrics")
    return {"fused": fused.best_lam, "cached": warm.best_lam,
            "staged": staged.best_lam}


def server_phase(h: int = SERVER_H, *, n_requests: int = 6,
                 n_tenants: int = 3, block: int = CONFIG.block,
                 backend: str = "auto", expect_backend: str = "pallas"
                 ) -> list:
    """``n_requests`` requests from ``n_tenants`` tenants through one
    server in f32; each λ* must equal a solo run of its problem.  Returns
    the responses."""
    cfg = TrafficConfig(n_requests=n_requests, n_tenants=n_tenants,
                        n_problems=2, h=h, n=4 * h, dtype="float32")
    reqs = make_traffic(cfg)
    strategy = paper_strategy(block)
    srv = CVSweepServer(strategy, backend=backend, precision="fp32")
    t0 = time.perf_counter()
    for req in reqs:
        srv.submit(req)
    resps = srv.drain()
    t_serve = time.perf_counter() - t0
    check(len(resps) == n_requests,
          f"[server] {len(resps)} responses to {n_requests} requests")

    solo_engine = CVEngine(strategy, backend=backend, precision="fp32")
    solo: dict = {}
    by_id = {req.request_id: req for req in reqs}
    for resp in sorted(resps, key=lambda r: r.request_id):
        req = by_id[resp.request_id]
        key = (id(req.folds), id(req.lams))
        if key not in solo:
            solo[key] = solo_engine.run(req.folds, req.lams)
        want = solo[key]
        got = resp.result
        check(got.extras["engine"]["backend"] == expect_backend,
              f"[server] backend {got.extras['engine']['backend']!r}")
        say(f"[server] request {resp.request_id} {resp.tenant} "
            f"q={len(got.lams)} status={resp.status} batch={resp.batch_size} "
            f"λ*={got.best_lam:.6g} solo λ*={want.best_lam:.6g} "
            f"max|curve-solo|={np.max(np.abs(got.errors - want.errors)):.3g}")
        check(got.best_lam == want.best_lam,
              f"[server] request {resp.request_id}: λ*={got.best_lam} "
              f"!= solo λ*={want.best_lam}")
    say(f"[server] {n_requests} requests, {n_tenants} tenants, h={h}: "
        f"{srv.dispatches} dispatches, hit rate {srv.cache.hit_rate():.2f}, "
        f"{t_serve:.1f} s first run (compilation included; not a metric)")
    return resps


def sharded_phase(h: int = PAPER_H, k: int = 4, *,
                  block: int = CONFIG.block, backend: str = "auto") -> dict:
    """The fused sweep sharded over every device (fold axis first), against
    the same problem unsharded on device 0.  Returns the mesh used."""
    folds, lams = paper_problem(h, k)
    strategy = paper_strategy(block)
    t0 = time.perf_counter()
    sharded = CVEngine(strategy, backend=backend, precision="fp32",
                       mesh="auto").run(folds, lams)
    t_sharded = time.perf_counter() - t0
    with jax.default_device(jax.devices()[0]):
        t0 = time.perf_counter()
        single = CVEngine(strategy, backend=backend,
                          precision="fp32").run(folds, lams)
        t_single = time.perf_counter() - t0
    mesh = sharded.extras["engine"]["mesh"]
    rel = float(np.max(np.abs(sharded.errors - single.errors)
                       / np.abs(single.errors)))
    say(f"[sharded] mesh={mesh} backend={sharded.extras['engine']['backend']}"
        f" λ*={sharded.best_lam:.6g} unsharded λ*={single.best_lam:.6g} "
        f"max relative curve difference={rel:.3g}")
    say(f"[sharded] {t_sharded:.1f} s sharded, {t_single:.1f} s unsharded, "
        f"first runs (compilation included; not metrics)")
    n_dev = len(jax.devices())
    check(mesh is not None and mesh.get("folds") == math.gcd(k, n_dev),
          f"[sharded] mesh {mesh}: the fold axis does not take "
          f"{math.gcd(k, n_dev)} devices")
    check(sharded.best_lam == single.best_lam,
          f"[sharded] λ*={sharded.best_lam} != unsharded "
          f"λ*={single.best_lam}")
    check(rel <= MAX_SHARD_DIFF,
          f"[sharded] curves differ by {rel:.3g} relative > "
          f"{MAX_SHARD_DIFF}")
    return mesh


def last_line(dev: dict) -> str:
    return json.dumps({"ok": True, "device": dev})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fold-sharded sweep on four chips")
    args = ap.parse_args(argv)
    try:
        dev = device_check(args.chips)
        say(f"[cache] compilation cache at "
            f"{enable_compile_cache(REPO / '.jax_cache')}")
        if args.chips == 4:
            sharded_phase()
        else:
            folds, lams = paper_problem(PAPER_H, CONFIG.k_folds)
            oracle = Oracle(folds, lams)
            for precision in ("fp32", "bf16_refined"):
                engine_phase(folds, lams, oracle, precision)
            server_phase()
    except SmokeError as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(last_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
