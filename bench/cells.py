"""A cell: one configuration driven by one traffic mix.

:func:`build` reads the configuration (``bench/configs``) and the mix
(``bench/traffic``); the returned :class:`Cell` makes the designs from the
seed, builds the program's engine or server, warms every shape the mix
will use (:meth:`Cell.setup`), runs the window (:meth:`Cell.window`),
frees the program's state (:meth:`Cell.release`) and compares what the
window produced with the reference (:meth:`Cell.check`).

With ``control`` the configuration's control runs in the program's place
(``control`` in the configuration file): the program at a lower
precision of its own, or the plain reference at a lower precision, called
per request as the program would be.  Its records go through the same
window and the same :meth:`Cell.check`.
"""
from __future__ import annotations

import dataclasses

from bench import data, load, reference, work

CLOSED_LOOP_REQUESTS = 100_000     # more than any window completes
STRATEGIES = ("picholesky",)       # what the reference implements


def build(cfg: dict, mix: dict, seed: int, seconds: float, *,
          control: bool = False) -> "Cell":
    if cfg["strategy"] not in STRATEGIES:
        raise ValueError(f"strategy {cfg['strategy']!r}: the reference "
                         f"implements only {STRATEGIES}")
    precision, standin = cfg["precision"], False
    if control:
        ctrl = cfg["control"]
        if ctrl["kind"] == "program":
            precision = ctrl["precision"]
        elif ctrl["kind"] == "reference":
            standin = True
        else:
            raise ValueError(f"unknown control kind {ctrl['kind']!r}")
    return Cell(cfg, mix, seed, seconds, precision, standin)


@dataclasses.dataclass
class StandInResult:
    """What the reference stand-in returns in place of a ``CVResult``."""
    errors: object
    best_lam: float


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Cell:
    def __init__(self, cfg, mix, seed, seconds, precision, standin=False):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.precision, self.standin = precision, standin
        self.grids = load.grids_of(mix, cfg)
        self.reqs = self._requests(seed, seconds)
        self.n_targets = int(mix.get("targets", 1))
        self.engine = self.server = None
        self._base = {}

    def _requests(self, seed: int, seconds: float) -> list:
        count = (load.offered(self.mix, seconds)
                 if self.mix["loop"] == "open" else CLOSED_LOOP_REQUESTS)
        return load.requests(self.mix, seed, count, seconds)

    # -- designs ------------------------------------------------------------

    def design(self, d: int, pool: int = 1):
        """(x, y) of design ``d``: x (n, h), y (targets, n).  Pool 1 is the
        mix's designs, pool 2 the warm-up designs outside it."""
        h = int(self.cfg["h"])
        return data.make_design(data.seed_key(self.seed, pool, d),
                                int(self.cfg["n_per_h"]) * h, h,
                                self.n_targets)

    def _folds(self, d: int, pool: int = 1) -> list:
        """The program's FoldData of each target of design ``d``; the
        targets share the design's Hessians and blocks."""
        from repro.core import make_folds
        x, y = self.design(d, pool)
        k = int(self.cfg["k"])
        first = make_folds(x, y[0], k)
        out = [first]
        for t in range(1, self.n_targets):
            f = make_folds(x, y[t], k)
            out.append(f._replace(hess=first.hess, fold_hess=first.fold_hess,
                                  x_folds=first.x_folds))
        return out

    # -- the program --------------------------------------------------------

    def _strategy(self):
        from repro.core.engine import PiCholeskyStrategy
        c = self.cfg
        return PiCholeskyStrategy(g=int(c["g"]), degree=int(c["degree"]),
                                  block=int(c["block"]))

    def setup(self, warm: bool = True) -> None:
        """Build the program's engine or server, warm every shape the mix
        uses (the server's only with ``warm``), and make the seed's
        designs."""
        from repro.core import CVEngine, FactorCache
        from repro.serving import CVSweepServer, ServerConfig

        self.lams = [data.log_grid(*g) for g in self.grids]
        if self.standin:
            return
        if self.mix["entry"] == "server":
            self.server = CVSweepServer(
                self._strategy(), backend=self.cfg["backend"],
                config=ServerConfig(**self.cfg.get("server", {})),
                precision=self.precision)
            if warm:                # its designs are freed before the pool
                self._warm_server()
        else:
            cache = None if self.mix["cache"] == "none" else FactorCache()
            self.engine = CVEngine(
                self._strategy(), backend=self.cfg["backend"],
                precision=self.precision, cache=cache)
        self._prepare()

    def reseed(self, seed: int, seconds: float) -> None:
        """The same cell under another seed, keeping the warm program:
        new requests and designs, the cache emptied or refilled."""
        self.seed = seed
        self.reqs = self._requests(seed, seconds)
        self.folds = {}
        if not self.standin:
            self._prepare()

    def _prepare(self) -> None:
        from repro.core import FactorCache
        used = sorted({r.design for r in self.reqs})
        grids = sorted({r.grid for r in self.reqs[:1000]})
        self.folds = {d: self._folds(d) for d in used}
        if self.server is not None:
            self.empty_server_cache()
            self._base = self.server_counts()
        elif self.engine.cache is None:
            first = self.folds[self.reqs[0].design][0]
            for g in grids:
                for _ in range(2):
                    self.engine.run(first, self.lams[g])
        else:      # fill the cache with every design, then replay once
            self.engine.cache = FactorCache()
            for folds in self.folds.values():
                for g in grids:
                    self.engine.run(folds[0], self.lams[g])
                    self.engine.run(folds[-1], self.lams[g])
            c = self.engine.cache
            self._base = dict(hits=c.hits, misses=c.misses)

    def empty_server_cache(self) -> None:
        from repro.core import FactorCache
        srv = self.server
        srv.cache = FactorCache(max_bytes=srv.config.cache_bytes)
        srv.engine().cache = srv.cache

    def server_counts(self) -> dict:
        srv = self.server
        return dict(hits=srv.cache.hits, misses=srv.cache.misses,
                    served=srv.served, dispatches=srv.dispatches)

    def _warm_server(self) -> None:
        """Compile every dispatch shape on designs outside the pool: a cold
        batch of each size up to ``max_batch`` for each grid length (the
        server's state program takes the grid), then a replay of each
        grid."""
        from repro.serving import SweepRequest
        srv = self.server
        size = srv.config.max_batch
        warm = [self._folds(d, pool=2)[0] for d in range(size)]
        by_q = {int(lams.shape[0]): lams for lams in self.lams}
        for lams in by_q.values():
            for b in range(1, size + 1):
                self.empty_server_cache()
                for d in range(b):
                    srv.submit(SweepRequest(f"warm-{d}", warm[d], lams))
                srv.step()
        for lams in self.lams:
            srv.submit(SweepRequest("warm-0", warm[0], lams))
            srv.step()

    def _request(self, req):
        from repro.serving import SweepRequest
        return SweepRequest(req.tenant, self.folds[req.design][req.target],
                            self.lams[req.grid])

    def _standin_call(self, req) -> StandInResult:
        """The reference at the control's precision, in place of
        ``CVEngine.run``: the request's design, target and grid."""
        import numpy as np
        x, y = self.design(req.design)
        lams = self.lams[req.grid]
        curve = reference.cv_curves(
            x, y[req.target][:, None], grids=[lams],
            passes=reference.PASSES[self.cfg["control"]["precision"]],
            **self._reference_kw())[0][:, 0]
        return StandInResult(curve, float(lams[int(np.argmin(curve))]))

    def window(self, seconds: float):
        if self.server is not None:
            return load.open_loop(self.server, self._request, self.reqs,
                                  seconds, _span)
        if self.standin:
            return load.closed_loop(self._standin_call, self.reqs, seconds,
                                    _span)
        return load.closed_loop(
            lambda r: self.engine.run(self.folds[r.design][r.target],
                                      self.lams[r.grid]),
            self.reqs, seconds, _span)

    def counters(self) -> dict:
        out = {}
        if self.server is not None:
            now, base = self.server_counts(), self._base
            out = {k: now[k] - base[k] for k in now}
        elif self.engine is not None and self.engine.cache is not None:
            c = self.engine.cache
            out = dict(hits=c.hits - self._base["hits"],
                       misses=c.misses - self._base["misses"])
        return out

    def work(self) -> dict:
        c = self.cfg
        h, k, block = int(c["h"]), int(c["k"]), int(c["block"])
        store = 2 if self.precision.startswith("bf16") else 4
        solves = 2 if self.precision == "bf16_refined" else 1
        q = self.grids[0][2]
        return dict(
            chol=work.anchor_factorization(h=h, k=k, g=int(c["g"]),
                                           block=block),
            interp=work.lambda_stage(h=h, k=k, q=q, degree=int(c["degree"]),
                                     block=block, itemsize=store,
                                     solves=solves))

    def release(self) -> None:
        """Free the program's state (designs, factor caches); the engine
        or server stays, with its compiled programs, for :meth:`reseed`."""
        from repro.core import FactorCache
        self.folds = {}
        if self.server is not None:
            self.empty_server_cache()
        elif self.engine is not None and self.engine.cache is not None:
            self.engine.cache = FactorCache()

    def _reference_kw(self) -> dict:
        c = self.cfg
        return dict(k=int(c["k"]), g=int(c["g"]), degree=int(c["degree"]),
                    block=int(c["block"]),
                    refine=int(c["reference"]["refine"]))

    # -- correctness --------------------------------------------------------

    def check(self, records) -> dict:
        """What the window produced against the reference.

        ``curve_gap``: the largest relative gap of any curve from the
        reference's for the same design, target and grid.  ``lam_mismatch``:
        how many results name a λ* that is not the argmin of their own
        curve (the program picks λ* on the host, apart from the curve)."""
        import numpy as np
        gap = 0.0 if records else float("inf")
        mismatch = 0
        by_design: dict = {}
        for r in records:
            by_design.setdefault(r.request.design, []).append(r)
        for d, recs in sorted(by_design.items()):
            targets = sorted({r.request.target for r in recs})
            grids = sorted({r.request.grid for r in recs})
            x, y = self.design(d)
            curves = reference.cv_curves(
                x, y[np.asarray(targets)].T,
                grids=[self.lams[g] for g in grids],
                passes=reference.PASSES[self.cfg["reference"]["precision"]],
                **self._reference_kw())
            del x, y
            for r in recs:
                if r.result is None:
                    continue
                want = curves[grids.index(r.request.grid)][
                    :, targets.index(r.request.target)]
                got = np.asarray(r.result.errors, np.float64)
                gap = max(gap, reference.curve_gap(got, want))
                lams = np.asarray(self.lams[r.request.grid])
                finite = np.flatnonzero(np.isfinite(got))
                pick = (finite[np.argmin(got[finite])] if finite.size
                        else None)
                if pick is None or float(r.result.best_lam) != \
                        float(lams[pick]):
                    mismatch += 1
        limits = self.cfg["limits"]
        return dict(
            curve_gap=dict(value=gap, limit=float(limits["curve_gap"])),
            lam_mismatch=dict(value=mismatch,
                              limit=int(limits["lam_mismatch"])))
