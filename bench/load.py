"""The one load generator: requests from a traffic mix, and the loops that
drive the program with them.

A mix is a data file in ``bench/traffic/``, named after the mix.  What
varies between mixes is only data: how many designs and targets, how
requests pick among them (in turn, or by Zipf popularity), the λ grids,
the arrival process (a closed loop with one client, or an open loop at a
fixed rate), and what the program is driven through:

* ``"entry": "engine"`` — one ``CVEngine``, called by one client;
  ``"cache": "none"`` gives it no factor cache (every problem is cold),
  ``"warm"`` gives it one that set-up fills with every design;
* ``"entry": "server"`` — one ``CVSweepServer`` (``submit``/``step``),
  with its factor cache emptied when the window opens.

Every seed gets the same multiset of designs ranks, grids and arrival gaps,
in another order, so seeds change which request comes when and not how much
work a window holds.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np

@dataclasses.dataclass
class Request:
    index: int
    due_s: float          # offset of its due time from the window's open
    design: int
    target: int
    grid: int             # index into Load.grids
    tenant: str


@dataclasses.dataclass
class Record:
    request: Request
    result: object = None  # the program's CVResult
    error: str = ""
    submit_s: float = math.nan   # offsets from the window's open
    done_s: float = math.inf
    batch: int = 1


def _counts(weights: np.ndarray, n: int) -> np.ndarray:
    """Largest-remainder rounding of n·weights to whole counts."""
    raw = weights / weights.sum() * n
    out = np.floor(raw).astype(int)
    out[np.argsort(out - raw)[: n - out.sum()]] += 1
    return out


def grids_of(mix: dict, cfg: dict) -> list:
    """(lo, hi, q) of each grid the mix uses: its palette, then the
    shifted grid if any.  A palette entry ``"paper"`` is the config's."""
    out = []
    for gdef in mix["grids"]:
        if gdef == "paper":
            gdef = cfg["grid"]
        out.append((float(gdef["lo"]), float(gdef["hi"]), int(gdef["q"])))
    if mix.get("shifted"):
        s = mix["shifted"]
        out.append((float(s["lo"]), float(s["hi"]), int(s["q"])))
    return out


def requests(mix: dict, seed: int, count: int, seconds: float) -> list:
    """The first ``count`` requests of the mix under ``seed``."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    n_d = int(mix["designs"])
    if mix.get("pick", "cycle") == "zipf":
        w = 1.0 / np.arange(1, n_d + 1) ** float(mix["zipf_a"])
        designs = np.repeat(np.arange(n_d), _counts(w, count))
        designs = rng.permutation(n_d)[rng.permutation(designs)]
    else:
        designs = (np.arange(count) + rng.integers(n_d)) % n_d
    n_t = int(mix.get("targets", 1))
    t_order = rng.permutation(n_t)
    targets = t_order[np.arange(count) % n_t]
    n_g = len(mix["grids"])
    every = (mix.get("shifted") or {}).get("every", 0)
    shifted = (np.arange(count) % every == every - 1) if every \
        else np.zeros(count, bool)
    grid = np.full(count, n_g)
    grid[~shifted] = rng.permutation(np.arange((~shifted).sum()) % n_g)
    if mix["loop"] == "open":
        u = (np.arange(count) + 0.5) / count
        gaps = rng.permutation(-np.log1p(-u))        # exponential quantiles
        due = np.cumsum(gaps)
        due = due / (due[-1] + gaps.mean()) * seconds  # mean rate count/s
    else:
        due = np.zeros(count)
    n_ten = int(mix.get("tenants", 1))
    return [Request(i, float(due[i]), int(designs[i]), int(targets[i]),
                    int(grid[i]), f"tenant-{i % n_ten}")
            for i in range(count)]


def offered(mix: dict, seconds: float) -> int:
    """How many requests an open loop offers in a window."""
    return max(1, round(float(mix["rate_per_s"]) * seconds))


def closed_loop(call, reqs: list, seconds: float, span) -> tuple:
    """One client: the next request starts when the last has returned, and
    none starts once the window has closed.  ``call(req)`` returns the
    result.  Returns (records, window seconds), the window ending when the
    last problem that began inside it ended."""
    records = []
    t0 = time.perf_counter()
    with span("window"):
        for req in reqs:
            start = time.perf_counter() - t0
            if start >= seconds:
                break
            rec = Record(req, submit_s=start)
            with span("problem"):
                try:
                    rec.result = call(req)
                except Exception as e:           # noqa: BLE001 — a failed
                    rec.error = f"{type(e).__name__}: {e}"  # problem counts
            rec.done_s = time.perf_counter() - t0
            records.append(rec)
        else:
            raise RuntimeError(f"the window outlasted {len(reqs)} requests")
    return records, records[-1].done_s


def open_loop(server, make_request, reqs: list, seconds: float,
              span) -> tuple:
    """Submit each request at its due time, step the server whenever it
    has work, and drain the queue after the window.  Returns (records,
    window seconds)."""
    records = {r.index: Record(r) for r in reqs}
    by_id = {}
    queue = collections.deque(reqs)
    t0 = time.perf_counter()
    with span("window"):
        while queue or server.pending:
            now = time.perf_counter() - t0
            while queue and queue[0].due_s <= now:
                req = queue.popleft()
                with span("submit"):
                    rid = server.submit(make_request(req))
                by_id[rid] = records[req.index]
                by_id[rid].submit_s = time.perf_counter() - t0
                now = by_id[rid].submit_s
            if server.pending:
                with span("step"):
                    try:
                        out = server.step()
                    except Exception as e:   # noqa: BLE001 — the batch is
                        out, err = [], f"{type(e).__name__}: {e}"  # lost
                        for rec in by_id.values():
                            if rec.result is None and not rec.error:
                                rec.error = err
                done = time.perf_counter() - t0
                for resp in out:
                    rec = by_id[resp.request_id]
                    rec.result, rec.done_s = resp.result, done
                    rec.batch = resp.batch_size
                    rec.error = ""
            elif queue:
                with span("host_wait"):
                    time.sleep(max(0.0, queue[0].due_s - now))
    return [records[r.index] for r in reqs], seconds
