"""One-off sweep of offered rates for an open-loop cell, to find its knee.

    python3 bench/sweep_rate.py --workload cv_service_zipf --seed 1 \
        --seconds 30 --rates 2 4 6 8 10

Sets the cell up once, then runs one window per rate (the factor cache
emptied before each) and prints, per rate, the 90th percentile of
completion less due time, the requests completed inside the window per
second, and the backlog: requests still unanswered when the window closed.
The knee is the highest rate whose backlog does not grow and whose p90 stays
under the latency limit that ``PERF.md`` states; the cell then offers a
fixed fraction of it, written as a number in its traffic file.  Not part of
a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import run as harness  # bench/run.py puts the repo and src/ on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.enable_compile_cache(harness.ROOT / ".jax_cache")
    harness.device_info(int(spec["cell"]["chips"]))
    from bench import cells, load, readers

    t0 = time.perf_counter()
    cell = cells.build(spec["config"], spec["mix"], args.seed, args.seconds)
    cell.setup()
    print(f"[sweep] set-up {time.perf_counter() - t0:.1f} s", flush=True)
    rows = []
    for rate in args.rates:
        mix = dict(spec["mix"], rate_per_s=rate)
        cell.reqs = load.requests(mix, args.seed, load.offered(
            mix, args.seconds), args.seconds)
        cell.empty_server_cache()
        base = cell.server_counts()
        records, window_s = cell.window(args.seconds)
        lat = [r.done_s - r.request.due_s if r.result is not None
               else math.inf for r in records]
        done_in = sum(1 for r in records if r.done_s <= window_s)
        now = cell.server_counts()
        row = dict(rate=rate, offered=len(records),
                   p50_s=readers.percentile(lat, 50),
                   p90_s=readers.percentile(lat, 90),
                   max_s=max(lat), req_per_s=done_in / window_s,
                   backlog=len(records) - done_in,
                   drain_s=max(r.done_s for r in records) - window_s,
                   hits=now["hits"] - base["hits"],
                   misses=now["misses"] - base["misses"],
                   batch_mean=(now["served"] - base["served"])
                   / max(1, now["dispatches"] - base["dispatches"]),
                   failed=sum(1 for r in records if r.result is None))
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps(dict(workload=args.workload, seed=args.seed,
                          seconds=args.seconds, rows=rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
