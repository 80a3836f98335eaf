"""Run a cell's sets on the chip, each run its own process, and report the
spreads that the bounds are set from.

    python3 bench/sets.py --workload <cell> --sets 2 --seeds 11 12 13 \\
        --seconds 51 [--trace-seeds 21 22 23] [--out chiprun_out/<cell>]

Every set runs ``bench/run.py`` once per seed, the seeds in the same order
in every set; then each ``--trace-seeds`` seed runs once with
``--trace 1``.  Each run's last line goes to ``<out>.jsonl`` and its
standard error to ``<out>.log``.  The summary gives, for each end-to-end
metric, each set's median and its spread: the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median.  This is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(workload: str, seed: int, seconds: float, trace: int,
            log) -> dict:
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=HERE.parent)
    wall = time.perf_counter() - t0
    log.write(f"=== seed {seed} trace {trace} rc {proc.returncode} "
              f"wall {wall:.1f} s\n{proc.stderr[-8000:]}\n")
    log.flush()
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        line = None
    return dict(seed=seed, trace=trace, rc=proc.returncode, wall_s=wall,
                line=line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out or f"chiprun_out/{args.workload}")
    out.parent.mkdir(parents=True, exist_ok=True)
    runs = []
    with open(f"{out}.log", "a") as log, open(f"{out}.jsonl", "a") as jl:
        plan = [(s, seed, 0) for s in range(1, args.sets + 1)
                for seed in args.seeds]
        plan += [(0, seed, 1) for seed in args.trace_seeds]
        for set_no, seed, trace in plan:
            r = dict(set=set_no, **one_run(args.workload, seed,
                                           args.seconds, trace, log))
            jl.write(json.dumps(r) + "\n")
            jl.flush()
            runs.append(r)
            line = r["line"] or {}
            print(json.dumps(dict(set=set_no, seed=seed, trace=trace,
                                  rc=r["rc"], correct=line.get("correct"),
                                  metrics={k: v["value"] for k, v in
                                           line.get("metrics", {}).items()},
                                  checks={k: v["value"] for k, v in
                                          line.get("checks", {}).items()},
                                  memory=line.get("device", {}).get(
                                      "memory_peak_bytes"))), flush=True)
    summary = {}
    for set_no in range(1, args.sets + 1):
        lines = [r["line"] for r in runs
                 if r["set"] == set_no and r["line"]]
        for name in sorted({k for ln in lines for k in ln["metrics"]}):
            vals = [ln["metrics"][name]["value"] for ln in lines
                    if name in ln["metrics"]]
            if len(vals) >= 2:
                summary.setdefault(name, {})[f"set{set_no}"] = dict(
                    median=statistics.median(vals), spread=spread(vals),
                    values=vals)
    print(json.dumps(dict(workload=args.workload, summary=summary,
                          all_correct=all(r["line"] and r["line"]["correct"]
                                          for r in runs))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
