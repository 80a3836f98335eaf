"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 10]
    python3 bench/control.py --workload <cell> --seeds ... --program
    python3 bench/control.py --workload <cell> --seeds 1 --program --set h=8192

The control is what ``correct`` has to refuse: the step below the precision
the configuration states.  A configuration names it under ``control``:

* ``{"kind": "reference", "precision": "high"}`` — the plain reference,
  computed at the lower precision, called per request in the program's
  place;
* ``{"kind": "program", "precision": "bf16_store"}`` — the program itself
  with its own lower-precision path switched on.

Either way the control runs the cell's own window and check
(``bench/run.py``'s ``run_cell``), so its ``correct`` is the benchmark's.
``--program`` instead reads the program as configured, for the lower
reading.  ``--set KEY=VALUE`` changes a number of the configuration, to
see whether another size runs and what memory it takes.  Every seed runs
in this one process on one warm engine or server, so set-up is paid once.
Each seed prints one line; the last line is a JSON summary.  This is not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness  # bench/run.py puts the repo and src/ on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program", action="store_true",
                    help="read the program as configured, not the control")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=N",
                    help="change a number of the configuration")
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        spec["config"][key] = int(value)
    harness.enable_compile_cache(harness.ROOT / ".jax_cache")
    dev = harness.device_info(int(spec["cell"]["chips"]))
    ctrl = spec["config"]["control"]
    mode = "program" if args.program else f"control/{ctrl['kind']}"
    readings = {}
    cell = None
    for seed in args.seeds:
        t0 = time.perf_counter()
        try:
            if cell is None:
                from bench import cells
                cell = cells.build(spec["config"], spec["mix"], seed,
                                   args.seconds, control=not args.program)
                cell.setup()
            out = harness.run_cell(spec, seed, args.seconds, False,
                                   t_start=t0, dev=dev, peak=None,
                                   cell=cell, log=lambda msg: None)
        except Exception as e:            # noqa: BLE001 — a size that does
            print(f"[{mode}] {args.workload} seed={seed} FAILED "   # not run
                  f"{type(e).__name__}: {str(e)[:2000]}", flush=True)
            readings[seed] = None
            continue
        checks = {k: c["value"] for k, c in out["checks"].items()}
        readings[seed] = dict(correct=out["correct"], **checks)
        print(f"[{mode}] {args.workload} seed={seed} correct={out['correct']}"
              f" {json.dumps(checks)} requests={out['attempted']} failed="
              f"{out['failed']} memory_peak_bytes="
              f"{out['device']['memory_peak_bytes']} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    gaps = [r["curve_gap"] for r in readings.values() if r]
    print(json.dumps(dict(workload=args.workload, mode=mode, set=args.set,
                          readings=readings,
                          max_curve_gap=max(gaps, default=None),
                          min_curve_gap=min(gaps, default=None))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
