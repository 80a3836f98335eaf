"""Trace one window of a cell and read it by the program's own stage names.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--set KEY=N] [--record <file.json>]

Builds and warms the cell as ``bench/run.py`` does, traces the window with
the same profiler options, and reads the trace twice: by op names
(``bench/reduce.py``, the layers the per-layer metrics report) and by the
program's scopes and spans (``bench/spans.py``).  Prints the device
milliseconds per problem of every ``cv.*`` scope and of the ops under no
scope, the host milliseconds per problem of every program span, the idle
gaps by the spans open in them, and last a JSON line with all of it.
JAX's persistent compile cache is keyed with the ops' metadata here, so a
program compiled before its scopes existed is never served.
``--record`` writes the trace's event record (``bench.spans.load``) to a
file, for the tests in ``bench/tests``.  This is not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time

import run as harness  # bench/run.py puts the repo and src/ on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=N",
                    help="change a number of the configuration")
    ap.add_argument("--record", default=None,
                    help="write the trace's event record to this file")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    spec = harness.load_spec(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        spec["config"][key] = int(value)
    harness.enable_compile_cache(harness.ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    dev = harness.device_info(int(spec["cell"]["chips"]))
    from bench import cells, reduce, spans

    cell = cells.build(spec["config"], spec["mix"], args.seed, args.seconds)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    trace_dir = harness.ROOT / ".bench_trace" / f"scopes-{args.workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    records, window_s = cell.window(args.seconds)
    jax.profiler.stop_trace()
    events = spans.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.record:
        with open(args.record, "w") as f:
            json.dump(events, f)
    n = len(records)
    layers = reduce.summarize(spans.as_reduce(events))
    read = spans.summarize(events)
    scopes = spans.per_problem_ms(read, n)
    if scopes is None:
        print("no program scopes in the trace", file=sys.stderr, flush=True)
    out = dict(
        workload=args.workload, seed=args.seed, set=args.set, device=dev,
        problems=n, failed=sum(1 for r in records if r.error),
        window_s=window_s, cv_s=window_s / n if n else None,
        setup_s=setup_s,
        layers_ms={k: 1e3 * v / n for k, v in layers["layers"].items()},
        busy_ms=1e3 * read["busy_s"] / n,
        idle_pct=100.0 * (1.0 - read["busy_s"] / read["window_s"]),
        scopes_ms=scopes,
        scope_ops_ms={k: [[op, 1e3 * v / n] for op, v in ops]
                      for k, ops in read["scope_ops"].items()},
        host_ms={k: 1e3 * v / n for k, v in sorted(read["host_s"].items())},
        idle_gaps=read["idle_gaps"],
        device_ops=layers["breakdown"]["device_ops"])
    for key in ("layers_ms", "scopes_ms", "host_ms"):
        print(f"[scopes] {key}: " + ", ".join(
            f"{k} {v:.3f}" for k, v in (out[key] or {}).items()),
            file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
