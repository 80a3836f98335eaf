"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<traffic>.json``) and metrics (``bench/metrics/<name>.py``)
are found by name.  Set-up makes the designs on the device from the seed,
builds the program's engine or server and warms every shape the cell's
traffic uses; then the window runs for ``--seconds``; then the program's
state is freed and what the window produced is compared with the plain
reference (``bench/reference.py``).  ``--trace 1`` profiles the window and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_spec(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration, mix and metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return dict(cell=cell, config=cfg, mix=mix, end_to_end=e2e,
                per_layer=per_layer)


def enable_compile_cache(default: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout.  Every program is
    kept, however quickly it compiled, so that a second run compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(default)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU; this benchmark has no CPU path")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts the programs traced and compiled while ``on``."""

    def __init__(self):
        import jax
        self.on, self.traced, self.compiled = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on:
            if name.endswith("jaxpr_trace_duration"):
                self.traced += 1
            elif name.endswith("backend_compile_duration"):
                self.compiled += 1


@dataclasses.dataclass
class Measured:
    """What the metric readers read."""
    records: list
    window_s: float
    setup_s: float
    counters: dict
    work: dict                  # layer -> bench.work.Work per problem
    peak: dict
    trace: dict | None = None   # bench.reduce.summarize output


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, dev: dict | None, peak: dict | None,
             control: bool = False, cell=None, log=print) -> dict:
    """Set up, run the window, reduce, check.  Returns the result object.

    ``control`` puts the configuration's control in the program's place
    (``bench.cells``).  A ``cell`` that an earlier call set up is reseeded
    instead of built anew, so that a series of seeds pays set-up once
    (``bench/control.py``)."""
    import jax
    from bench import cells, reduce

    if cell is None:
        cell = cells.build(spec["config"], spec["mix"], seed, seconds,
                           control=control)
        cell.setup()
    else:
        cell.reseed(seed, seconds)
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_start
    trace_dir = ROOT / ".bench_trace" / spec["cell"]["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.on = True
    records, window_s = cell.window(seconds)
    counter.on = False
    summary = None
    if trace:
        jax.profiler.stop_trace()
        events = reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = reduce.summarize(events, n_chips=spec["cell"]["chips"])
    mem = memory_peak_bytes()
    counters = cell.counters()
    counters.update(traced_in_window=counter.traced,
                    compiled_in_window=counter.compiled)
    m = Measured(records=records, window_s=window_s, setup_s=setup_s,
                 counters=counters, work=cell.work(), peak=peak or {},
                 trace=summary)
    metrics = {}
    for entry in (spec["per_layer"] if trace else spec["end_to_end"]):
        value = load_reader(entry["name"])(m)
        if value is not None:
            metrics[entry["name"]] = dict(value=value, unit=entry["unit"])
    cell.release()
    gc.collect()
    t_ref = time.perf_counter()
    checks = cell.check(records)
    failed = sum(1 for r in records
                 if r.error or r.result is None
                 or not math.isfinite(r.done_s))
    log(f"[bench] {len(records)} requests, {failed} failed; window "
        f"{window_s:.3f} s; set-up {setup_s:.3f} s; reference "
        f"{time.perf_counter() - t_ref:.3f} s; programs traced/compiled in "
        f"the window: {counter.traced}/{counter.compiled}; counters "
        f"{json.dumps({k: v for k, v in counters.items() if not isinstance(v, list)})}")
    took = sorted(r.done_s - r.submit_s for r in records
                  if math.isfinite(r.done_s - r.submit_s))
    if took:    # tells one stall in the window from a slowdown of all
        log(f"[bench] request seconds from submit: median "
            f"{took[len(took) // 2]:.4f}, longest {took[-1]:.4f}")
    for r in records:
        if r.error:
            log(f"[bench] request {r.request.index} failed: {r.error}")
            break
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    device = dict(dev or {}, memory_peak_bytes=mem)
    out = dict(correct=correct, attempted=len(records), failed=failed,
               metrics=metrics, device=device)
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = summary["breakdown"]
    out["checks"] = checks
    return out


def checks_lines(out: dict) -> list:
    """Each number compared, beside its limit."""
    return [f"check {name} = {c['value']!r} (limit {c['limit']!r})"
            for name, c in out["checks"].items()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    enable_compile_cache(ROOT / ".jax_cache")
    from bench import work
    try:
        dev = device_info(int(spec["cell"]["chips"]))
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 3
    peak = work.peak_for(dev["kind"])

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, dev=dev, peak=peak, log=log)
    for line in checks_lines(out):
        log(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
