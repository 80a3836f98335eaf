"""From the profiler's trace of a window to per-layer device seconds, the
device's busy time and the ``breakdown``.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
small JSON-able record: for each TPU, the events of its ``XLA Ops`` line
(its op label, start, duration), and the host's spans.
:func:`summarize` reduces that record; ``bench/tests/data`` keeps one,
recorded on the chip, that the tests reduce.

Layers are told apart by the names of the device ops.  The trace gives
each op as its HLO instruction, and XLA names a Pallas kernel's custom call
after the jitted function that holds it: ``vmap_vmap_jit_cholesky_blocked___.4``,
``vmap_jit_interp_solve__.15``.  An op is kept as its instruction name with
its custom-call target, if any: ``custom-call.46[InvertDiagBlocksLowerTriangular]``.
The anchor factorization is every op named for a Cholesky (the Pallas
kernel, or XLA's own ``cholesky`` op or custom call); the λ stage is every
op named for ``interp_solve``, with XLA's diagonal-block inversions, which
in this program only ``interp_solve`` issues; everything else that runs on
the device in the window is the engine's XLA work.
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re

LAYERS = ("chol", "interp", "engine")
#: the benchmark's own host spans (bench/load.py), by which idle gaps are
#: told apart; each gap also names the innermost host event open in it
SPANS = ("window", "problem", "submit", "step", "host_wait")
DEVICE_LINE = "XLA Ops"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(hlo: str) -> str:
    """``name[custom-call target]`` of an op given as its HLO text."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    m = _TARGET.search(hlo)
    return f"{name}[{m.group(1)}]" if m else name


def layer_of(op: str) -> str:
    name, _, target = op.partition("[")
    if "cholesky" in name.lower() or "Cholesky" in target:
        return "chol"
    if "interp_solve" in name or target.startswith("InvertDiagBlocks"):
        return "interp"
    return "engine"


def load(trace_dir) -> dict:
    """The events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(paths[-1]))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    evs.extend([op_label(e.name), float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if e.duration_ns > 0)
    return dict(device={k: v for k, v in device.items() if v}, host=host)


def _self_times(events: list) -> list:
    """(event, self ns): an event's duration less that of the events
    nested directly inside it on the same line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for e in events:
        while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e[2], stack[-1][0][1] + stack[-1][0][2]
                                - e[1])
        stack.append([e, e[2]])
    out.extend(tuple(s) for s in stack)
    return out


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for op, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([op, a, b - a])
    return out


def _short(op: str) -> str:
    """An op's layer and its name without the instruction number."""
    name, _, target = op.partition("[")
    return f"{layer_of(op)}:{re.sub(r'[.]\d+$', '', name)}" + \
        (f"[{target}" if target else "")


def summarize(events: dict, n_chips: int = 1) -> dict:
    """Per-layer device seconds, busy and window seconds, and the
    breakdown, over the host's ``window`` span, averaged over the chips
    that ran anything.  The idle gaps are those of the first chip, each
    labelled by the benchmark span open at its middle and the innermost
    host event there."""
    spans = [h for h in events["host"] if h[0] == "window"]
    if not spans:
        raise ValueError("the trace holds no 'window' span")
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    planes = sorted(events["device"].items())
    layers = collections.Counter()
    ops = collections.Counter()
    busy, gaps_by = 0.0, collections.Counter()
    host = sorted(events["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    ours = [h for h in host if h[0] in SPANS]
    our_starts = [h[1] for h in ours]
    for i, (_, evs) in enumerate(planes):
        evs = _clip(evs, lo, hi)
        for (op, _, _), self_ns in _self_times(evs):
            layers[layer_of(op)] += self_ns
            ops[_short(op)] += self_ns
        merged = _union([(s, s + d) for _, s, d in evs])
        busy += sum(e - s for s, e in merged)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    t = (a + b) / 2
                    span = _host_at(ours, our_starts, t)
                    inner = _host_at(host, starts, t)
                    label = span if inner == span else f"{span} > {inner}"
                    gaps_by[label] += b - a
    n = max(1, len(planes))
    ns = 1e-9 / n
    return dict(
        layers={k: layers[k] * ns for k in LAYERS},
        busy_s=busy * ns, window_s=(hi - lo) * 1e-9,
        breakdown=dict(
            device_ops=[[k, v * ns] for k, v in ops.most_common(10)],
            idle_gaps=[[k, v * 1e-9] for k, v in gaps_by.most_common(10)]))


def _host_at(host: list, starts: list, t: float) -> str:
    """The innermost host span open at ``t``: of those open, the latest to
    start.  ``host`` is sorted by start and ``starts`` is its starts."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d = host[i]
        if t < s + d:
            return name
    return "(no host span)"
