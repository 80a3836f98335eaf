"""From the profiler's trace of a window to device seconds per program
scope, host seconds per program span, and idle gaps labelled by the
program's spans.

The program names its stages (``src/repro/core/tracing.py`` holds the
table): a device scope (``jax.named_scope``, ``cv.*``) lands in the
``op_name`` metadata of every HLO op traced under it, and a host span
(``jax.profiler.TraceAnnotation``, ``cv.*`` and ``cache.*``) is an event
of the host's trace on the device clock.

:func:`load` reads the newest ``.trace.json.gz`` under a directory (the
profiler writes it beside the ``.xplane.pb``) into a JSON-able record: for
each TPU, the ops of its ``XLA Ops`` line as ``[label, start_ns,
duration_ns, name_stack]``, and the host's events as ``[name, start_ns,
duration_ns]``.  An op's name stack is its ``op_name`` metadata, which
that file gives as the op's ``tf_op`` argument.  The ``.xplane.pb`` does
not: on a TPU v5e its ``XLA Ops`` events carry only the HLO text, the
device offset and the duration (checked on the chip).
:func:`as_reduce` turns the record into what ``bench.reduce.summarize``
reads, so one trace gives both readings.

:func:`summarize` gives, over the host's ``window`` span, device seconds
per scope (each op's self time goes to the innermost ``cv.*`` component
of its name stack, :data:`NO_SCOPE` when it has none) with each scope's
largest ops by the op-name layers of ``bench.reduce``, host seconds per
program span, and the idle gaps of the first chip, each labelled by the
benchmark span, the innermost program span and the innermost host event
open at its middle: ``problem > cv.fetch > D2H Dispatch``.  A trace in
which no op carries a scope (a program without scopes, or one served from
a compile cache keyed without metadata) has ``scopes`` None.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import json
import math
import pathlib
import re

from bench import reduce

#: the argument of a device op's trace event that holds its ``op_name``
STACK_ARG = "tf_op"
NO_SCOPE = "(no scope)"
#: the program's host spans, by prefix
PROGRAM_SPANS = ("cv.", "cache.")
_SCOPE = re.compile(r"cv\.[a-z_]+")


def scope_of(stack: str) -> str | None:
    """The innermost ``cv.*`` component of a name stack, or None."""
    found = _SCOPE.findall(stack or "")
    return found[-1] if found else None


def load(trace_dir) -> dict:
    """The events of the newest trace under ``trace_dir``, with each
    device op's name stack."""
    paths = sorted(pathlib.Path(trace_dir).rglob("*.trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    with gzip.open(paths[-1]) as f:
        trace = json.load(f)["traceEvents"]
    names = {(e["pid"], e.get("tid")): e["args"]["name"] for e in trace
             if e.get("ph") == "M"
             and e.get("name") in ("process_name", "thread_name")}
    device, host = {}, []
    for e in trace:
        if e.get("ph") != "X":
            continue
        proc = names.get((e["pid"], None), "")
        start, dur = 1e3 * e["ts"], 1e3 * e.get("dur", 0.0)
        if proc.startswith("/device:TPU:") \
                and names.get((e["pid"], e["tid"])) == reduce.DEVICE_LINE:
            args = e.get("args", {})
            device.setdefault(proc, []).append(
                [reduce.op_label(args.get("long_name", e["name"])), start,
                 dur, args.get(STACK_ARG, "")])
        elif proc.startswith("/host:CPU") and dur > 0:
            host.append([e["name"], start, dur])
    return dict(device=device, host=host)


def as_reduce(events: dict) -> dict:
    """The record as ``bench.reduce.summarize`` reads it."""
    return dict(device={k: [e[:3] for e in v]
                        for k, v in events["device"].items()},
                host=events["host"])


def _open(events: list, starts: list, t: float, since: float,
          innermost: bool = False) -> list:
    """(name, start) of ``events`` (sorted by start) open at ``t`` that
    started at or after ``since``, outermost first; only the innermost
    with ``innermost``."""
    out = []
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d = events[i]
        if s < since:
            break
        if t < s + d:
            out.append((name, s))
            if innermost:
                break
    return out[::-1]


def summarize(events: dict) -> dict:
    """Device seconds per scope, host seconds per program span and the
    labelled idle gaps, over the ``window`` span (module doc)."""
    spans = [h for h in events["host"] if h[0] == "window"]
    if not spans:
        raise ValueError("the trace holds no 'window' span")
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    scopes = collections.Counter()
    scope_ops = collections.defaultdict(collections.Counter)
    busy = 0.0
    planes = sorted(events["device"].items())
    host = sorted(events["host"], key=lambda h: h[1])
    groups = {
        "bench": [h for h in host if h[0] in reduce.SPANS],
        "program": [h for h in host if h[0].startswith(PROGRAM_SPANS)],
        "any": host}
    starts = {k: [h[1] for h in v] for k, v in groups.items()}
    gaps = collections.Counter()
    for i, (_, evs) in enumerate(planes):
        clipped = []
        for op, s, d, stack in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                clipped.append([op, a, b - a, stack])
        for (op, _, _, stack), self_ns in reduce._self_times(clipped):
            scope = scope_of(stack) or NO_SCOPE
            scopes[scope] += self_ns
            scope_ops[scope][reduce._short(op)] += self_ns
        merged = reduce._union([(s, s + d) for _, s, d, _ in clipped])
        busy += sum(e - s for s, e in merged)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps[_gap_label(groups, starts, (a + b) / 2)] += b - a
    host_s = collections.Counter()
    for name, s, d in groups["program"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            host_s[name] += (b - a) * 1e-9
    ns = 1e-9 / max(1, len(planes))
    scoped = any(k != NO_SCOPE for k in scopes)
    return dict(
        scopes={k: v * ns for k, v in scopes.items()} if scoped else None,
        scope_ops={k: [[op, v * ns] for op, v in c.most_common(6)]
                   for k, c in scope_ops.items()},
        busy_s=busy * ns, window_s=(hi - lo) * 1e-9,
        host_s=dict(host_s),
        idle_gaps=[[k, v * 1e-9] for k, v in gaps.most_common(10)])


def _gap_label(groups: dict, starts: dict, t: float) -> str:
    """The benchmark span open at ``t``, the program spans open inside
    it, outermost first, and the innermost host event."""
    bench = _open(groups["bench"], starts["bench"], t, -math.inf, True)
    since = bench[0][1] if bench else -math.inf
    parts = [n for n, _ in bench + _open(groups["program"],
                                         starts["program"], t, since)]
    inner = _open(groups["any"], starts["any"], t, since, True)
    if inner and inner[0][0] not in parts:
        parts.append(inner[0][0])
    return " > ".join(parts) or "(no host span)"


def per_problem_ms(summary: dict, n_problems: int) -> dict | None:
    """Device milliseconds per problem of every scope, or None."""
    if summary["scopes"] is None or n_problems <= 0:
        return None
    return {k: 1e3 * v / n_problems
            for k, v in sorted(summary["scopes"].items())}
