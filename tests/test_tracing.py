"""Stage names in the compiled programs and in the profiler's host trace.

The device scopes of :mod:`repro.core.tracing` must reach the ``op_name``
metadata of the compiled programs (they are what a device trace reads to
attribute each op to a stage), and the host spans must nest per problem
as the module's table says.
"""
from __future__ import annotations

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import CVEngine, FactorCache, tracing
from repro.testing.strategies import regression_folds

#: an instruction of the kinds that do the device work, with its op_name
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*? "
                    r"(fusion|dot|custom-call|copy)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"cv\.[a-z_]+")


def _compiled_texts(path: str, precision: str, h: int = 256) -> list:
    """Compiled HLO of every program one sweep runs: the split, then the
    fused sweep, or the cached path's prepare, state and replay
    programs."""
    folds = regression_folds(h=h, n=4 * h, k=4, dtype=jnp.float32)
    lams = jnp.logspace(-3, 0, 9, dtype=jnp.float32)
    eng = CVEngine("picholesky", backend="reference", precision=precision)
    stats = (folds.hess, folds.grad, folds.fold_hess, folds.fold_grad)
    h_tr, g_tr = jax.eval_shape(eng._split, *stats)
    texts = [eng._split.lower(*stats).compile().as_text()]
    args = (h_tr, g_tr, folds.x_folds, folds.y_folds, lams)
    if path == "fused":
        texts.append(eng._sweep_fn(None).lower(*args).compile().as_text())
    else:
        prepare = eng._prepare_fn()
        texts.append(prepare.lower(*args).compile().as_text())
        state_fn = eng._state_fn(None, True)
        state_args = (jnp.arange(h_tr.shape[0]), h_tr, g_tr,
                      jax.eval_shape(prepare, *args))
        texts.append(state_fn.lower(*state_args).compile().as_text())
        state, _ = jax.eval_shape(state_fn, *state_args)
        texts.append(eng._replay_fn(None).lower(state, *args)
                     .compile().as_text())
    return texts


@pytest.mark.parametrize("path,precision", [
    ("fused", "fp32"), ("fused", "bf16_refined"),
    ("cached", "fp32"), ("cached", "bf16_refined")])
def test_every_traced_op_carries_a_stage_scope(path, precision):
    texts = _compiled_texts(path, precision)
    found = set(_SCOPE.findall("\n".join(texts)))
    want = set(tracing.SCOPES) - (
        set() if precision == "bf16_refined" else {tracing.REFINE})
    assert want <= found, f"scopes missing: {sorted(want - found)}"
    unscoped = []
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            op = _OP_NAME.search(line) if m else None
            # an op the program traced has a name stack, "jit(...)/...";
            # copies and wrappers XLA makes itself carry no op_name, or
            # only a parameter's name, and no scope can reach them
            if op and op.group(1).startswith("jit(") \
                    and not _SCOPE.search(op.group(1)):
                unscoped.append((m.group(1), op.group(1)))
    assert not unscoped, unscoped[:5]


def _host_events(trace_dir: pathlib.Path) -> list:
    """(name, start, end, stats) of the program's host spans."""
    from jax.profiler import ProfileData
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("cv.", "cache.")):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_host_spans_nest_per_problem(tmp_path):
    folds = regression_folds(h=32, n=256, k=4)
    lams = jnp.logspace(-3, 0, 7)
    eng = CVEngine("picholesky", backend="reference", cache=FactorCache())
    eng.run(folds, lams)                         # compile outside the trace
    eng.cache = FactorCache()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(folds, lams)
        eng.run(folds, lams)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    runs = [e for e in events if e[0] == "cv.run"]
    assert [r[3]["status"] for r in runs] == ["miss", "hit"]
    assert all((r[3]["h"], r[3]["k"], r[3]["q"]) == (32, 4, 7) for r in runs)
    for _, lo, hi, _ in runs:
        inner = [e for e in events if lo <= e[1] and e[2] <= hi
                 and e[0] != "cv.run"]
        names = [e[0] for e in inner
                 if e[0] in ("cache.fingerprint", "cache.d2h",
                             "cache.lookup", "cv.fetch")]
        collapsed = [n for i, n in enumerate(names)
                     if i == 0 or names[i - 1] != n]
        assert collapsed == ["cache.fingerprint", "cache.d2h",
                             "cache.lookup", "cv.fetch"]
        fp = next(e for e in inner if e[0] == "cache.fingerprint")
        d2h = next(e for e in inner if e[0] == "cache.d2h")
        assert fp[1] <= d2h[1] and d2h[2] <= fp[2]
        assert fp[3]["bytes"] == 4 * 32 * 32 * 8
    results = [e[3]["result"] for e in events if e[0] == "cache.lookup"]
    assert results[0] == "miss" and results[-1] == "hit"


def test_fingerprint_bytes_count_the_hashed_hessians():
    k, h = 4, 32
    folds = regression_folds(h=h, n=256, k=k)
    lams = jnp.logspace(-3, 0, 7)
    cache = FactorCache()
    eng = CVEngine("picholesky", backend="reference", cache=cache)
    per_lookup = k * h * h * folds.fold_hess.dtype.itemsize
    for n in (1, 2):
        res = eng.run(folds, lams)
        assert cache.fingerprint_bytes == n * per_lookup
        assert cache.stats["fingerprint_bytes"] == n * per_lookup
        assert res.extras["engine"]["cache"]["fingerprint_bytes"] == \
            n * per_lookup
    assert FactorCache().stats["fingerprint_bytes"] == 0
