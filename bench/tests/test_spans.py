"""The trace read by the program's own stage names: device seconds per
scope, idle gaps by the spans open in them, the reading of a trace with no
scopes, the op-name reading left as it was, and traces recorded on the
chip."""
import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import reduce, run, spans

DATA = pathlib.Path(__file__).resolve().parent / "data"
CHOL = "cholesky_panel.3[tpu_custom_call]"
FWD = "interp_solve_fwd.7[tpu_custom_call]"
ANCHOR = ("jit(statef)/cv.anchor_chol/vmap(jit(cholesky_blocked))/"
          "cholesky_panel")
LAM = "jit(replay)/cv.lam_stage/vmap(jit(interp_solve))/interp_solve_fwd"
SCORE = "jit(replay)/cv.lam_stage/vmap(cv.score)/dot_general"


def _record(device, host):
    return dict(device={"/device:TPU:0": device}, host=host)


def test_scope_of_takes_the_innermost_stage():
    assert spans.scope_of(ANCHOR) == "cv.anchor_chol"
    assert spans.scope_of(SCORE) == "cv.score"
    assert spans.scope_of("jit(f)/while/body/add") is None
    assert spans.scope_of("") is None


def test_scope_seconds_and_gap_labels():
    device = [[CHOL, 100.0, 50.0, ANCHOR],
              ["while.2", 200.0, 100.0, LAM],
              [FWD, 210.0, 60.0, LAM],
              ["fusion.4", 280.0, 20.0, SCORE],
              ["copy.9", 310.0, 30.0, ""]]
    host = [["window", 0.0, 1000.0], ["problem", 40.0, 900.0],
            ["cv.run", 45.0, 890.0], ["cache.fingerprint", 50.0, 45.0],
            ["cache.d2h", 55.0, 30.0], ["D2H Dispatch", 60.0, 20.0],
            ["cv.fetch", 400.0, 500.0], ["np.asarray(jax.Array)", 410.0,
                                         100.0]]
    s = spans.summarize(_record(device, host))
    assert s["scopes"] == pytest.approx({
        "cv.anchor_chol": 50e-9,
        "cv.lam_stage": (100 - 60 - 20 + 60) * 1e-9,
        "cv.score": 20e-9, spans.NO_SCOPE: 30e-9})
    assert s["busy_s"] == pytest.approx(180e-9)
    assert sum(s["scopes"].values()) == pytest.approx(s["busy_s"])
    assert s["scope_ops"]["cv.anchor_chol"] == [
        ["chol:cholesky_panel[tpu_custom_call]", pytest.approx(50e-9)]]
    assert [op for op, _ in s["scope_ops"]["cv.lam_stage"]] == [
        "interp:interp_solve_fwd[tpu_custom_call]", "engine:while"]
    assert s["host_s"]["cache.fingerprint"] == pytest.approx(45e-9)
    assert s["host_s"]["cache.d2h"] == pytest.approx(30e-9)
    gaps = dict(s["idle_gaps"])
    # [0,100]: mid 50, in the fingerprint before the copy began; [150,200]
    # mid 175 in cv.run alone; [300,310] and [340,1000]: mid 305 in cv.run,
    # mid 670 in the curve's copy with no host event left open
    assert gaps == pytest.approx({
        "problem > cv.run > cache.fingerprint": 100e-9,
        "problem > cv.run": (50 + 10) * 1e-9,
        "problem > cv.run > cv.fetch": 660e-9}, abs=1e-12)
    assert spans.per_problem_ms(s, 2)["cv.score"] == pytest.approx(1e-5)


def test_gap_in_the_hessian_copy_names_the_copy_and_the_runtime():
    device = [["fusion.1", 0.0, 10.0, SCORE], ["fusion.2", 90.0, 10.0, SCORE]]
    host = [["window", 0.0, 100.0], ["problem", 0.0, 100.0],
            ["cache.fingerprint", 5.0, 90.0], ["cache.d2h", 20.0, 60.0],
            ["D2H Dispatch", 30.0, 40.0]]
    s = spans.summarize(_record(device, host))
    assert s["idle_gaps"] == [[
        "problem > cache.fingerprint > cache.d2h > D2H Dispatch",
        pytest.approx(80e-9)]]


def test_readers_give_nothing_without_scopes():
    device = [[CHOL, 100.0, 50.0, ""], ["fusion.1", 200.0, 10.0, "jit(f)"]]
    s = spans.summarize(_record(device, [["window", 0.0, 300.0]]))
    assert s["scopes"] is None
    assert spans.per_problem_ms(s, 3) is None
    assert s["busy_s"] == pytest.approx(60e-9)


def test_as_reduce_leaves_the_op_name_reading_as_it_was():
    device = [[CHOL, 100.0, 50.0, ANCHOR], [FWD, 200.0, 60.0, LAM],
              ["fusion.4", 280.0, 20.0, SCORE]]
    record = _record(device, [["window", 0.0, 1000.0]])
    by_ops = reduce.summarize(spans.as_reduce(record))
    assert by_ops["layers"] == pytest.approx(
        {"chol": 50e-9, "interp": 60e-9, "engine": 20e-9})


def test_no_window_span_raises():
    with pytest.raises(ValueError, match="window"):
        spans.summarize(_record([], [["problem", 0.0, 1.0]]))


def test_recorded_trace_reads_as_at_the_parent():
    """The op-name reading of the trace recorded before the program had
    scopes: the layers, busy and window seconds, and the ten largest ops,
    as the accepted benchmark read them."""
    s = reduce.summarize(json.loads((DATA / "trace_h512.json").read_text()))
    assert s["layers"] == pytest.approx(
        {"chol": 0.010907101, "interp": 0.027767633, "engine": 0.003456395},
        rel=1e-12)
    assert s["busy_s"] == pytest.approx(0.042131129, rel=1e-12)
    assert s["window_s"] == pytest.approx(0.047039488, rel=1e-12)
    want = [
        ["interp:custom-call[InvertDiagBlocksLowerTriangular]", 0.020339414],
        ["chol:vmap_vmap_jit_cholesky_blocked___[tpu_custom_call]",
         0.010907101],
        ["interp:vmap_jit_interp_solve__[tpu_custom_call]", 0.00742239],
        ["engine:reshape", 0.001530097],
        ["engine:copy", 0.000879156],
        ["engine:fusion", 0.000580854],
        ["engine:bitcast_dynamic-update-slice_fusion", 0.000155711],
        ["engine:reduce", 0.000101573],
        ["engine:add_select_fusion", 3.1623e-05],
        ["engine:vmap_vmap_jit_pack_tril___[tpu_custom_call]", 2.779e-05]]
    got = s["breakdown"]["device_ops"]
    assert [k for k, _ in got] == [k for k, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want],
                                                rel=1e-12)


def test_recorded_scoped_chip_trace():
    """Two fresh paper-configuration sweeps at h=512 on one TPU v5e chip,
    with the program's scopes: the scopes account for the busy time, the
    anchor factorization scope is the Cholesky layer the op names give,
    and the kernels keep the names the op-name reading keys on."""
    events = json.loads((DATA / "trace_h512_scoped.json").read_text())
    s = spans.summarize(events)
    assert s["scopes"] is not None
    assert sum(s["scopes"].values()) == pytest.approx(s["busy_s"], rel=0.01)
    assert s["scopes"].get(spans.NO_SCOPE, 0.0) < 0.01 * s["busy_s"]
    layers = reduce.summarize(spans.as_reduce(events))["layers"]
    assert s["scopes"]["cv.anchor_chol"] == pytest.approx(layers["chol"],
                                                          rel=0.01)
    assert s["scopes"]["cv.lam_stage"] >= layers["interp"]
    assert {"cv.theta_fit", "cv.score", "cv.split"} <= set(s["scopes"])
    ops = {reduce.layer_of(op) for plane in events["device"].values()
           for op, *_ in plane if "[tpu_custom_call]" in op
           and ("cholesky" in op or "interp_solve" in op)}
    assert ops == {"chol", "interp"}


def test_fingerprint_mb_reads_the_hashed_bytes(small):
    """Every problem of the targets cell fingerprints the design's k fold
    Hessians, f32 (h, h) each."""
    from bench import cells
    spec = small("paper_h4096_targets")
    cfg = spec["config"]
    cell = cells.build(cfg, spec["mix"], 2**31 + 77, 1.0)
    cell.setup()
    records, _ = cell.window(1.0)
    assert len(records) >= 2 and not any(r.error for r in records)
    read = run.load_reader("fingerprint_mb")
    assert read(SimpleNamespace(records=records)) == pytest.approx(
        int(cfg["k"]) * int(cfg["h"]) ** 2 * 4 / 1e6)


def test_fingerprint_mb_gives_nothing_without_the_counter():
    read = run.load_reader("fingerprint_mb")
    without = SimpleNamespace(extras=dict(engine=dict(
        cache=dict(status="hit", hits=3))))
    records = [SimpleNamespace(result=without)] * 3
    assert read(SimpleNamespace(records=records)) is None
    cold = SimpleNamespace(extras=dict(engine=dict(cache=None)))
    assert read(SimpleNamespace(records=[SimpleNamespace(result=cold)] * 3)) \
        is None
