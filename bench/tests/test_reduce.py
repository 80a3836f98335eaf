"""The trace reduction: layers by name stack, self time under nesting, busy
as a union, idle gaps by the host span open, and a trace recorded on the
chip."""
import json
import pathlib

import pytest

from bench import reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
CHOL = "vmap_vmap_jit_cholesky_blocked___.4[tpu_custom_call]"
INTERP = "vmap_jit_interp_solve__.15[tpu_custom_call]"


def _events(device, host):
    return dict(device={"/device:TPU:0": device}, host=host)


def test_op_label_and_layer():
    hlo = ('%custom-call.46 = f32[5,1,32,1,128,128]{4,5,3,2,1,0} '
           'custom-call(f32[5,1,32,1,128,128] %fusion.86), '
           'custom_call_target="InvertDiagBlocksLowerTriangular"')
    assert reduce.op_label(hlo) == \
        "custom-call.46[InvertDiagBlocksLowerTriangular]"
    assert reduce.op_label("%fusion.2 = f32[3] fusion(%p)") == "fusion.2"
    assert reduce.layer_of(CHOL) == "chol"
    assert reduce.layer_of("cholesky.1") == "chol"
    assert reduce.layer_of("custom-call.3[Cholesky]") == "chol"
    assert reduce.layer_of(INTERP) == "interp"
    assert reduce.layer_of(reduce.op_label(hlo)) == "interp"
    assert reduce.layer_of("fusion.2") == "engine"
    assert reduce.layer_of("custom-call.19[LuDecompositionBlock]") == \
        "engine"


def test_layers_busy_and_idle():
    device = [[CHOL, 100.0, 50.0],
              [INTERP, 200.0, 100.0],
              ["fusion.1", 310.0, 30.0],
              ["fusion.2", 900.0, 500.0]]                   # past window
    host = [["window", 0.0, 1000.0], ["problem", 40.0, 410.0],
            ["step", 500.0, 300.0], ["np.asarray(jax.Array)", 330.0, 100.0]]
    s = reduce.summarize(_events(device, host))
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["layers"]["chol"] == pytest.approx(50e-9)
    assert s["layers"]["interp"] == pytest.approx(100e-9)
    assert s["layers"]["engine"] == pytest.approx(130e-9)   # 30 + 100 clipped
    assert s["busy_s"] == pytest.approx(280e-9)
    # gaps [0,100] [150,200] [300,310] go to the problem open at their
    # middles, [340,900] to the step; none is in the host copy
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"problem": 160e-9, "step": 560e-9})


def test_nested_ops_count_their_self_time_once():
    device = [["while.1", 0.0, 100.0],
              [INTERP, 10.0, 40.0],
              ["fusion.3", 60.0, 20.0]]
    s = reduce.summarize(_events(device, [["window", 0.0, 100.0]]))
    assert s["layers"]["interp"] == pytest.approx(40e-9)
    assert s["layers"]["engine"] == pytest.approx((40 + 20) * 1e-9)
    assert s["busy_s"] == pytest.approx(100e-9)


def test_no_window_span_raises():
    with pytest.raises(ValueError, match="window"):
        reduce.summarize(_events([], [["problem", 0.0, 1.0]]))


def test_recorded_chip_trace():
    """Two fresh paper-configuration sweeps at h=512 on one TPU v5e chip,
    traced and read by ``reduce.load``: both kernels are found by name,
    and the rest is the engine's; the host copy of the curve is where the
    device waits."""
    events = json.loads((DATA / "trace_h512.json").read_text())
    s = reduce.summarize(events)
    layers = s["layers"]
    assert layers["chol"] > 0 and layers["interp"] > 0 \
        and layers["engine"] > 0
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(layers.values()) == pytest.approx(s["busy_s"], rel=0.05)
    assert s["breakdown"]["device_ops"][0][1] > 0
    assert "np.asarray(jax.Array)" in s["breakdown"]["idle_gaps"][0][0]
