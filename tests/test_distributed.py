"""Distributed substrate: compression, sharding resolution, roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed import compression, hlo_cost, sharding
from repro.distributed.context import MeshCtx
from repro.models.params import Spec


# ------------------------------------------------------------- compression


@given(seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_int8_quantization_bounded_error(seed):
    x = jnp.asarray(np.random.RandomState(seed).randn(64) * 10)
    q, s = compression.quantize_int8(x)
    err = jnp.max(jnp.abs(compression.dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_steps():
    """EF property: accumulated transported signal ≈ accumulated true signal
    (residual stays bounded, does not drift)."""
    rs = np.random.RandomState(0)
    grads = [jnp.asarray(rs.randn(32) * (1 + i % 3)) for i in range(50)]
    residual = jnp.zeros(32)
    sent = jnp.zeros(32)
    true = jnp.zeros(32)
    for g in grads:
        deq, residual = compression.ef_compress_tree(g, residual)
        sent = sent + deq
        true = true + g
    # total drift equals the final residual — bounded by one quant step
    np.testing.assert_allclose(np.asarray(sent + residual), np.asarray(true),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(residual))) < 1.0


def test_ef_tree_structure_preserved():
    tree = {"a": jnp.ones((4, 4)), "b": {"c": jnp.zeros(3)}}
    res = jax.tree.map(jnp.zeros_like, tree)
    deq, new_res = compression.ef_compress_tree(tree, res)
    assert jax.tree.structure(deq) == jax.tree.structure(tree)
    assert jax.tree.structure(new_res) == jax.tree.structure(tree)


# ------------------------------------------------------------- sharding


def _ctx():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return MeshCtx.from_mesh(mesh, fsdp=True)


def test_spec_pspec_resolution():
    ctx = _ctx()
    ps = sharding.spec_pspec(Spec((8, 16), ("fsdp", "model")), ctx)
    assert ps == jax.sharding.PartitionSpec("data", "model")
    ps2 = sharding.spec_pspec(Spec((8,), (None,)), ctx)
    assert ps2 == jax.sharding.PartitionSpec(None)


def test_spec_pspec_divisibility_check():
    mesh = jax.make_mesh((1,), ("model",))
    # fake a 16-wide axis via ctx override
    class FakeCtx:
        fsdp_axis = None
        def axis_size(self, name):
            return 16
    with pytest.raises(ValueError):
        sharding.spec_pspec(Spec((10,), ("model",)), FakeCtx())


def test_meshctx_no_mesh_noop():
    ctx = MeshCtx(None)
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, "data", None) is x
    assert ctx.tp_size == 1 and ctx.dp_size == 1


# ------------------------------------------------------------- hlo parser


def test_hlo_cost_counts_loop_trips():
    n = 64
    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((n, n), jnp.float32),
                         jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    cost = hlo_cost.analyze_hlo(c.as_text())
    expect = 7 * 2 * n ** 3
    assert abs(cost.flops - expect) / expect < 0.05
    assert cost.unknown_trip_loops == 0


def test_hlo_cost_nested_loops_multiply():
    n = 32
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((n, n), jnp.float32),
                         jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    cost = hlo_cost.analyze_hlo(c.as_text())
    expect = 15 * 2 * n ** 3
    assert abs(cost.flops - expect) / expect < 0.10


def test_collective_formulas():
    text = '''
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups=[2,8]<=[16], to_apply=%add
}
'''
    cost = hlo_cost.analyze_hlo(text)
    size = 16 * 16 * 4
    assert abs(cost.wire["all-reduce"] - 2 * 7 / 8 * size) < 1e-6


# ------------------------------------------------------- λ-chunk heuristic


def test_auto_lam_chunk_floor_is_one():
    # budget smaller than ONE λ's packed row still streams: floor at 1
    from repro.core import packing
    h, block = 128, 128
    per_lam = packing.packed_nbytes(h, block, jnp.float32)
    assert sharding.auto_lam_chunk(h, block, jnp.float32, per_lam - 1) == 1
    assert sharding.auto_lam_chunk(h, block, jnp.float32, 0) == 1


def test_auto_lam_chunk_bf16_doubles_fp32():
    # storage dtype halves the per-λ bytes → chunk doubles at the same
    # budget (the memory half of the mixed-precision contract)
    h, block, budget = 128, 128, 1 << 20
    c32 = sharding.auto_lam_chunk(h, block, jnp.float32, budget)
    c16 = sharding.auto_lam_chunk(h, block, jnp.bfloat16, budget)
    assert c16 == 2 * c32


def test_auto_lam_chunk_h_smaller_than_block():
    # h < block: one padded tile — the chunk follows the PADDED packed
    # bytes, so it can only shrink (never overflow the budget) vs h=block
    from repro.core import packing
    budget = 1 << 20
    small = sharding.auto_lam_chunk(24, 128, jnp.float32, budget)
    exact = sharding.auto_lam_chunk(128, 128, jnp.float32, budget)
    assert small == budget // packing.packed_nbytes(24, 128, jnp.float32)
    assert small == exact   # both pack one 128-tile
    # and a proportionate block tracks the smaller true working set
    tight = sharding.auto_lam_chunk(24, 32, jnp.float32, budget)
    assert tight >= small


# ------------------------------------------------------------ HW presets


def test_hw_presets_cover_platforms():
    from repro.distributed import roofline as rl
    # keyed by device_kind; the v5e entry carries the published peaks
    assert set(rl.HW_PRESETS) == {"cpu", "TPU v5 lite"}
    for hw in rl.HW_PRESETS.values():
        assert hw.peak_flops > 0 and hw.hbm_bw > 0 and hw.link_bw > 0
    tpu = rl.HW_PRESETS["TPU v5 lite"]
    assert (tpu.name, tpu.peak_flops, tpu.hbm_bw) == \
        ("tpu-v5e", 197e12, 819e9)


def _fake_device_kind(monkeypatch, kind):
    class _Dev:
        device_kind = kind
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "NVIDIA H100"])
def test_detect_hw_unknown_device_kind_raises(monkeypatch, kind):
    from repro.distributed import roofline as rl
    monkeypatch.delenv("REPRO_HW", raising=False)
    _fake_device_kind(monkeypatch, kind)
    with pytest.raises(ValueError, match="no peak rates for device kind"):
        rl.detect_hw()
    # an explicit preset still serves a device the table does not know
    monkeypatch.setenv("REPRO_HW", "tpu-v5e")
    assert rl.detect_hw() == rl.HW_PRESETS["TPU v5 lite"]


def test_detect_hw_platform_and_env_override(monkeypatch):
    from repro.distributed import roofline as rl
    monkeypatch.delenv("REPRO_HW", raising=False)
    assert rl.detect_hw() == rl.HW_PRESETS[jax.devices()[0].device_kind]
    _fake_device_kind(monkeypatch, "TPU v5 lite")
    assert rl.detect_hw().name == "tpu-v5e"
    monkeypatch.setenv("REPRO_HW", "cpu")
    assert rl.detect_hw().name == "cpu"
    monkeypatch.setenv("REPRO_HW", "TPU v5 lite")     # by key, too
    assert rl.detect_hw().name == "tpu-v5e"
    monkeypatch.setenv("REPRO_HW_PEAK_FLOPS", "1e12")
    hw = rl.detect_hw()
    assert hw.peak_flops == 1e12 and hw.name.endswith("+env")
    assert hw.hbm_bw == rl.HW_PRESETS["TPU v5 lite"].hbm_bw  # others untouched
    monkeypatch.setenv("REPRO_HW", "hal9000")
    with pytest.raises(ValueError, match="no such preset"):
        rl.detect_hw()


def test_roofline_uses_hw_rates():
    from repro.distributed import roofline as rl
    hw = rl.HW(name="toy", peak_flops=100.0, hbm_bw=10.0, link_bw=1.0)
    roof = rl.Roofline(flops=200.0, hbm_bytes=50.0, wire_bytes=3.0,
                       by_collective={}, chips=1, hw=hw)
    assert roof.compute_s == 2.0 and roof.memory_s == 5.0
    assert roof.collective_s == 3.0
    assert roof.step_s == 5.0 and roof.bottleneck == "memory"
    s = roof.summary()
    assert s["step_s"] == 5.0 and s["hw"] == "toy"


def test_roofline_cache_aware_memory_term():
    """Cache-modelled HW: a cache-resident working set streams at
    cache_bw; a spilled one blends toward hbm_bw by the spilled fraction
    (monotone in working-set size — the property that lets the tuner rank
    λ-chunk/block candidates whose total bytes are flat)."""
    from repro.distributed import roofline as rl
    hw = rl.HW(name="toy", peak_flops=1e9, hbm_bw=10.0, link_bw=1.0,
               cache_bw=100.0, cache_bytes=1000.0)
    mk = lambda ws: rl.Roofline(flops=0.0, hbm_bytes=500.0, wire_bytes=0.0,
                                by_collective={}, chips=1, hw=hw,
                                temp_bytes=ws)
    assert mk(800.0).effective_bw == 100.0          # fits: cache speed
    half = mk(2000.0)                               # 50% resident
    assert half.effective_bw == pytest.approx(0.5 * 100.0 + 0.5 * 10.0)
    assert mk(10_000.0).effective_bw < half.effective_bw   # monotone
    assert mk(None).effective_bw == 10.0            # unknown ws: flat model
    # cache-less HW ignores temp_bytes entirely
    flat = rl.HW(name="flat", peak_flops=1e9, hbm_bw=10.0, link_bw=1.0)
    roof = rl.Roofline(flops=0.0, hbm_bytes=500.0, wire_bytes=0.0,
                       by_collective={}, chips=1, hw=flat, temp_bytes=5.0)
    assert roof.effective_bw == 10.0
    assert mk(2000.0).summary()["effective_bw"] == half.effective_bw


def test_hlo_cost_slice_through_bitcast_not_charged_full():
    """A fusion that consumes its parameter only through view ops
    (bitcast/reshape) feeding a slice is charged the slice bytes, not the
    whole array — the per-tile packed-factor read pattern.  A fusion that
    reads the parameter directly still pays the full operand."""
    text = '''
%fused_computation.1 (param_0.1: f32[1000,16]) -> f32[1,16] {
  %param_0.1 = f32[1000,16]{1,0} parameter(0)
  %bitcast.1 = f32[1000,1,16]{2,1,0} bitcast(f32[1000,16]{1,0} %param_0.1)
  %slice.1 = f32[1,1,16]{2,1,0} slice(f32[1000,1,16]{2,1,0} %bitcast.1), slice={[3:4], [0:1], [0:16]}
  ROOT %bitcast.2 = f32[1,16]{1,0} bitcast(f32[1,1,16]{2,1,0} %slice.1)
}

%fused_computation.2 (param_0.2: f32[1000,16]) -> f32[1000,16] {
  %param_0.2 = f32[1000,16]{1,0} parameter(0)
  ROOT %add.1 = f32[1000,16]{1,0} add(f32[1000,16]{1,0} %param_0.2, f32[1000,16]{1,0} %param_0.2)
}

ENTRY %main (p: f32[1000,16]) -> f32[1000,16] {
  %p = f32[1000,16]{1,0} parameter(0)
  %tile = f32[1,16]{1,0} fusion(f32[1000,16]{1,0} %p), kind=kLoop, calls=%fused_computation.1
  ROOT %dense = f32[1000,16]{1,0} fusion(f32[1000,16]{1,0} %p), kind=kLoop, calls=%fused_computation.2
}
'''
    cost = hlo_cost.analyze_hlo(text)
    full = 1000 * 16 * 4
    tile = 1 * 1 * 16 * 4
    # sliced fusion: result + touched slice; dense fusion: result + operand
    assert cost.hbm_bytes == pytest.approx((1 * 16 * 4 + tile) + 2 * full)
