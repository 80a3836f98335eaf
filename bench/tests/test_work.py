"""The least work of each layer, pinned at the paper configuration."""
import pytest

from bench import work


def test_packed_size_h4096():
    assert work.packed_size(4096, 128) == 528 * 128 * 128 == 8_650_752


def test_anchor_factorization_counts():
    w = work.anchor_factorization(h=4096, k=5, g=4, block=128)
    assert w.flops == pytest.approx(20 * 4096 ** 3 / 3)
    assert w.flops == pytest.approx(4.58e11, rel=1e-3)
    assert w.bytes == 20 * 2 * 8_650_752 * 4


def test_lambda_stage_bytes():
    w = work.lambda_stage(h=4096, k=5, q=31, degree=2, block=128)
    theta = 2 * 5 * 3 * 8_650_752 * 4
    assert theta == pytest.approx(1.04e9, rel=2e-3)
    assert w.bytes == theta + 5 * 2 * 31 * 4096 * 4 * 2
    # bf16 storage halves Θ; one refinement doubles the sweeps
    w16 = work.lambda_stage(h=4096, k=5, q=31, degree=2, block=128,
                            itemsize=2, solves=2)
    assert w16.bytes == theta + 2 * 5 * 2 * 31 * 4096 * 4 * 2


def test_least_time_takes_the_larger_bound():
    peak = work.peak_for("TPU v5 lite")
    chol = work.anchor_factorization(h=4096, k=5, g=4, block=128)
    assert chol.least_s(peak) == pytest.approx(4.58e11 / 197e12, rel=1e-3)
    lam = work.lambda_stage(h=4096, k=5, q=31, degree=2, block=128)
    assert lam.least_s(peak) == pytest.approx(lam.bytes / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak_for("TPU v9 imaginary")
