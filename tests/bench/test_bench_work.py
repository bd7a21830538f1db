"""Bytes and operations from shapes (bench/work.py)."""
import math

import pytest

import bench_tiny  # noqa: F401
from bench import work


def test_one_gemm():
    w = work.bitplane_gemm(b=16, k=2048, n=6144, weight_bits=4)
    assert w.ops == 2 * 16 * 2048 * 6144
    # 4 bit-planes of 2048/8 bytes per column, int8 x, int32 out
    assert w.bytes == 4 * 256 * 6144 + 16 * 2048 + 4 * 16 * 6144


def test_one_placed_gemm_counts_logical_bytes():
    """The placed product streams its window's padding too, but only the
    logical planes are work: a smaller pad reads as a higher share."""
    placed = work.placed_bitplane_gemm(16, 6144, 2048, 4)
    assert placed == work.bitplane_gemm(16, 6144, 2048, 4)
    assert work.bitplane_gemm(1, 9, 8, 4).bytes == 4 * 2 * 8 + 9 + 32


def test_least_seconds_takes_the_binding_bound():
    w = work.Work(ops=393e12, bytes=819e9 * 2)
    assert work.least_seconds(w, 393e12, 819e9) == pytest.approx(2.0)
    assert work.least_seconds(work.Work(393e12 * 3, 1.0), 393e12,
                              819e9) == pytest.approx(3.0)


def _cfg():
    return {"hidden_size": 2048, "intermediate_size": 6144,
            "num_hidden_layers": 28, "num_attention_heads": 16,
            "num_key_value_heads": 8, "head_dim": 128,
            "vocab_size": 151936,
            "pud": {"weight_bits": 4, "unembed": True,
                    "packable": ["mixer.wi", "mixer.wg", "mixer.wo"]}}


def test_decode_step_sums_every_packed_projection():
    cfg = _cfg()
    step = work.pud_decode_step(cfg, 16)
    want = (work.bitplane_gemm(16, 2048, 6144, 4).scale(2 * 28)
            + work.bitplane_gemm(16, 6144, 2048, 4).scale(28)
            + work.bitplane_gemm(16, 2048, 151936, 4))
    assert step.ops == pytest.approx(want.ops)
    assert step.bytes == pytest.approx(want.bytes)
    assert work.logical_pud_bytes(cfg) == \
        4 * (256 * 6144 * 56 + 768 * 2048 * 28 + 256 * 151936)


def test_forward_flops_count_live_context():
    cfg = _cfg()
    proj = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144
    one = 2 * (28 * (proj + 2 * 2048 * 1) + 2048 * 151936)
    assert work.forward_flops(cfg, 1) == one
    assert work.forward_flops(cfg, 101) - one == 2 * 28 * 2 * 2048 * 100


def test_calibration_iteration_bytes():
    """State in and out at 4 bytes a column, operand bits at one bit each,
    no noise: the f32 layout the program uses today is not the work."""
    w = work.calib_iteration(g=16, s=256, m=5, c=65536)
    assert w.ops == 0
    assert w.bytes == 16 * 65536 * (16 + 256 * 5 / 8)
    assert math.isclose(w.bytes / 1e6, 184.5, rel_tol=1e-3)
