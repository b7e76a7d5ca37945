"""The kernel's work follows from its shapes; peaks come from the table."""
import pytest

import work


def test_fused_gemm_work_from_shapes():
    ops, nbytes = work.fused_gemm_work(P=24, B=32, K=256, N=32)
    # eight bit planes of a (32 x 256) x (256 x 32) multiply-add per design
    assert ops == 2 * 8 * 24 * 32 * 256 * 32
    # two (P, K, N) f32 noise fields, (P, B, N) f32 out, x and w once
    assert nbytes == 4 * (2 * 24 * 256 * 32 + 24 * 32 * 32
                          + 32 * 256 + 256 * 32)
    ops2, bytes2 = work.fused_gemm_work(P=48, B=32, K=256, N=32)
    assert ops2 == 2 * ops and bytes2 > nbytes


def test_roofline_share_names_the_bound():
    ops, nbytes = work.fused_gemm_work(P=1000, B=32, K=256, N=32)
    t_bytes = nbytes / 819e9
    share, bound = work.roofline_share(ops, nbytes, 2 * t_bytes,
                                       "TPU v5 lite")
    assert bound == "bytes"
    assert share == pytest.approx(0.5)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.roofline_share(1.0, 1.0, 1.0, "cpu")
