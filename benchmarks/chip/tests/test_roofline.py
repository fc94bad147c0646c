"""The peaks table and the score pass's least work."""
import pytest

import roofline


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.peaks("TPU v99")


def test_v5e_peaks_and_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_byte_per_s"] == 819e9


def test_score_pass_bytes_at_4096x256():
    M, N = 4096, 256
    flops, nbytes = roofline.score_pass_cost(M, N)
    # Qc and pc read, c written, all f32, plus the O(M + N) vectors
    vectors = 4 * (4 * M + N)
    assert nbytes == 12 * M * N + vectors
    assert flops == 3 * M * N + 3 * M
    t, bound = roofline.least_time(flops, nbytes,
                                   roofline.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert 15.3e-6 < t < 15.6e-6
