"""Work counts and peaks, checked against counts worked by hand."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import peaks, workcount  # noqa: E402


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


def test_large_n_counts_by_hand():
    cfg = _cfg("large_n")
    # V*T = 8 problems, N = 12000, p + 1 = 785, 2 x 40 QP iterations
    assert workcount.build_flops(cfg) == 2 * 8 * 12000 ** 2 * 785
    assert workcount.build_flops(cfg) == 1.80864e12
    per_iter = 4 * 8 * 12000 * 785                 # one f32 read of Z
    assert per_iter == 301_440_000                 # 301.4 MB
    assert workcount.admm_bytes(cfg) == 80 * per_iter == 24_115_200_000
    assert workcount.admm_flops(cfg) == 80 * per_iter


def test_large_n_x4_counts_whole_problem():
    cfg = _cfg("large_n_x4")
    # V*T = 4 problems, N = 24000
    assert workcount.build_flops(cfg) == 2 * 4 * 24000 ** 2 * 785
    assert workcount.admm_bytes(cfg) == 80 * 4 * 4 * 24000 * 785


def test_large_n_x4_counts_one_chips_share():
    """Over 4 chips each holds a 6000-row panel of every K: the build's
    share is half of large_n's whole build, the Z read a quarter."""
    cfg, one = _cfg("large_n_x4"), _cfg("large_n")
    assert workcount.build_flops(cfg, 4) == 2 * 4 * 6000 * 24000 * 785
    assert workcount.build_flops(cfg, 4) == workcount.build_flops(one) / 2
    assert workcount.admm_bytes(cfg, 4) == 80 * 4 * 4 * 6000 * 785
    assert workcount.admm_bytes(cfg, 4) == workcount.admm_bytes(one) / 4
    assert workcount.admm_flops(cfg, 4) == workcount.admm_flops(cfg) / 4


def test_least_time_on_a_v5e():
    cfg = _cfg("large_n")
    kind = "TPU v5 lite"
    t = peaks.least_time_s(workcount.admm_flops(cfg),
                           workcount.admm_bytes(cfg), kind)
    assert t == pytest.approx(24_115_200_000 / 819e9)       # 29.44 ms
    assert peaks.least_time_s(workcount.build_flops(cfg), 0.0, kind) == \
        pytest.approx(1.80864e12 / 197e12)                  # 9.18 ms


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.least_time_s(1.0, 1.0, "TPU v4")
