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
    # V*T = 8 problems, N = 20000, p + 1 = 257, 2 x 40 QP iterations
    assert workcount.build_flops(cfg) == 2 * 8 * 20000 ** 2 * 257
    assert workcount.build_flops(cfg) == 1.6448e12
    per_iter = 4 * 8 * 20000 * 257                 # one f32 read of Z
    assert per_iter == 164_480_000                 # 164.5 MB
    assert workcount.admm_bytes(cfg) == 80 * per_iter == 13_158_400_000
    assert workcount.admm_flops(cfg) == 80 * per_iter


def test_large_n_x4_counts_whole_problem():
    cfg = _cfg("large_n_x4")
    assert workcount.build_flops(cfg) == 2 * 8 * 40000 ** 2 * 257
    assert workcount.admm_bytes(cfg) == 80 * 4 * 8 * 40000 * 257


def test_least_time_on_a_v5e():
    cfg = _cfg("large_n")
    kind = "TPU v5 lite"
    t = peaks.least_time_s(workcount.admm_flops(cfg),
                           workcount.admm_bytes(cfg), kind)
    assert t == pytest.approx(13_158_400_000 / 819e9)       # 16.07 ms
    assert peaks.least_time_s(workcount.build_flops(cfg), 0.0, kind) == \
        pytest.approx(1.6448e12 / 197e12)                   # 8.35 ms


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.least_time_s(1.0, 1.0, "TPU v4")
