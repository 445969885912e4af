"""The reduction from a trace to the per-layer numbers, on hand-made
intervals whose answers are worked out by hand."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import trace as T  # noqa: E402


def test_union_subtract_clip():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert T.subtract([(0, 4)], []) == [(0, 4)]
    assert T.clip([(0, 4), (6, 10)], 2, 8) == [(2, 4), (6, 8)]
    assert T.total([(0, 3), (5, 8)]) == 6


def _summary():
    # window 0..100 ns; chip 0: ops at 10-30 (fusion), 25-40 (overlaps),
    # 50-60 (all-gather, 55-60 overlapped by a fusion), 90-120 (clipped)
    ops = {0: [T.Op("fusion.1", 10, 30), T.Op("fusion.2", 25, 40),
               T.Op("all-gather.3", 50, 60), T.Op("fusion.1", 55, 60),
               T.Op("fusion.1", 90, 120)],
           1: [T.Op("fusion.1", 0, 50)]}
    spans = [T.Span("bench.window", 0, 100),
             T.Span("bench.build", 0, 45), T.Span("bench.admm", 45, 100),
             T.Span("backend_compile_and_load", 62, 88)]
    return T.TraceSummary(window=(0, 100), ops=ops, spans=spans)


def test_busy_idle_and_spans():
    s = _summary()
    # chip 0 busy: 10-40, 50-60, 90-100 = 50 ns; chip 1: 0-50 = 50 ns
    assert T.total(s.busy(0)) == 50 and T.total(s.busy(1)) == 50
    assert s.busy_s() == pytest.approx(50e-9)
    assert s.window_s() == pytest.approx(100e-9)
    assert s.idle_share() == pytest.approx(0.5)
    # device time inside each span on chip 0: build 10-40 = 30 ns,
    # admm 50-60 + 90-100 = 20 ns
    assert s.span_device_s("bench.build") == pytest.approx(30e-9)
    assert s.span_device_s("bench.admm") == pytest.approx(20e-9)
    assert s.span_count("bench.admm") == 1


def test_collectives_only_where_nothing_else_runs():
    s = _summary()
    assert s.has_collectives(0) and not s.has_collectives(1)
    # all-gather 50-60, of which 55-60 overlapped by a fusion: 5 ns
    assert s.collective_exposed_s(0) == pytest.approx(5e-9)


def test_collectives_inside_a_loop_are_not_hidden_by_it():
    # as a TPU trace lists them: the loop spans its body's operations,
    # and the fusion that consumes the gather names it as an operand
    ops = {0: [T.Op("%while.7 = (s32[], f32[4,2,40000]) while(...)", 0, 100),
               T.Op("%all-gather.3 = f32[4,2,40000] all-gather(...)", 20, 30),
               T.Op("%fusion.1 = f32[4,2,10000] fusion(f32[4,2,40000] "
                    "%all-gather.3)", 25, 40),
               T.Op("%all-gather.3 = f32[4,2,40000] all-gather(...)", 60, 70)]}
    s = T.TraceSummary(window=(0, 100), ops=ops,
                       spans=[T.Span("bench.fit", 0, 100)])
    # 20-25 and 60-70 run alone in the loop's body: 15 ns
    assert s.collective_exposed_s(0, within="bench.fit") == \
        pytest.approx(15e-9)


def test_breakdown_names_ops_and_gaps():
    s = _summary()
    top = s.top_ops(2)
    assert top[0][0] == "fusion.1"
    # fusion.1: 10-30 (20) + 55-60 (5) + 90-100 (10, clipped) = 35 ns
    assert top[0][1] == pytest.approx(35e-9)
    gaps = s.idle_gaps(3)
    # idle: 0-10 (build), 40-50 (admm: midpoint 45 is admm's start),
    # 60-90 (the compile inside admm is the innermost span)
    assert gaps[0] == ["backend_compile_and_load", pytest.approx(30e-9)]
    assert {g[0] for g in gaps[1:]} == {"bench.build", "bench.admm"}
    assert sum(g[1] for g in s.idle_gaps(10)) == pytest.approx(50e-9)


def test_no_device_planes_reads_nothing():
    s = T.TraceSummary(window=(0, 100), ops={}, spans=[])
    assert s.idle_share() is None and s.busy_s() == 0.0


def test_idle_share_and_collectives_inside_named_spans():
    s = _summary()
    # inside bench.build (0-45): chip 0 busy 10-40 = 30 ns, chip 1 busy
    # 0-45 = 45 ns; mean busy 37.5 of 45 ns
    assert s.span_idle_share("bench.build") == pytest.approx(1 - 37.5 / 45)
    assert s.span_idle_share("no.such.span") is None
    # the exposed all-gather (55-60 overlapped) lies inside bench.admm
    assert s.collective_exposed_s(0, within="bench.admm") == \
        pytest.approx(5e-9)
    assert s.collective_exposed_s(0, within="bench.build") == 0.0
