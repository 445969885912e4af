"""The program's recorded spans placed on a trace's clock, and the reader
of compile time per whole fit, on hand-made spans whose answers are
worked out by hand."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import progspans  # noqa: E402
from benchlib import spec as spec_lib  # noqa: E402
from benchlib import trace as T  # noqa: E402

#: recorder time = trace time + 5 s (the trace counts from its start)
SHIFT = 5_000_000_000


def _trace():
    # two whole fits (0-1000 us, 2000-3000 us), each with a scan_execute
    # span of its own length; the window is 0-4000 us
    us = 1000
    spans = [T.Span("bench.window", 0, 4000 * us),
             T.Span("bench.fit", 0, 1000 * us),
             T.Span("scan_execute", 100 * us, 700 * us),
             T.Span("bench.fit", 2000 * us, 3000 * us),
             T.Span("scan_execute", 2100 * us, 2500 * us)]
    return T.TraceSummary(window=(0, 4000 * us), ops={}, spans=spans)


def _recorded(extra=()):
    us = 1000
    return [("scan_execute", SHIFT + 100 * us, SHIFT + 700 * us),
            ("scan_execute", SHIFT + 2100 * us, SHIFT + 2500 * us),
            # an older span outside the trace pairs with nothing
            ("scan_execute", SHIFT - 9000 * us, SHIFT - 8000 * us),
            *extra]


def test_offset_is_the_one_most_pairs_agree_on():
    assert progspans.offset_ns(_trace(), _recorded()) == -SHIFT


def test_no_offset_without_agreeing_pairs():
    trace = _trace()
    assert progspans.offset_ns(trace, []) is None
    # durations that match no trace span: nothing agrees
    assert progspans.offset_ns(trace, [("scan_execute", 0, 10)]) is None
    # one pair of two trace spans is too few to agree on
    one = [("scan_execute", SHIFT + 100_000, SHIFT + 700_000)]
    assert progspans.offset_ns(trace, one) is None


@pytest.fixture
def recorder():
    """The program's recorder, emptied before and after the test."""
    from repro.obs import spans

    spans.clear_spans()
    yield spans
    spans.clear_spans()


def _record(spans, events):
    for name, s, e in events:
        spans.record(name, s, e - s)


class _Reading:
    def __init__(self, trace):
        self.trace = trace


def test_compile_ms_per_whole_fit(recorder):
    us = 1000
    compiles = [  # recorder clock; per fit: trace, lower, compile
        ("compile:jit(scan)", SHIFT + 150 * us, SHIFT + 160 * us),
        ("compile:jit(scan)", SHIFT + 160 * us, SHIFT + 300 * us),
        ("compile:jit(scan)", SHIFT + 300 * us, SHIFT + 600 * us),
        # a nested trace inside the first: counted once (the union)
        ("compile:jit(multiply)", SHIFT + 151 * us, SHIFT + 152 * us),
        ("compile:jit(scan)", SHIFT + 2150 * us, SHIFT + 2450 * us),
        # between the fits (a split fit's compile): not in a whole fit
        ("compile:jit(scan)", SHIFT + 1500 * us, SHIFT + 1800 * us),
        # running over the end of the second fit: clipped to it
        ("compile:jit(other)", SHIFT + 2900 * us, SHIFT + 3100 * us)]
    _record(recorder, _recorded(compiles))
    read = spec_lib.Spec(ROOT, BENCH).reader("fit.compile_ms_per_fit")
    # fit 1: 150-600 us = 450 us; fit 2: 300 + 100 us; over 2 fits
    assert read(_Reading(_trace())) == pytest.approx((450 + 400) / 2 / 1e3)


def test_compile_reader_reads_nothing_without_compile_spans(recorder):
    read = spec_lib.Spec(ROOT, BENCH).reader("fit.compile_ms_per_fit")
    _record(recorder, _recorded())
    assert read(_Reading(_trace())) is None       # a program without them
    assert read(_Reading(None)) is None
    assert progspans.on_trace(_trace(), "compile:") is None
