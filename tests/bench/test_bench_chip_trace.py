"""The trace reduction and the per-layer readers on a small trace recorded
on a TPU v5 lite: one traced run of ``paper.fit`` at iters=4,
qp_iters=4, with the program's recorder saved beside it
(``data/paper_fit_small.*``)."""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
DATA = os.path.join(HERE, "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import progspans  # noqa: E402
from benchlib import spec as spec_lib  # noqa: E402
from benchlib import trace as T  # noqa: E402

#: the readers of the trace that the chip run reported for paper.fit
TRACE_READERS = ["fit.device_idle_pct", "build.device_ms_per_fit",
                 "admm.device_ms_per_fit"]
PROGRAM_SPANS = ("scan_execute", "invariant_build")


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(DATA, "paper_fit_small.recorder.json")) as fh:
        meta = json.load(fh)
    return meta


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip") / "paper_fit_small.xplane.pb"
    with gzip.open(os.path.join(DATA, "paper_fit_small.xplane.pb.gz")) as fh:
        path.write_bytes(fh.read())
    return str(path)


@pytest.fixture(scope="module")
def summary(xplane):
    return T.read_xplane(xplane)


@pytest.fixture
def recorder(chip):
    """The program's recorder holding what the chip run recorded."""
    from repro.obs import spans

    spans.clear_spans()
    for ev in chip["recorder"]:
        start = int(round(ev["ts"] * 1e3))
        spans.record(ev["name"], start, int(round(ev["dur"] * 1e3)),
                     ev.get("args"))
    yield spans
    spans.clear_spans()


class _Reading:
    def __init__(self, trace, chip):
        spec = spec_lib.Spec(ROOT, BENCH)
        self.trace = trace
        self.config = dict(spec.config("paper"), **chip["config_overrides"])
        self.device_kind = chip["device_kind"]
        self.result = {}


def _read(metric, summary, chip):
    return spec_lib.Spec(ROOT, BENCH).reader(metric)(_Reading(summary, chip))


def test_the_trace_holds_a_tpu_and_the_window(summary):
    assert summary.chips() == [0] and summary.ops[0]
    assert summary.span_count("bench.fit") >= 1
    assert summary.span_count("scan_execute") >= 2


@pytest.mark.parametrize("metric", TRACE_READERS)
def test_readers_give_what_the_chip_run_reported(metric, summary, chip):
    assert _read(metric, summary, chip) == pytest.approx(
        chip["result_metrics"][metric], rel=1e-12)


def test_compile_reader_reads_the_chip_trace(summary, chip, recorder):
    ms = _read("fit.compile_ms_per_fit", summary, chip)
    assert ms == pytest.approx(chip["compile_ms_per_fit_on_chip"],
                               rel=1e-9)
    fit_ms = [(s.end - s.start) / 1e6 for s in summary.spans
              if s.name == "bench.fit"]
    assert 0 < ms < sum(fit_ms) / len(fit_ms)


def test_compile_reader_reads_nothing_from_a_program_without_it(
        summary, chip, recorder):
    recorder.clear_spans()
    for ev in chip["recorder"]:
        if not ev["name"].startswith("compile:"):
            recorder.record(ev["name"], int(round(ev["ts"] * 1e3)),
                            int(round(ev["dur"] * 1e3)))
    assert _read("fit.compile_ms_per_fit", summary, chip) is None


def test_recorder_spans_lie_on_their_annotations(xplane, summary, chip):
    """Each program span the recorder holds starts and ends within 100 us
    of its own annotation, once the trace's profile_start_time is added;
    the offset the readers agree on from pairs is that start time."""
    from jax.profiler import ProfileData

    start = None
    for plane in ProfileData.from_file(xplane).planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
    assert start is not None
    rec = [(ev["name"], ev["ts"] * 1e3 - start,
            (ev["ts"] + ev["dur"]) * 1e3 - start)
           for ev in chip["recorder"] if ev["name"] in PROGRAM_SPANS]
    pairs = 0
    for sp in summary.spans:
        if sp.name in PROGRAM_SPANS:
            s, e = min(((s, e) for n, s, e in rec if n == sp.name),
                       key=lambda se: abs(se[0] - sp.start))
            assert abs(s - sp.start) < 100e3 and abs(e - sp.end) < 100e3
            pairs += 1
    assert pairs >= 2
    events = [(ev["name"], int(round(ev["ts"] * 1e3)),
               int(round((ev["ts"] + ev["dur"]) * 1e3)))
              for ev in chip["recorder"]]
    assert abs(progspans.offset_ns(summary, events) + start) < 100e3
