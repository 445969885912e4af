"""The invariant build's share of its roofline: a chip's share of the
2 V T N^2 (p+1) operations (every entry of K, which the Gershgorin bound
needs; over the cell's chips) over the published bfloat16 peak, over
chip 0's device time inside the ``bench.build`` spans per fit."""
from benchlib import peaks, workcount


def read(r):
    if (r.trace is None or not r.trace.chips()
            or not r.trace.span_count("bench.build")):
        return None
    busy = r.trace.span_device_s("bench.build") / r.trace.span_count(
        "bench.build")
    if busy <= 0:
        return None
    least = peaks.least_time_s(workcount.build_flops(r.config, r.run.chips),
                               0.0, r.device_kind)
    return 100.0 * least / busy
