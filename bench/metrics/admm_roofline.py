"""The ADMM scan's share of its roofline: the least time a chip needs
for its share of the work any implementation must do (one float32 read
of Z per inner QP iteration, ``benchlib.workcount``, over the cell's
chips), over chip 0's device time inside the ``bench.admm`` spans per
fit."""
from benchlib import peaks, workcount


def read(r):
    if (r.trace is None or not r.trace.chips()
            or not r.trace.span_count("bench.admm")):
        return None
    busy = r.trace.span_device_s("bench.admm") / r.trace.span_count(
        "bench.admm")
    if busy <= 0:
        return None
    chips = r.run.chips
    least = peaks.least_time_s(workcount.admm_flops(r.config, chips),
                               workcount.admm_bytes(r.config, chips),
                               r.device_kind)
    return 100.0 * least / busy
