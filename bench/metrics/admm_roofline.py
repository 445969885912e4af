"""The ADMM scan's share of its roofline: the least time the chip needs
for the work any implementation must do (one float32 read of Z per inner
QP iteration, ``benchlib.workcount``), over the device time measured
inside the ``bench.admm`` spans per fit."""
from benchlib import peaks, workcount


def read(r):
    if (r.trace is None or not r.trace.chips()
            or not r.trace.span_count("bench.admm")):
        return None
    busy = r.trace.span_device_s("bench.admm") / r.trace.span_count(
        "bench.admm")
    if busy <= 0:
        return None
    least = peaks.least_time_s(workcount.admm_flops(r.config),
                               workcount.admm_bytes(r.config), r.device_kind)
    return 100.0 * least / busy
