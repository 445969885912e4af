"""The sparse ADMM scan's share of its roofline: one read of Z's
nonzeros per inner QP iteration (8 bytes a nonzero, 4 a row for the
bias column; ``benchlib.workcount_sparse``, on the datasets the split
fits ran) at the published HBM bandwidth, over chip 0's device time
inside the ``bench.admm`` spans per fit."""
from benchlib import peaks, workcount, workcount_sparse


def read(r):
    done = r.result.get("split_datasets")
    if (r.trace is None or not r.trace.chips() or not done
            or not r.trace.span_count("bench.admm")):
        return None
    busy = r.trace.span_device_s("bench.admm") / r.trace.span_count(
        "bench.admm")
    if busy <= 0:
        return None
    data = r.run.state["data"]
    steps = workcount.qp_iterations(r.config)
    least = sum(peaks.least_time_s(0.0, workcount_sparse.admm_bytes(
        data[m]["vals"], data[m]["mask"], steps), r.device_kind)
        for m in done) / len(done)
    return 100.0 * least / busy
