"""The sparse invariant build's share of its roofline: the nonzero
products every entry of K needs, 2 sum_{v,t} sum_f c_f^2 operations
(``benchlib.workcount_sparse``, counted on the datasets the split fits
built), at the published bfloat16 peak, over chip 0's device time inside
the ``bench.build`` spans per fit."""
from benchlib import peaks, workcount_sparse


def read(r):
    done = r.result.get("split_datasets")
    if (r.trace is None or not r.trace.chips() or not done
            or not r.trace.span_count("bench.build")):
        return None
    busy = r.trace.span_device_s("bench.build") / r.trace.span_count(
        "bench.build")
    if busy <= 0:
        return None
    data = r.run.state["data"]
    flops = {m: workcount_sparse.build_flops(
        data[m]["rows"], data[m]["cols"], data[m]["vals"], data[m]["mask"],
        r.config["p"]) for m in set(done)}
    least = sum(peaks.least_time_s(flops[m], 0.0, r.device_kind)
                for m in done) / len(done)
    return 100.0 * least / busy
