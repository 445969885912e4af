"""Device busy time inside the ``bench.admm`` span (the ADMM scan,
``Plan.run``: dual QP loop and update) per traced fit, on chip 0."""


def read(r):
    if (r.trace is None or not r.trace.chips()
            or not r.trace.span_count("bench.admm")):
        return None
    return (1e3 * r.trace.span_device_s("bench.admm")
            / r.trace.span_count("bench.admm"))
