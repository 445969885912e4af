"""Device busy time inside the ``bench.build`` span (the invariant
build, ``engine.compile_problem``) per traced fit, on chip 0."""


def read(r):
    if (r.trace is None or not r.trace.chips()
            or not r.trace.span_count("bench.build")):
        return None
    return (1e3 * r.trace.span_device_s("bench.build")
            / r.trace.span_count("bench.build"))
