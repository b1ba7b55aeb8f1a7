"""Device: share of the profiled suite in which no operation ran."""


def read(r):
    from devtrace import busy_ns
    if r.hi <= r.lo or not r.trace.ops:
        return None
    return 100.0 * (1.0 - busy_ns(r.trace, r.lo, r.hi) / (r.hi - r.lo))
