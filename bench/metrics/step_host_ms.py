"""Logic: milliseconds of ``perception.step`` spans per drive-second.  The
span covers the host-to-device copies of a batch and the dispatch of the
jitted step; the decode and forward run on the device after it closes."""


def read(r):
    t = r.span_s("perception.step")
    return 1e3 * t / r.drive_s if t > 0 else None
