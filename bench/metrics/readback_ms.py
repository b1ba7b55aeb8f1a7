"""Logic: milliseconds of ``perception.readback`` spans per drive-second.
The span covers the wait for a step's logits to reach the host: the
device queue ahead of the step, the step itself and the device-to-host
copy."""


def read(r):
    t = r.span_s("perception.readback")
    return 1e3 * t / r.drive_s if t > 0 else None
