"""Kernels: the sensor-decode Pallas kernel's share of its roofline.  The
least time the chip could take for the bytes every call must move (payload
read, float32 features written) at peak HBM bandwidth, over the device time
of the kernel's events in the trace.  The kernel is memory-bound: it does
one multiply-add per byte."""

#: the kernel's op in the device trace: the custom call is named after the
#: jitted wrapper around the ``pallas_call`` (``_sensor_decode.1``)
KERNEL = "_sensor_decode"


def read(r):
    from flops.sensor_decode import decode_bytes
    t = r.device_s(r.trace.ops, lambda n: n.split(".")[0] == KERNEL)
    if t <= 0 or not r.step_calls:
        return None
    need = sum(decode_bytes(rows, nb) for rows, nb in r.step_calls)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
