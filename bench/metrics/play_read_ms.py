"""Partition replay: milliseconds of ``play.read`` spans (bag read, chunk
decode and time-order framing of each micro-batch) per drive-second."""


def read(r):
    t = r.span_s("play.read")
    return 1e3 * t / r.drive_s if t > 0 else None
