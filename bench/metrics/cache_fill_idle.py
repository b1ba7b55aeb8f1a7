"""Bag cache: share of the profiled suite in which no operation runs on
the device and at least one ``bag.cache_fill`` span is open (a worker
copying its partition into the in-memory bag cache)."""


def read(r):
    from attribution import idle_under
    return idle_under(r, lambda s: s[2] == "bag.cache_fill")
