"""Bytes fetched from the storage tier per byte the application requested,
over the window (IOMetrics)."""


def read(w):
    c = w.counters
    if c["bytes_requested"] <= 0:
        return None
    return c["bytes_from_storage"] / c["bytes_requested"]
