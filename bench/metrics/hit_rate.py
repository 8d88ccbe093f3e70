"""Percent of cache-line probes that hit, over the window (IOMetrics)."""


def read(w):
    c = w.counters
    probes = c["hits"] + c["misses"]
    return 100.0 * c["hits"] / probes if probes > 0 else None
