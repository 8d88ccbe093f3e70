"""Lines fetched from the storage tier per token (IOMetrics misses)."""


def read(w):
    return w.counters["misses"] / w.tokens if w.tokens else None
