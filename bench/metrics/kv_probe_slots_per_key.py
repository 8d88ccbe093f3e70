"""Index slots read per key looked up, over the window (IOMetrics
``kv_probe_slots`` / ``kv_lookups``); nothing where no key was looked up."""


def read(w):
    n = w.counters.get("kv_lookups", 0)
    return w.counters["kv_probe_slots"] / n if n > 0 else None
