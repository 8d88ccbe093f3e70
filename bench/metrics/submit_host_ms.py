"""Median host time of the submit call, in ms (the benchmark's own span
around it: tracing, dispatch and any host work the call does)."""
import statistics


def read(w):
    return 1e3 * statistics.median(w.submit_s) if w.submit_s else None
