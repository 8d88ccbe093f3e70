"""Percent of the traced window in which the device computed nothing: no
operation ran, or it waited on a host callback (``tracereduce``)."""


def read(w):
    if not w.trace or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
