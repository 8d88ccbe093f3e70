"""From a profiler trace to the numbers the per-layer readers take.

``load_xplane`` turns the ``.xplane.pb`` JAX's profiler writes into plain
lists: device operations ``(name, start_s, dur_s)`` of each TPU and the
benchmark's host spans ``(name, start_s, end_s)``, on one clock.
``reduce`` then computes, inside the traced window: busy time, time per
operation name, and the idle time, put to what the host was doing.

An operation that waits on a transfer from or to the host
(``is_host_transfer=true``: the ``recv-done`` and ``send-done`` of a host
callback) computes nothing: the device sits in it until the host's callback
has run.  So busy time is the union of the operations' intervals less the
union of those waits, averaged over chips.  Idle time in the waits is put
to ``host_callback``; each other gap, in which no operation ran, to the
benchmark's host span it overlaps most.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
NAME_CHARS = 160     # of an op's HLO text kept in the breakdown
HOST_TRANSFER = "is_host_transfer=true"
HOST_CALLBACK = "host_callback"     # idle while the device waits on the host


@dataclasses.dataclass
class Trace:
    ops: dict            # device name -> [(op name, start_s, dur_s)]
    spans: list          # [(span name, start_s, end_s)] on the host


def load_xplane(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    span_names = set(span_names) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return Trace(ops=ops, spans=spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy, lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi]`` that ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds, time per op name, and idle time by what
    the host was doing (``host_callback`` or a host span).

    The window is the host span named ``window``; without one, the span of
    all device operations.
    """
    win = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    all_ops = [o for v in trace.ops.values() for o in v]
    if not all_ops:
        return {}
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for _, s, _ in all_ops)
        hi = max(s + d for _, s, d in all_ops)
    busy_per_dev, idle = [], collections.Counter()
    op_time = collections.Counter()
    op_calls = collections.Counter()
    host = sorted((s, e, n) for n, s, e in trace.spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in host]
    for dev_ops in trace.ops.values():
        ran = union(((s, s + d) for _, s, d in dev_ops), lo, hi)
        waits = union(((s, s + d) for n, s, d in dev_ops
                       if HOST_TRANSFER in n), lo, hi)
        waited = sum(e - s for s, e in waits)     # waits lie inside ``ran``
        busy_per_dev.append(sum(e - s for s, e in ran) - waited)
        if waited > 0:
            idle[HOST_CALLBACK] += waited
        for name, s, d in dev_ops:
            inside = _overlap(s, s + d, lo, hi)
            if inside > 0:
                op_time[name] += inside
                op_calls[name] += 1
        for g0, g1 in gaps(ran, lo, hi):
            best, where = 0.0, "other"
            for s, e, n in host[max(0, bisect.bisect(starts, g0) - 1):]:
                if s >= g1:
                    break
                ov = _overlap(g0, g1, s, e)
                if ov > best:
                    best, where = ov, n
            idle[where] += g1 - g0
    n_dev = len(trace.ops)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_per_dev) / n_dev,
        "op_time": dict(op_time),
        "op_calls": dict(op_calls),
        "device_ops": [[n[:NAME_CHARS], t / n_dev]
                       for n, t in op_time.most_common(top)],
        "idle_gaps": [[n, t / n_dev] for n, t in idle.most_common(top)],
    }
