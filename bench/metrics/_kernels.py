"""Shared by the kernel roofline readers: which trace operations are a
kernel's calls.

The Pallas kernels carry no stable ``name=``: the trace names each call
after the jitted function that holds it (``%counted.2``, ``%counted.3``).
So a call is known by its signature in the HLO text the trace prints: a
``tpu_custom_call`` with the kernel's operand and result shapes.  The work
is counted from the cell's shapes (``roofline.py``), not from the trace.
"""
from __future__ import annotations

import re

import roofline

SIGNATURES = {
    # (s32[m,1], s32[m,1]) <- (s32[m,1] keys, s32[sets,ways] tags, owner)
    "cache_probe": re.compile(
        r"= \(s32\[(\d+),1\]\{[^}]*\}, s32\[\1,1\]\{[^}]*\}\) custom-call\("
        r"s32\[\1,1\]\{[^}]*\} %[^,]+, s32\[\d+,\d+\]\{[^}]*\} %[^,]+, "
        r"s32\[\d+,\d+\]\{[^}]*\}"),
    # f32[n,line] <- (s32[n] slots, f32[lines,line] cache data)
    "gather_blocks": re.compile(
        r"= [a-z0-9]+\[(\d+),(\d+)\]\{[^}]*\} custom-call\("
        r"s32\[\1\]\{[^}]*\} %[^,]+, [a-z0-9]+\[\d+,\2\]\{[^}]*\}"),
}
TARGET = 'custom_call_target="tpu_custom_call"'


def calls(w, kernel: str) -> tuple[int, float]:
    """``(calls, device seconds)`` of ``kernel`` in the traced window."""
    if w.trace is None:
        return 0, 0.0
    n, t = 0, 0.0
    sig = SIGNATURES[kernel]
    for name, secs in w.trace["op_time"].items():
        if TARGET in name and sig.search(name):
            n += w.trace["op_calls"][name]
            t += secs
    return n, t


def share(w, kernel: str):
    n, t = calls(w, kernel)
    shape = w.kernel_shapes.get(kernel)
    if shape is None:
        return None
    return roofline.roofline_share(kernel, shape, n, t, w.peak)
