"""Peaks of each chip, and the work each kernel of the request path must do.

The work is counted from a call's shapes, by what the algorithm needs and
not by what an implementation moves, so a rewrite of a kernel is measured
against the same work.  A roofline share is the least time the chip could
take (the larger of operations over peak rate and bytes over peak
bandwidth) over the time the kernel took.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind`` as JAX names it; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS_FILE.name} with its source")
    return table[device_kind]


def cache_probe_work(lanes: int, ways: int, **_) -> dict:
    """Each lane reads its key, one set's ``ways`` tags and owners, and
    writes its hit flag and slot (4-byte words); no arithmetic counts."""
    return {"flops": 0.0, "bytes": float(lanes * (4 + 2 * ways * 4 + 4 + 4))}


def gather_blocks_work(lanes: int, itemsize: int, **_) -> dict:
    """Each lane's element is read once and written once."""
    return {"flops": 0.0, "bytes": float(2 * lanes * itemsize)}


WORK = {"cache_probe": cache_probe_work, "gather_blocks": gather_blocks_work}


def roofline_share(kernel: str, shape: dict, calls: int, seconds: float,
                   peak: dict) -> float | None:
    """Percent of its roofline that ``calls`` calls of ``kernel`` reached
    in ``seconds`` of device time; ``None`` when nothing was timed."""
    if calls <= 0 or seconds <= 0:
        return None
    w = WORK[kernel](**shape)
    least = max(calls * w["flops"] / peak["bf16_flops_per_s"],
                calls * w["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
