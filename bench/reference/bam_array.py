"""Reference for an array tier: element ``i`` holds ``array_values(i)``."""
from __future__ import annotations

import numpy as np

import values as V


def expected(cfg: dict, seed: int, items: np.ndarray) -> dict:
    return {"values": V.array_values(np.asarray(items), V.seed_words(seed),
                                     np)}


def bits_differ(got: np.ndarray, want: np.ndarray) -> int:
    """Values that differ as bits (a float32 NaN never equals itself)."""
    got = np.asarray(got, np.float32).view(np.uint32)
    want = np.asarray(want, np.float32).view(np.uint32)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))


def compare(got: dict, want: dict) -> dict:
    return {"value_mismatches": bits_differ(got["values"], want["values"])}
