"""Reference for a YCSB key-value cell: every requested key is one of the
records, and its value row is ``ycsb.record_values(key, column)``."""
from __future__ import annotations

import numpy as np

import values as V
import ycsb
from reference.bam_array import bits_differ


def expected(cfg: dict, seed: int, items: np.ndarray) -> dict:
    keys = ycsb.request_keys(items, cfg)
    col = np.arange(int(cfg["value_elems"]), dtype=np.uint32)
    return {"values": ycsb.record_values(keys[:, None], col[None, :],
                                         V.seed_words(seed), np),
            "found": np.ones(keys.shape, bool)}


def compare(got: dict, want: dict) -> dict:
    found = np.asarray(got["found"])
    return {"value_mismatches": bits_differ(got["values"], want["values"]),
            "found_mismatches": (int(np.count_nonzero(found != want["found"]))
                                 if found.shape == want["found"].shape
                                 else int(want["found"].size))}
