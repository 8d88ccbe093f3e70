"""A ``BamArray`` (``backend="sim"``) over a float32 tier in host memory,
read through the donated ``submit_jit`` / ``wait_jit`` token pair."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import datagen
import values as V

COUNTERS = ("hits", "misses", "bytes_from_storage", "bytes_requested",
            "requests", "doorbells", "dropped", "tokens_submitted",
            "tokens_waited", "cross_op_coalesced")


def read_counters(metrics) -> dict:
    m = jax.device_get({k: getattr(metrics, k) for k in COUNTERS})
    return {k: float(v) for k, v in m.items()}


class System:
    def __init__(self, cfg: dict, seed: int):
        from repro.core import BamArray
        self.cfg = cfg
        n = int(cfg["n_elems"])
        self.n_items = n
        tier = datagen.array_tier(n, V.seed_words(seed))
        self.arr, self.st = BamArray.build(
            tier, int(cfg["block_elems"]), num_sets=int(cfg["num_sets"]),
            ways=int(cfg["ways"]), num_queues=int(cfg["num_queues"]),
            queue_depth=int(cfg["queue_depth"]), backend="sim")
        self._submit = self.arr.submit_jit(donate=True)
        self._wait = self.arr.wait_jit(donate=True)

    def kernel_shapes(self, lanes: int) -> dict:
        shape = dict(lanes=lanes, ways=int(self.cfg["ways"]), itemsize=4)
        return {"cache_probe": shape, "gather_blocks": shape}

    def request(self, items: np.ndarray):
        from repro.core import IORequest
        return IORequest.read(jnp.asarray(items.astype(np.int32)))

    def submit(self, req):
        self.st, tok = self._submit(self.st, req)
        return tok

    def wait(self, tok) -> dict:
        self.st, vals = self._wait(self.st, tok)
        return {"values": vals}

    def counters(self) -> dict:
        return read_counters(self.st.metrics)
