"""A ``BamKVStore`` over YCSB's records: the device index over a slot-ordered
float32 tier in host memory (``backend="sim"``), read through the store's
donated ``submit_jit`` / ``wait_jit`` token pair.

Item ids are YCSB's uniform variates; ``request`` turns them into keys
under the configuration's ``request_distribution`` (``ycsb``) on the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import datagen
import values as V
import ycsb
from systems.bam_array import read_counters

KV_COUNTERS = ("kv_lookups", "kv_probe_slots")


def kv_tier(table, value_elems: int, words) -> np.ndarray:
    """The slot-ordered tier: row ``s`` holds the record of the key in
    ``table[s]`` (zeros where the slot is empty).  Made on the device from
    the index, ``datagen.CHUNK_BYTES`` at a time, and copied to the host."""
    cap = table.shape[0]
    out = np.empty((cap, value_elems), np.float32)
    rows = max(1, min(cap, datagen.CHUNK_BYTES // out[:1].nbytes))
    col = jnp.arange(value_elems, dtype=jnp.uint32)[None, :]

    @jax.jit
    def make(k):
        k = k[:, None]
        return jnp.where(k >= 0, ycsb.record_values(k, col, words, jnp),
                         jnp.float32(0))

    nxt = make(table[:rows])
    for r0 in range(0, cap, rows):
        cur = nxt
        if r0 + rows < cap:                        # overlap the next chunk
            nxt = make(table[r0 + rows:r0 + 2 * rows])
        out[r0:r0 + rows] = np.asarray(cur)
    return out


class System:
    def __init__(self, cfg: dict, seed: int):
        from repro.core import BamArray, BamKVStore
        ycsb.check_workload(cfg)
        self.cfg = cfg
        self.n_items = ycsb.N_ITEMS
        self.record_count = int(cfg["record_count"])
        ve = self.value_elems = int(cfg["value_elems"])
        cap = int(cfg["index_capacity"])
        # the index first: a program that cannot place the keys stops here
        keys = ycsb.record_key(np.arange(self.record_count))
        table, _, probes = BamKVStore.place(keys, capacity=cap)
        table_dev = jnp.asarray(table)
        tier = kv_tier(table_dev, ve, V.seed_words(seed))
        arr, st = BamArray.build(
            tier, int(cfg["block_elems"]), num_sets=int(cfg["num_sets"]),
            ways=int(cfg["ways"]), num_queues=int(cfg["num_queues"]),
            queue_depth=int(cfg["queue_depth"]), backend="sim")
        self.arr = BamKVStore(array=arr, capacity=cap, value_elems=ve,
                              probes=probes)
        self.st = self.arr.state(st, table_dev)
        self._submit = self.arr.submit_jit(donate=True)
        self._wait = self.arr.wait_jit(donate=True)

    def kernel_shapes(self, lanes: int) -> dict:
        shape = dict(lanes=lanes * self.value_elems,
                     ways=int(self.cfg["ways"]), itemsize=4)
        return {"cache_probe": shape, "gather_blocks": shape}

    def request(self, items: np.ndarray):
        return jnp.asarray(ycsb.request_keys(items, self.cfg))

    def submit(self, req):
        self.st, tok = self._submit(self.st, req)
        return tok

    def wait(self, tok) -> dict:
        self.st, vals = self._wait(self.st, tok)
        return {"values": vals, "found": tok.found}

    def counters(self) -> dict:
        m = self.st.bam.metrics
        kv = jax.device_get({k: getattr(m, k) for k in KV_COUNTERS})
        return {**read_counters(m), **{k: float(v) for k, v in kv.items()}}
