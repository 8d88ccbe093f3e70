"""Block-store backends for the BaM storage tier.

Two interchangeable backends sit below the BaM queues/cache:

* ``SimStorage`` — the data lives on the host (a numpy array or ``np.memmap``)
  and is fetched with ``jax.pure_callback`` from inside jitted code.  This is
  the *functional* backend used by the application examples and tests: real
  data, real gathers, host round-trip standing in for the NVMe DMA.

* ``HBMStorage`` — the data is an in-graph ``jnp`` array (shardable across the
  mesh).  This is the *dry-run/roofline* backend: the compiler sees the gather
  traffic of on-demand fetches, so ``cost_analysis()`` and the HLO collective
  schedule account for the BaM data path.  On a real deployment this tier is
  host/remote memory reached by DMA; the software above is identical.

Both expose ``fetch_blocks(keys) -> (n, block_elems)`` with sentinel keys
(< 0) returning zeros, and a write path for the BaM write support.

``SimStorage``'s two host callbacks are the request path's host half.  The
fused request path calls each only when it has work: the fetch when a lane
missed, the write-back when a dirty line was evicted (``BamArray``'s
``_fetch_gated`` / ``_write_back_gated``).  Each runs its body inside a
profiler span (``bam.storage.fetch``, ``bam.storage.write_back``, with the
``rows`` shipped and the ``live`` rows among them) and adds to plain host
counters (:meth:`SimStorage.counters`).
With the profiler off a span costs one inactive ``TraceAnnotation``.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback as _io_callback
from jax.profiler import TraceAnnotation


class BlockStore(Protocol):
    num_blocks: int
    block_elems: int
    dtype: jnp.dtype

    def fetch_blocks(self, keys: jax.Array) -> jax.Array: ...
    def write_blocks(self, keys: jax.Array, lines: jax.Array) -> None: ...


COUNTERS = ("fetch_calls", "fetch_rows", "fetch_live_rows",
            "write_calls", "write_rows", "write_live_rows")


@dataclasses.dataclass
class SimStorage:
    """Host-resident block store fetched via pure_callback (the 'SSD').

    Counts what crosses the host boundary: callback calls, the rows each
    ships, and the live rows (key >= 0) among them.  The callbacks may run
    on several host threads, so the counts are taken under a lock.
    """

    data: np.ndarray  # (num_blocks, block_elems)
    _counts: dict = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COUNTERS, 0),
        repr=False, compare=False)
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def __post_init__(self):
        assert self.data.ndim == 2, "block store must be (num_blocks, block_elems)"

    def counters(self) -> dict:
        """Host-boundary counters since construction (``COUNTERS``)."""
        with self._lock:
            return dict(self._counts)

    def _count(self, op: str, rows: int, live: int) -> None:
        with self._lock:
            c = self._counts
            c[op + "_calls"] += 1
            c[op + "_rows"] += rows
            c[op + "_live_rows"] += live

    @property
    def num_blocks(self) -> int:
        return self.data.shape[0]

    @property
    def block_elems(self) -> int:
        return self.data.shape[1]

    @property
    def dtype(self):
        return jnp.dtype(self.data.dtype)

    def _host_fetch(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys)
        dead = keys < 0
        rows = int(keys.shape[0])
        live = rows - int(np.count_nonzero(dead))
        self._count("fetch", rows, live)
        with TraceAnnotation("bam.storage.fetch", rows=rows, live=live):
            safe = np.clip(keys, 0, self.num_blocks - 1)
            out = self.data[safe]
            out[dead] = 0
        return out

    def fetch_blocks(self, keys: jax.Array) -> jax.Array:
        out_shape = jax.ShapeDtypeStruct((keys.shape[0], self.block_elems), self.dtype)
        return jax.pure_callback(self._host_fetch, out_shape, keys, vmap_method="sequential")

    def _host_write(self, keys: np.ndarray, lines: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys)
        mask = keys >= 0
        rows = int(keys.shape[0])
        live = int(np.count_nonzero(mask))
        self._count("write", rows, live)
        with TraceAnnotation("bam.storage.write_back", rows=rows, live=live):
            self.data[keys[mask]] = np.asarray(lines)[mask]
        return np.zeros((), np.int32)

    def write_blocks(self, keys: jax.Array, lines: jax.Array) -> jax.Array:
        # io_callback: ordered side effect (a write IOP).
        return _io_callback(
            self._host_write, jax.ShapeDtypeStruct((), jnp.int32), keys, lines,
            ordered=True,
        )

    @staticmethod
    def from_array(arr: np.ndarray, block_elems: int) -> "SimStorage":
        flat = np.ascontiguousarray(arr).reshape(-1)
        pad = (-flat.shape[0]) % block_elems
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
        return SimStorage(flat.reshape(-1, block_elems))


@jax.tree_util.register_pytree_node_class
class HBMStorage:
    """In-graph block store (a shardable cold tier the compiler can see)."""

    def __init__(self, data: jax.Array):
        self.data = data  # (num_blocks, block_elems)

    @property
    def num_blocks(self) -> int:
        return self.data.shape[0]

    @property
    def block_elems(self) -> int:
        return self.data.shape[1]

    @property
    def dtype(self):
        return self.data.dtype

    def fetch_blocks(self, keys: jax.Array) -> jax.Array:
        safe = jnp.clip(keys, 0, self.num_blocks - 1)
        out = jnp.take(self.data, safe, axis=0)
        return jnp.where((keys >= 0)[:, None], out, 0)

    def write_blocks(self, keys: jax.Array, lines: jax.Array) -> "HBMStorage":
        safe = jnp.clip(keys, 0, self.num_blocks - 1)
        cur = jnp.take(self.data, safe, axis=0)
        lines = jnp.where((keys >= 0)[:, None], lines, cur)
        return HBMStorage(self.data.at[safe].set(lines))

    # pytree plumbing -----------------------------------------------------
    def tree_flatten(self):
        return (self.data,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    @staticmethod
    def from_array(arr: jax.Array, block_elems: int) -> "HBMStorage":
        flat = arr.reshape(-1)
        pad = (-flat.shape[0]) % block_elems
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return HBMStorage(flat.reshape(-1, block_elems))
