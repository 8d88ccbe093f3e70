"""Makes a cell's storage tier on the device, chunk by chunk, and copies it
into one host array (the tier lives in host memory).

Each chunk is one jitted call of the formula in ``values``; a chunk is 256
MiB, so the device never holds more than about twice that for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import values as V

CHUNK_BYTES = 256 << 20


def _fill(out: np.ndarray, n_rows: int, make) -> np.ndarray:
    """``out[r0:r1] = make(r0)`` chunk by chunk; ``make`` takes the first
    row as a uint32 scalar and returns ``rows_per_chunk`` rows."""
    row_bytes = out[:1].nbytes
    rows = max(1, min(n_rows, CHUNK_BYTES // row_bytes))
    fn = jax.jit(make, static_argnums=1)
    nxt = fn(jnp.uint32(0), rows)
    for r0 in range(0, n_rows, rows):
        cur = nxt
        if r0 + rows < n_rows:                     # overlap the next chunk
            nxt = fn(jnp.uint32(r0 + rows), rows)
        r1 = min(n_rows, r0 + rows)
        out[r0:r1] = np.asarray(cur)[: r1 - r0]
    return out


def array_tier(n_elems: int, words) -> np.ndarray:
    """The float32 array tier, ``n_elems`` long."""
    out = np.empty((n_elems,), np.float32)

    def make(r0, rows):
        idx = r0 + jnp.arange(rows, dtype=jnp.uint32)
        return V.array_values(idx, words, jnp)

    return _fill(out, n_elems, make)
