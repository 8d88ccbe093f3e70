"""Paged block gather Pallas-TPU kernel — the BamArray data path.

``out[i] = data[slots[i]]`` with the slot vector scalar-prefetched so the
``BlockSpec`` index map *is* the page table walk: each grid step's input DMA
is redirected at a dynamic physical line while the previous line streams
out.  This is the literal TPU translation of BaM's "SSD DMA engine delivers
the requested block into the assigned buffer": HBM→VMEM DMA indexed by the
request wavefront.

TPU tiling: a block's second-minor dimension must be a multiple of the
sublane tile (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit), so a single
line cannot be its own block.  Each grid step instead DMAs the aligned
``tile``-row group holding its line, selects the line's row with a static
select chain, and writes it into row ``i % tile`` of a ``tile``-row output
block that stays resident for ``tile`` consecutive steps.

Negative slots (invalid / bypassed requests) are clamped in the index map
and zero-filled in the kernel body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import round_up


def _sublane_tile(num_lines: int, dtype) -> int:
    """Rows per block: the dtype's sublane tile, or every line if fewer."""
    return min(num_lines, 8 * max(1, 4 // jnp.dtype(dtype).itemsize))


def gather_blocks_pallas(data: jax.Array, slots: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """data: (num_lines, line_elems); slots: (n,) int32 -> (n, line_elems).

    One requested line per grid step; the scalar-prefetched slot feeds the
    input index map, so consecutive steps' DMAs pipeline.
    """
    n = slots.shape[0]
    num_lines, line_elems = data.shape
    tile = _sublane_tile(num_lines, data.dtype)
    n_pad = round_up(n, tile)
    slots_p = jnp.full((n_pad,), -1, jnp.int32).at[:n].set(
        slots.astype(jnp.int32))

    def kernel(slots_ref, data_ref, out_ref):
        i = pl.program_id(0)
        slot = slots_ref[i]
        r = jnp.maximum(slot, 0) % tile
        line = data_ref[0:1, :]
        for k in range(1, tile):                  # static select chain
            line = jnp.where(r == k, data_ref[k:k + 1, :], line)
        line = jnp.where(slot >= 0, line, jnp.zeros_like(line))
        for k in range(tile):                     # static row store
            @pl.when(i % tile == k)
            def _():
                out_ref[k:k + 1, :] = line

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad,),
        in_specs=[
            pl.BlockSpec((tile, line_elems),
                         lambda i, s: (jnp.maximum(s[i], 0) // tile, 0)),
        ],
        out_specs=pl.BlockSpec((tile, line_elems),
                               lambda i, s: (i // tile, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, line_elems), data.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gather_blocks",
    )(slots_p, data)
    return out[:n]
