"""Set-associative cache probe Pallas-TPU kernel.

BaM's GPU probe is a per-thread hash + tag compare with warp coalescing.
The TPU-native adaptation replaces the random tag-row *gather* (a poor fit
for the TPU memory system) with a **one-hot MXU matmul**: the wavefront's
set indices become a one-hot matrix that multiplies the tag directory, so
the probe rides the systolic array instead of scalar loads.

int32 tags are exact-gathered by splitting into two 16-bit halves (each
exactly representable in f32), gathering both halves with the same one-hot
matmul, and recombining — a standard exact-gather-by-matmul trick.

Grid: ``(request blocks, set blocks)``.  Each step holds one ``block_m``
column of requests and one ``block_s``-set slice of the directory in VMEM,
so the one-hot is ``(block_m, block_s)`` whatever the directory size; a
request's set lies in exactly one slice, and the slice that holds it
writes its hit and slot into the request block's resident output.
Requests and outputs are ``(m, 1)`` columns, so a request's set index is
already sublane-major when it meets the lane iota of the set slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import round_up


def _hash(k):
    """:func:`repro.utils.mix_hash` in int32 arithmetic (the TPU kernel
    has no unsigned->float casts): wrapping multiply, logical shift."""
    k = k * jnp.int32(-1640531535)                 # 2654435761 mod 2**32
    k = k ^ jax.lax.shift_right_logical(k, jnp.int32(16))
    return k & jnp.int32(0x7FFFFFFF)


def _exact_rows(onehot, table):
    """Exact int32 row gather through the one-hot matmul (16-bit halves,
    each exact in f32; HIGHEST keeps the MXU from rounding them to bf16)."""
    lo = (table & jnp.int32(0xFFFF)).astype(jnp.float32)
    hi = jax.lax.shift_right_logical(table, jnp.int32(16)).astype(jnp.float32)
    dims = (((1,), (0,)), ((), ()))
    hp = jax.lax.Precision.HIGHEST
    row_lo = jax.lax.dot_general(onehot, lo, dims, precision=hp,
                                 preferred_element_type=jnp.float32)
    row_hi = jax.lax.dot_general(onehot, hi, dims, precision=hp,
                                 preferred_element_type=jnp.float32)
    return (row_hi.astype(jnp.int32) << 16) | row_lo.astype(jnp.int32)


def _first_way(mask, ways):
    """Lowest set way per row of an ``(m, ways)`` bool matrix, else -1, as
    an ``(m, 1)`` column (a static unroll; no argmax over bools on TPU)."""
    way = jnp.full((mask.shape[0], 1), -1, jnp.int32)
    for w in reversed(range(ways)):
        way = jnp.where(mask[:, w:w + 1], w, way)
    return way


def _probe_kernel(keys_ref, tags_ref, *rest, num_sets: int, ways: int,
                  bm: int, bs: int, tenant: int, has_owner: bool):
    if has_owner:
        owner_ref, hit_ref, slot_ref = rest
    else:
        owner_ref, (hit_ref, slot_ref) = None, rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        hit_ref[...] = jnp.zeros_like(hit_ref)
        slot_ref[...] = jnp.full_like(slot_ref, -1)

    keys = keys_ref[...]                             # (bm, 1)
    valid = keys >= 0
    sets = _hash(jnp.where(valid, keys, 0)) % num_sets
    local = sets - j * bs                            # set within this slice
    onehot = (local == jax.lax.broadcasted_iota(jnp.int32, (bm, bs), 1)
              ).astype(jnp.float32)                  # (bm, bs)
    rows = _exact_rows(onehot, tags_ref[...])        # (bm, W); 0 off-slice
    in_slice = (local >= 0) & (local < bs)
    eq = (rows == keys) & valid & in_slice
    if has_owner:
        eq = eq & (_exact_rows(onehot, owner_ref[...]) == jnp.int32(tenant))
    way = _first_way(eq, ways)
    hit = way >= 0
    hit_ref[...] = jnp.where(hit, 1, hit_ref[...])
    slot_ref[...] = jnp.where(hit, sets * ways + way, slot_ref[...])


def cache_probe_pallas(tags: jax.Array, keys: jax.Array, *,
                       owner: jax.Array | None = None, tenant: int = 0,
                       block_m: int = 512, block_s: int = 1024,
                       interpret: bool = False):
    """tags: (num_sets, ways) int32; keys: (m,) int32.

    Returns (hit (m,) bool, slot (m,) int32 flat line slot, -1 on miss) —
    bit-identical to :func:`repro.core.cache.probe`.  With ``owner`` (the
    per-line tenant stamp), a hit additionally requires ``owner ==
    tenant`` — the multi-tenant tag namespacing.
    """
    num_sets, ways = tags.shape
    m = keys.shape[0]
    bm = round_up(min(block_m, m), 8)
    mp = round_up(m, bm)
    bs = num_sets if num_sets <= block_s else block_s
    sp = round_up(num_sets, bs)
    keys_p = jnp.full((mp, 1), -1, jnp.int32).at[:m, 0].set(keys)

    def pad_dir(d):
        return d if sp == num_sets else jnp.pad(
            d, ((0, sp - num_sets), (0, 0)), constant_values=-1)

    kernel = functools.partial(_probe_kernel, num_sets=num_sets, ways=ways,
                               bm=bm, bs=bs, tenant=tenant,
                               has_owner=owner is not None)
    col_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    dir_spec = pl.BlockSpec((bs, ways), lambda i, j: (j, 0))
    in_specs = [col_spec, dir_spec]
    operands = [keys_p, pad_dir(tags)]
    if owner is not None:
        in_specs.append(dir_spec)
        operands.append(pad_dir(owner))
    hit, slot = pl.pallas_call(
        kernel,
        grid=(mp // bm, sp // bs),
        in_specs=in_specs,
        out_specs=[col_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct((mp, 1), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cache_probe",
    )(*operands)
    return hit[:m, 0].astype(bool), slot[:m, 0]
