"""Fused cache probe + clock-sweep victim select — Pallas-TPU kernel.

This is the BaM submission hot path (probe → allocate) as *one* set-local
pass: hash each request to its set, compare the set's tags (hit / miss),
and — for the misses — pick the victim way in **class-then-clock** order
(invalid first, speculative second, demand-resident last; clock order
within each class), honouring pinned lines, the tenant way window, foreign
dirty lines, pending speculative lines and protected slots.

TPU adaptation, same playbook as ``cache_probe.py``:

* every row gather (tags, owner, refcount, dirty, speculative, clock hand)
  rides a **one-hot MXU matmul** instead of a random gather; int32 values
  are exact-gathered by 16-bit halves;
* the paper's "threads racing on the clock hand" becomes the segmented
  rank of each miss among same-set misses — computed here as an exclusive
  prefix sum of the one-hot set matrix, taken by a **strictly lower
  triangular matmul** (no sort, no atomic; Mosaic has no cumsum);
* the victim is selected *without materializing the ``(m, ways)`` stable
  argsort* the jnp core used: each way's sort key is
  ``class * ways + clock_pos`` (distinct per row), and the chosen way is
  the eligible one whose *eligible-order index* — the count of eligible
  ways with a strictly smaller key, a ``ways``-step unrolled comparison —
  equals the request's rank.  This selects exactly the way the stable
  argsort would;
* protected slots (this wavefront's hits + the caller's explicit list)
  are scattered into a per-(set, way) count matrix by a second one-hot
  matmul — a scatter-by-matmul, no ``.at[]``.

Grid: a single step; the wavefront (as ``(m, 1)`` columns), the
directory, the ``(m, num_sets)`` one-hot and the ``(m, m)`` triangle are
resident in VMEM.  That fits small wavefronts over small directories
only: for 4096 lanes the TPU compiler refuses it, so ``impl="auto"`` runs
the jnp oracle for this stage (see :mod:`repro.kernels.ops`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cache_probe import _exact_rows, _first_way, _hash
from repro.utils import round_up


def _pa_kernel(keys_ref, amask_ref, prot_ref, tags_ref, owner_ref,
               refcount_ref, dirty_ref, spec_ref, hand_ref,
               hit_ref, hslot_ref, way_ref, ok_ref, evk_ref, evd_ref, *,
               num_sets: int, ways: int, m: int, tenant: int, way_lo: int,
               way_hi: int, spec_insert: bool, protect_hits: bool):
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    keys = keys_ref[...]                                 # (m, 1)
    valid = keys >= 0
    amask = amask_ref[...] != 0
    sets = _hash(jnp.where(valid, keys, 0)) % num_sets   # (m, 1)

    onehot = (sets == jax.lax.broadcasted_iota(jnp.int32, (m, num_sets), 1)
              ).astype(f32)                              # (m, S)

    def gather_small(table_f32):
        """Row gather of small non-negative counts/flags (exact in f32)."""
        return jax.lax.dot_general(onehot, table_f32,
                                   (((1,), (0,)), ((), ())), precision=hp,
                                   preferred_element_type=f32)

    def scatter_count(rows_onehot, cols_onehot):
        """Per-(set, way) count matrix of (row set, col way) pairs."""
        return jax.lax.dot_general(rows_onehot, cols_onehot,
                                   (((0,), (0,)), ((), ())), precision=hp,
                                   preferred_element_type=f32)

    tags_rows = _exact_rows(onehot, tags_ref[...])       # (m, W)
    owner_rows = _exact_rows(onehot, owner_ref[...])
    ref_rows = gather_small(refcount_ref[...].astype(f32))
    dirty_rows = gather_small(dirty_ref[...].astype(f32)) > 0.5
    spec_rows = gather_small(spec_ref[...].astype(f32)) > 0.5
    hand = gather_small(hand_ref[...].astype(f32)).astype(jnp.int32)  # (m,1)

    # ---- probe ----------------------------------------------------------
    eq = (tags_rows == keys) & valid & (owner_rows == jnp.int32(tenant))
    hway = _first_way(eq, ways)                          # (m, 1)
    hit = hway >= 0
    hslot = jnp.where(hit, sets * ways + hway, -1)

    miss = valid & ~hit & amask

    # ---- per-(row, way) eligibility -------------------------------------
    warange = jax.lax.broadcasted_iota(jnp.int32, (m, ways), 1)
    elig = ref_rows < 0.5
    foreign_dirty = (owner_rows != jnp.int32(tenant)) \
        & (tags_rows >= 0) & dirty_rows
    elig = elig & ~foreign_dirty
    if way_lo != 0 or way_hi != ways:
        elig = elig & (warange >= way_lo) & (warange < way_hi)
    if spec_insert:
        elig = elig & ~(spec_rows & (tags_rows >= 0))

    # protected (set, way) pairs: scatter-by-matmul into a count matrix.
    prot_mat = jnp.zeros((num_sets, ways), f32)
    if protect_hits:
        prot_mat = prot_mat + scatter_count(
            onehot * hit.astype(f32), (hway == warange).astype(f32))
    prot = prot_ref[...]                                 # (p, 1) flat slots
    pvalid = prot >= 0
    psets = jnp.where(pvalid, prot // ways, 0)
    pways = jnp.where(pvalid, prot % ways, 0)
    p = prot.shape[0]
    ponehot = ((psets ==
                jax.lax.broadcasted_iota(jnp.int32, (p, num_sets), 1))
               & pvalid).astype(f32)
    pw1 = (pways ==
           jax.lax.broadcasted_iota(jnp.int32, (p, ways), 1)).astype(f32)
    prot_mat = prot_mat + scatter_count(ponehot, pw1)
    elig = elig & ~(gather_small(prot_mat) > 0.5)

    # ---- rank among same-set misses: exclusive prefix sum, no sort ------
    # (a strictly-lower-triangular matmul: Mosaic has no cumsum)
    miss_col = onehot * miss.astype(f32)                 # (m, S)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)).astype(f32)
    csum = jax.lax.dot_general(lower, miss_col, (((1,), (0,)), ((), ())),
                               precision=hp, preferred_element_type=f32)
    rank = jnp.sum(csum * onehot, axis=1, keepdims=True).astype(jnp.int32)

    # ---- class-then-clock victim select, argsort-free -------------------
    clock_pos = (warange - hand + ways) % ways
    vclass = jnp.where(tags_rows < 0, 0,
                       jnp.where(spec_rows, 1, 2)).astype(jnp.int32)
    key_w = vclass * ways + clock_pos                    # distinct per row
    eidx = jnp.zeros((m, ways), jnp.int32)
    n_elig = jnp.zeros((m, 1), jnp.int32)
    for wp in range(ways):                               # static unroll
        e_wp = elig[:, wp:wp + 1]
        eidx = eidx + ((key_w[:, wp:wp + 1] < key_w) & e_wp
                       ).astype(jnp.int32)
        n_elig = n_elig + e_wp.astype(jnp.int32)
    sel = elig & (eidx == rank) & miss
    ok = miss & (n_elig >= rank + 1)
    way = jnp.maximum(_first_way(sel, ways), 0)
    dirty_i = dirty_rows.astype(jnp.int32)               # no i1 selects
    evk = jnp.zeros((m, 1), jnp.int32)
    evd = jnp.zeros((m, 1), jnp.int32)
    for w in range(ways):                                # static unroll
        pick = way == w
        evk = jnp.where(pick, tags_rows[:, w:w + 1], evk)
        evd = jnp.where(pick, dirty_i[:, w:w + 1], evd)

    hit_ref[...] = hit.astype(jnp.int32)
    hslot_ref[...] = hslot
    way_ref[...] = jnp.where(ok, way, -1)
    ok_ref[...] = ok.astype(jnp.int32)
    evk_ref[...] = jnp.where(ok, evk, -1)
    evd_ref[...] = jnp.where(ok, evd, 0)


def probe_allocate_pallas(tags, owner, refcount, dirty, speculative,
                          clock_hand, keys, valid, alloc_mask=None,
                          protect_slots=None, *, tenant: int = 0,
                          way_lo: int = 0, way_hi: int | None = None,
                          spec_insert: bool = False,
                          protect_hits: bool = True,
                          interpret: bool = False):
    """Fused probe + victim select over a raw cache directory.

    Inputs mirror :class:`repro.core.cache.CacheState` fields; ``keys`` /
    ``valid`` / ``alloc_mask`` are the wavefront.  Returns ``(hit,
    hit_slot, way, ok, evicted_key, evicted_dirty)``, bit-identical to
    :func:`repro.kernels.ref.probe_allocate_ref`.
    """
    num_sets, ways = tags.shape
    way_hi = ways if way_hi is None else way_hi
    m = keys.shape[0]
    mp = round_up(m, 8)
    keys_p = jnp.full((mp, 1), -1, jnp.int32).at[:m, 0].set(
        jnp.where(valid, keys, -1).astype(jnp.int32))
    am = jnp.ones((m,), jnp.int32) if alloc_mask is None \
        else alloc_mask.astype(jnp.int32)
    am_p = jnp.zeros((mp, 1), jnp.int32).at[:m, 0].set(am)
    prot = jnp.full((1,), -1, jnp.int32) if protect_slots is None \
        else protect_slots.astype(jnp.int32)
    pp = round_up(prot.shape[0], 8)
    prot_p = jnp.full((pp, 1), -1, jnp.int32).at[:prot.shape[0], 0].set(prot)

    kernel = functools.partial(
        _pa_kernel, num_sets=num_sets, ways=ways, m=mp, tenant=tenant,
        way_lo=way_lo, way_hi=way_hi, spec_insert=spec_insert,
        protect_hits=protect_hits)
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    dir_spec = whole((num_sets, ways))
    col = whole((mp, 1))
    out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[col, col, whole((pp, 1))] + [dir_spec] * 5
        + [whole((num_sets, 1))],
        out_specs=[col] * 6,
        out_shape=[jax.ShapeDtypeStruct((mp, 1), jnp.int32)] * 6,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="probe_allocate",
    )(keys_p, am_p, prot_p,
      tags, owner, refcount.astype(jnp.int32),
      dirty.astype(jnp.int32), speculative.astype(jnp.int32),
      clock_hand.reshape(num_sets, 1))
    hit, hslot, way, ok, evk, evd = [o[:m, 0] for o in out]
    return (hit.astype(bool), hslot, way, ok.astype(bool), evk,
            evd.astype(bool))
