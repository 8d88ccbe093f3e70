"""BaM software cache (§III-D) — set-associative, clock replacement, functional.

The paper's cache is a GPU-resident, lock-minimal cache keyed by block
offset: per-line atomic state + reference counts; a global clock hand picks
victims; line locks prevent duplicate fetches of the same line.

TPU adaptation.  The unit of concurrency is the wavefront, and the BaM
coalescer (``core/coalescer.py``) runs *before* the cache, so by construction
at most one requester per line reaches the cache — the paper's per-line lock
becomes a static guarantee.  All state transitions are vectorized scatters
over a functional :class:`CacheState`:

* probe      — hash(key) -> set, compare the set's ``ways`` tags at once;
* allocate   — per-set clock sweep; concurrent misses that collide on a set
  are rank-ordered with a segmented prefix-sum so each takes a distinct way
  (or bypasses the cache when the set has no evictable way — the paper's
  "thread moves on and retries" becomes read-through-without-insert);
* fill       — scatter fetched lines into the data array;
* refcounts  — ``acquire``/``release`` pin lines against eviction, and a
  transient ``protect`` overlay guards this wavefront's hits.

The clock hand is per-set (a sharded fine-grain analogue of the paper's
single global counter — same policy, no cross-set serialization).

Prefetch support (``core/prefetch.py``): lines filled by readahead carry a
``speculative`` bit.  The victim sweep orders each set's ways *invalid
first, speculative second, demand-resident last* (within each class, clock
order), so a wrong prefetch is reclaimed before any demand line is touched
— speculative fills are "insert without pin".  A demand hit on a
speculative line *promotes* it (clears the bit): from then on it is an
ordinary resident line.

Async submission support (``BamArray.submit``/``wait``): a line whose tag
has been claimed but whose DMA has not completed carries an ``inflight``
bit — the vectorized analogue of the paper's per-line lock held between
command submission and completion.  A later submission that probes the
same key *hits* the in-flight line (cross-op coalescing: the duplicate
fetch is suppressed before it ever touches the SQ rings) and whichever
token completes first performs the fill and clears the bit.  Reference
counts now hold across the whole submit→wait span: every line a pending
token touched (hit or newly granted) stays pinned until that token is
waited, so interleaved tokens can never evict each other's in-flight data.

Multi-tenant support (``BamRuntime``): several BaM arrays can share one
``CacheState``.  Every resident line records its ``owner`` tenant, and
``probe``/``allocate`` take a ``tenant`` id so block key *k* of tenant A
never aliases block *k* of tenant B (the tag match requires the owner to
match too).  Isolation is *way-partitioning*: ``allocate(way_lo, way_hi)``
confines a tenant's clock sweep to its contiguous way quota, so a
streaming tenant can never evict a partitioned neighbour's lines.  With
the full way range (the default, and the runtime's ``isolation="shared"``
mode) tenants compete for every way exactly as a single tenant does today
— except that *foreign dirty* lines are never victimised: a write-back
must go to the evictor's own storage tier, so evicting another tenant's
dirty line would corrupt it.  Clean foreign lines are fair game (they are
re-fetchable from their owner's storage).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as _ops
from repro.utils import mix_hash, pytree_dataclass, segment_rank

__all__ = [
    "CacheState", "make_cache", "probe", "allocate", "probe_allocate",
    "fill", "acquire", "release", "pin_keys", "mark_dirty", "promote",
    "mark_inflight", "clear_inflight", "grant_bookkeeping",
    "fill_complete",
]


@pytree_dataclass(meta_fields=("num_sets", "ways", "line_elems"))
class CacheState:
    num_sets: int
    ways: int
    line_elems: int
    tags: jax.Array        # (num_sets, ways) int32 block key, -1 invalid
    owner: jax.Array       # (num_sets, ways) int32 tenant id of the line
    refcount: jax.Array    # (num_sets, ways) int32 — pinned lines have >0
    dirty: jax.Array       # (num_sets, ways) bool — needs write-back on evict
    speculative: jax.Array  # (num_sets, ways) bool — prefetched, evict-first
    inflight: jax.Array    # (num_sets, ways) bool — tag claimed, fill pending
    clock_hand: jax.Array  # (num_sets,) int32 in [0, ways)
    data: jax.Array        # (num_sets*ways, line_elems)
    hits: jax.Array        # () int32 cumulative line hits (post-coalesce)
    misses: jax.Array      # () int32 cumulative line misses
    bypasses: jax.Array    # () int32 misses that could not be inserted

    @property
    def num_lines(self) -> int:
        return self.num_sets * self.ways


def make_cache(num_sets: int, ways: int, line_elems: int,
               dtype=jnp.float32) -> CacheState:
    z = lambda: jnp.zeros((), jnp.int32)
    return CacheState(
        num_sets=num_sets, ways=ways, line_elems=line_elems,
        tags=jnp.full((num_sets, ways), -1, jnp.int32),
        owner=jnp.zeros((num_sets, ways), jnp.int32),
        refcount=jnp.zeros((num_sets, ways), jnp.int32),
        dirty=jnp.zeros((num_sets, ways), bool),
        speculative=jnp.zeros((num_sets, ways), bool),
        inflight=jnp.zeros((num_sets, ways), bool),
        clock_hand=jnp.zeros((num_sets,), jnp.int32),
        data=jnp.zeros((num_sets * ways, line_elems), dtype),
        hits=z(), misses=z(), bypasses=z(),
    )


def _set_of(cache: CacheState, keys: jax.Array) -> jax.Array:
    return mix_hash(keys) % cache.num_sets


@pytree_dataclass
class ProbeResult:
    hit: jax.Array    # (m,) bool
    slot: jax.Array   # (m,) int32 flat line slot (set*ways+way); -1 on miss
    set_idx: jax.Array  # (m,) int32 (reused by allocate)
    speculative: jax.Array  # (m,) bool — hit landed on a prefetched line
    inflight: jax.Array  # (m,) bool — hit landed on a not-yet-filled line


def probe(cache: CacheState, keys: jax.Array,
          valid: jax.Array | None = None, tenant: int = 0,
          impl: str = "auto") -> ProbeResult:
    """Vectorized set-associative lookup for a wavefront of (unique) keys.

    ``tenant`` namespaces the tag match: a line counts as a hit only when
    its owner matches, so shared-cache tenants with overlapping key spaces
    never read each other's lines.  Single-tenant callers keep the default
    (every line is owned by tenant 0).

    The tag compare is dispatched through :mod:`repro.kernels.ops`
    (``impl="auto"``: the Pallas one-hot-matmul probe on TPU, the
    bit-identical jnp oracle as an XLA graph elsewhere —
    :func:`repro.kernels.ops.resolve_impl`).
    """
    if valid is None:
        valid = keys >= 0
    sets = _set_of(cache, keys)                         # (m,)
    hit, slot = _ops.cache_probe(cache.tags, jnp.where(valid, keys, -1),
                                 owner=cache.owner, tenant=tenant, impl=impl)
    safe = jnp.where(hit, slot, 0)
    spec = hit & cache.speculative.reshape(-1)[safe]
    infl = hit & cache.inflight.reshape(-1)[safe]
    return ProbeResult(hit=hit, slot=slot, set_idx=sets.astype(jnp.int32),
                       speculative=spec, inflight=infl)


_segment_rank = segment_rank


def _apply_grants(cache: CacheState, keys: jax.Array, sets: jax.Array,
                  way: jax.Array, ok: jax.Array, n_valid: jax.Array,
                  speculative: bool, tenant: int) -> CacheState:
    """Commit a wavefront of victim grants: scatter the claimed tags and
    flags, advance each touched set's clock hand past the granted way,
    bump the miss/bypass counters.

    The single copy of the grant-commit block shared by :func:`allocate`
    and :func:`probe_allocate` — the two paths stay bit-identical by
    construction.  Rows with ``ok=False`` scatter out of bounds and drop;
    granted ``(set, way)`` pairs are distinct per wavefront by the rank
    disambiguation, so scatter order cannot matter.
    """
    ways = cache.ways

    def _commit():
        s_i = jnp.where(ok, sets, cache.num_sets)
        w_i = jnp.where(ok, way, 0)
        tags = cache.tags.at[s_i, w_i].set(keys, mode="drop")
        owner = cache.owner.at[s_i, w_i].set(jnp.int32(tenant), mode="drop")
        dirty = cache.dirty.at[s_i, w_i].set(False, mode="drop")
        spec = cache.speculative.at[s_i, w_i].set(speculative, mode="drop")
        # A granted line starts life *filled from the grantor's
        # perspective*: the async submit path re-marks it in flight right
        # after allocation.
        infl = cache.inflight.at[s_i, w_i].set(False, mode="drop")

        # Advance each touched set's hand past the granted way's clock
        # position (the victim select may run in class-sorted order, so the
        # position is recovered from the way index, not the sweep
        # position).
        hand = cache.clock_hand[sets]
        clock_pos = (way - hand) % ways
        adv = jnp.zeros((cache.num_sets,), jnp.int32).at[s_i].max(
            clock_pos + 1, mode="drop")
        return (tags, owner, dirty, spec, infl,
                (cache.clock_hand + adv) % ways)

    def _no_grants():
        return (cache.tags, cache.owner, cache.dirty, cache.speculative,
                cache.inflight, cache.clock_hand)

    # Hit fast path: a wavefront with no grants drops every update, so the
    # directory passes through bit-identical — skip the commit scatters.
    tags, owner, dirty, spec, infl, clock_hand = jax.lax.cond(
        jnp.any(ok), _commit, _no_grants)

    n_ok = jnp.sum(ok.astype(jnp.int32))
    # Speculative fills are not demand traffic: keep the miss/bypass
    # counters (the hit-rate denominators) demand-only.
    miss_inc = jnp.int32(0) if speculative else n_valid
    byp_inc = jnp.int32(0) if speculative else n_valid - n_ok
    return _replace_data(
        cache, tags=tags, owner=owner, dirty=dirty, speculative=spec,
        inflight=infl, clock_hand=clock_hand,
        misses=cache.misses + miss_inc,
        bypasses=cache.bypasses + byp_inc)


@pytree_dataclass
class AllocResult:
    slot: jax.Array          # (m,) int32 flat slot granted; -1 if bypassed/invalid
    ok: jax.Array            # (m,) bool — inserted into the cache
    evicted_key: jax.Array   # (m,) int32 key previously in the slot (-1 none)
    evicted_dirty: jax.Array  # (m,) bool — evicted line needs write-back


def allocate(cache: CacheState, keys: jax.Array,
             valid: jax.Array,
             protect_slots: jax.Array | None = None,
             speculative: bool = False,
             tenant: int = 0,
             way_lo: int = 0,
             way_hi: int | None = None,
             ) -> Tuple[CacheState, AllocResult]:
    """Grant a victim slot per missed key (clock sweep, rank-disambiguated).

    ``protect_slots`` is a wavefront-transient list of flat slots that must
    not be evicted (this round's hits); pass the probe hits' slots.

    ``speculative=True`` marks the granted lines as prefetched: they are
    inserted without pin and become the sweep's preferred victims until a
    demand hit :func:`promote`\\ s them.  Speculative allocations also never
    cannibalize a *pending* (unpromoted) prefetched line — they take free
    ways or retire old demand lines, and when a set offers neither the hint
    is simply dropped (``ok=False``, nothing fetched).  Without this rule a
    deep readahead window evicts its own not-yet-consumed predictions under
    set conflicts and turns into pure I/O waste.

    Multi-tenant knobs (all static): granted lines are stamped with
    ``tenant``; ``[way_lo, way_hi)`` confines the victim sweep to that way
    window (the runtime's way-partitioning — a partitioned tenant can only
    ever evict lines inside its own quota).  Whatever the window, a line
    that is *dirty and owned by another tenant* is never victimised: its
    write-back would be routed to the wrong storage tier.  The defaults
    (tenant 0, full way range) are byte-for-byte today's single-tenant
    behaviour.
    """
    m = keys.shape[0]
    ways = cache.ways
    way_hi = ways if way_hi is None else way_hi
    if not (0 <= way_lo < way_hi <= ways):
        raise ValueError(
            f"way window [{way_lo}, {way_hi}) invalid for ways={ways}")
    sets = _set_of(cache, keys)

    # Eviction eligibility per line: not referenced, not protected this
    # round, not another tenant's dirty data, inside the caller's way quota.
    elig_line = (cache.refcount == 0).reshape(-1)
    foreign_dirty = (cache.owner != jnp.int32(tenant)) \
        & (cache.tags >= 0) & cache.dirty
    elig_line = elig_line & ~foreign_dirty.reshape(-1)
    if way_lo != 0 or way_hi != ways:
        in_window = (jnp.arange(ways, dtype=jnp.int32) >= way_lo) \
            & (jnp.arange(ways, dtype=jnp.int32) < way_hi)
        elig_line = elig_line & jnp.broadcast_to(
            in_window[None, :], (cache.num_sets, ways)).reshape(-1)
    if speculative:
        pending = (cache.speculative & (cache.tags >= 0)).reshape(-1)
        elig_line = elig_line & ~pending
    if protect_slots is not None:
        psafe = jnp.where(protect_slots >= 0, protect_slots,
                          cache.num_lines)           # OOB -> dropped
        overlay = jnp.zeros((cache.num_lines,), bool).at[psafe].set(
            True, mode="drop")
        elig_line = elig_line & ~overlay
    elig = elig_line.reshape(cache.num_sets, ways)

    rank = _segment_rank(sets, valid)                   # (m,)
    hand = cache.clock_hand[sets]                       # (m,)
    way_order = (hand[:, None] + jnp.arange(ways, dtype=jnp.int32)[None, :]) % ways
    # Victim class per way: 0 = invalid (free), 1 = speculative (prefetched,
    # unpromoted), 2 = demand-resident.  A stable sort of the clock-rotated
    # sweep by class keeps clock order within each class while guaranteeing
    # prefetched lines are reclaimed before any demand line is touched.
    # With no speculative lines this coincides with the plain clock sweep:
    # tags are only ever invalid before first use and sets fill in clock
    # order, so the invalid ways are exactly the suffix the hand points at
    # — the demand-only path is unchanged from the paper's policy.
    vclass = jnp.where(cache.tags < 0, 0,
                       jnp.where(cache.speculative, 1, 2)).astype(jnp.int32)
    class_rot = vclass[sets[:, None], way_order]        # (m, ways)
    pref = jnp.argsort(class_rot, axis=1, stable=True)  # (m, ways)
    way_order = jnp.take_along_axis(way_order, pref, axis=1)
    elig_rot = elig[sets[:, None], way_order]           # (m, ways) in sweep order
    csum = jnp.cumsum(elig_rot.astype(jnp.int32), axis=1)
    want = (rank + 1)[:, None]
    sel = elig_rot & (csum == want)                     # first way with cum count == rank+1
    ok = valid & (csum[:, -1] >= rank + 1)
    way_pos = jnp.argmax(sel, axis=1).astype(jnp.int32)
    way = way_order[jnp.arange(m), way_pos]
    slot = (sets * ways + way).astype(jnp.int32)

    evicted_key = jnp.where(ok, cache.tags[sets, way], -1).astype(jnp.int32)
    evicted_dirty = jnp.where(ok, cache.dirty[sets, way], False)

    cache2 = _apply_grants(cache, keys, sets, way, ok,
                           jnp.sum(valid.astype(jnp.int32)),
                           speculative, tenant)
    return cache2, AllocResult(
        slot=jnp.where(ok, slot, -1), ok=ok,
        evicted_key=evicted_key, evicted_dirty=evicted_dirty)


def probe_allocate(cache: CacheState, keys: jax.Array,
                   valid: jax.Array | None = None, *,
                   alloc_mask: jax.Array | None = None,
                   protect_slots: jax.Array | None = None,
                   protect_hits: bool = True,
                   speculative: bool = False,
                   tenant: int = 0,
                   way_lo: int = 0,
                   way_hi: int | None = None,
                   impl: str = "auto",
                   ) -> Tuple[CacheState, ProbeResult, AllocResult]:
    """Fused :func:`probe` + :func:`allocate` — the submission hot path.

    One kernel pass (:func:`repro.kernels.ops.probe_allocate`) performs
    the tag probe and, for the misses, the class-then-clock victim select
    — argsort-free, honouring exactly what the two-step path honours:
    pinned lines, foreign dirty lines, the ``[way_lo, way_hi)`` tenant
    way window, pending speculative lines under ``speculative=True``,
    this wavefront's own hits (``protect_hits=True``, the fused
    equivalent of passing the probe's slots as ``protect_slots``) and any
    extra ``protect_slots``.  ``alloc_mask`` further restricts which
    misses may allocate (the readahead path's "never re-fetch a line this
    wavefront just evicted" rule).

    The scatters (tag claim, owner stamp, clock-hand advance, miss/bypass
    counters) are identical to :func:`allocate`'s; results are
    bit-identical to ``probe`` + ``allocate`` with
    ``protect_slots=probe.slot`` — the oracle tests assert it.
    """
    m = keys.shape[0]
    ways = cache.ways
    way_hi = ways if way_hi is None else way_hi
    if not (0 <= way_lo < way_hi <= ways):
        raise ValueError(
            f"way window [{way_lo}, {way_hi}) invalid for ways={ways}")
    if valid is None:
        valid = keys >= 0
    sets = _set_of(cache, keys)

    hit, hslot, way, ok, evicted_key, evicted_dirty = _ops.probe_allocate(
        cache.tags, cache.owner, cache.refcount, cache.dirty,
        cache.speculative, cache.clock_hand, keys, valid=valid,
        alloc_mask=alloc_mask, protect_slots=protect_slots, tenant=tenant,
        way_lo=way_lo, way_hi=way_hi, spec_insert=speculative,
        protect_hits=protect_hits, impl=impl)

    safe = jnp.where(hit, hslot, 0)
    pr = ProbeResult(
        hit=hit, slot=hslot, set_idx=sets.astype(jnp.int32),
        speculative=hit & cache.speculative.reshape(-1)[safe],
        inflight=hit & cache.inflight.reshape(-1)[safe])

    slot = (sets * ways + jnp.where(ok, way, 0)).astype(jnp.int32)

    miss = valid & ~hit
    if alloc_mask is not None:
        miss = miss & alloc_mask
    cache2 = _apply_grants(cache, keys, sets, way, ok,
                           jnp.sum(miss.astype(jnp.int32)),
                           speculative, tenant)
    return cache2, pr, AllocResult(
        slot=jnp.where(ok, slot, -1), ok=ok,
        evicted_key=evicted_key, evicted_dirty=evicted_dirty)


def fill(cache: CacheState, slots: jax.Array, ok: jax.Array,
         lines: jax.Array) -> CacheState:
    """DMA-completion analogue: scatter fetched lines into granted slots.

    A completion wave with nothing pending (every lane already resident —
    the warm-cache steady state) drops every update, so the line store
    passes through bit-identical and the full-width data scatter is
    skipped."""
    def _commit():
        idx = jnp.where(ok, slots, cache.num_lines)      # OOB -> dropped
        return cache.data.at[idx].set(lines.astype(cache.data.dtype),
                                      mode="drop")
    data = jax.lax.cond(jnp.any(ok), _commit, lambda: cache.data)
    return _replace_data(cache, data=data)


def count_hits(cache: CacheState, n_hits: jax.Array) -> CacheState:
    return _replace_data(cache, hits=cache.hits + n_hits)


def acquire(cache: CacheState, slots: jax.Array) -> CacheState:
    """refcount++ on the given flat slots (slot<0 ignored)."""
    ok = slots >= 0
    idx = jnp.where(ok, slots, 0)
    rc = cache.refcount.reshape(-1).at[idx].add(ok.astype(jnp.int32))
    return _replace_data(cache, refcount=rc.reshape(cache.num_sets, cache.ways))


def release(cache: CacheState, slots: jax.Array) -> CacheState:
    ok = slots >= 0
    idx = jnp.where(ok, slots, 0)
    rc = cache.refcount.reshape(-1).at[idx].add(-ok.astype(jnp.int32))
    rc = jnp.maximum(rc, 0)
    return _replace_data(cache, refcount=rc.reshape(cache.num_sets, cache.ways))


def pin_keys(cache: CacheState, keys: jax.Array,
             tenant: int = 0) -> CacheState:
    """User-directed residency control (paper: 'fine-grain control of cache
    residency'): pin resident lines for the given keys."""
    pr = probe(cache, keys, tenant=tenant)
    return acquire(cache, pr.slot)


def promote(cache: CacheState, slots: jax.Array) -> CacheState:
    """Clear the speculative bit on the given flat slots (slot<0 ignored).

    Called when a demand access hits a prefetched line: from then on the
    line competes for residency like any other demand line.
    """
    ok = slots >= 0
    idx = jnp.where(ok, slots, cache.num_lines)          # OOB -> dropped
    s = cache.speculative.reshape(-1)
    s = s.at[idx].set(False, mode="drop")
    return _replace_data(cache,
                         speculative=s.reshape(cache.num_sets, cache.ways))


def mark_inflight(cache: CacheState, slots: jax.Array) -> CacheState:
    """Mark the given flat slots as *in flight* (slot<0 ignored).

    An in-flight line has its tag claimed (so concurrent submissions
    coalesce against it — the paper's per-line lock / BaM's duplicate-fetch
    suppression) but its data not yet DMA'd.  The token that fills the line
    (or any waiter that finds it still pending) clears the bit; readers
    must never gather from a line whose in-flight bit is set.
    """
    ok = slots >= 0
    idx = jnp.where(ok, slots, cache.num_lines)          # OOB -> dropped
    s = cache.inflight.reshape(-1)
    s = s.at[idx].set(True, mode="drop")
    return _replace_data(cache,
                         inflight=s.reshape(cache.num_sets, cache.ways))


def clear_inflight(cache: CacheState, slots: jax.Array) -> CacheState:
    """Clear the in-flight bit on the given flat slots (slot<0 ignored)."""
    ok = slots >= 0
    idx = jnp.where(ok, slots, cache.num_lines)          # OOB -> dropped
    s = cache.inflight.reshape(-1)
    s = s.at[idx].set(False, mode="drop")
    return _replace_data(cache,
                         inflight=s.reshape(cache.num_sets, cache.ways))


def grant_bookkeeping(cache: CacheState, n_hits: jax.Array,
                      promote_slots: jax.Array, pin_slots: jax.Array,
                      inflight_slots: jax.Array) -> CacheState:
    """Fused submission-side bookkeeping: :func:`count_hits` +
    :func:`promote` + :func:`acquire` + :func:`mark_inflight` in ONE
    :class:`CacheState` construction.

    The four steps touch disjoint fields (``hits``, ``speculative``,
    ``refcount``, ``inflight``), so the fusion is bit-identical to the
    sequential helpers in any order — this is the traced-submit hot path
    trimming three full pytree rebuilds per wavefront.
    """
    ok_p = promote_slots >= 0
    spec = cache.speculative.reshape(-1).at[
        jnp.where(ok_p, promote_slots, cache.num_lines)].set(
        False, mode="drop")
    ok_a = pin_slots >= 0
    rc = cache.refcount.reshape(-1).at[
        jnp.where(ok_a, pin_slots, 0)].add(ok_a.astype(jnp.int32))
    ok_i = inflight_slots >= 0
    infl = cache.inflight.reshape(-1).at[
        jnp.where(ok_i, inflight_slots, cache.num_lines)].set(
        True, mode="drop")
    shape2 = (cache.num_sets, cache.ways)
    return _replace_data(
        cache, hits=cache.hits + n_hits,
        speculative=spec.reshape(shape2), refcount=rc.reshape(shape2),
        inflight=infl.reshape(shape2))


def fill_complete(cache: CacheState, slots: jax.Array, ok: jax.Array,
                  lines: jax.Array) -> CacheState:
    """Fused completion: :func:`fill` + :func:`clear_inflight` on the same
    slots in ONE :class:`CacheState` construction (``data`` and
    ``inflight`` are disjoint fields — bit-identical to the pair).

    Gated like :func:`fill`: a wait with nothing pending (warm-cache
    steady state) skips the full-width data scatter entirely."""
    def _commit():
        idx = jnp.where(ok, slots, cache.num_lines)      # OOB -> dropped
        data = cache.data.at[idx].set(lines.astype(cache.data.dtype),
                                      mode="drop")
        infl = cache.inflight.reshape(-1).at[idx].set(False, mode="drop")
        return data, infl.reshape(cache.num_sets, cache.ways)

    data, infl = jax.lax.cond(
        jnp.any(ok), _commit, lambda: (cache.data, cache.inflight))
    return _replace_data(cache, data=data, inflight=infl)


def invalidate_failed(cache: CacheState, slots: jax.Array,
                      mask: jax.Array) -> CacheState:
    """Graceful degradation: evict granted-but-never-filled lines whose
    fetch command errored out past its retry budget.

    The line's tag is freed (so a later access re-allocates and re-fetches
    it), its in-flight and speculative bits clear, and its dirty bit
    clears — the data store is **not** touched: a line is never filled
    from a failed fetch, and a freed tag can never be gathered.  Pins are
    left to the normal :func:`release` pairing (a still-pinned invalid
    slot cannot be re-allocated — victim eligibility requires
    ``refcount == 0`` — so riders holding the pin stay safe and fall back
    to read-through).
    """
    live = mask & (slots >= 0)
    idx = jnp.where(live, slots, cache.num_lines)        # OOB -> dropped
    shape2 = (cache.num_sets, cache.ways)
    tags = cache.tags.reshape(-1).at[idx].set(-1, mode="drop")
    infl = cache.inflight.reshape(-1).at[idx].set(False, mode="drop")
    spec = cache.speculative.reshape(-1).at[idx].set(False, mode="drop")
    dirty = cache.dirty.reshape(-1).at[idx].set(False, mode="drop")
    return _replace_data(
        cache, tags=tags.reshape(shape2), inflight=infl.reshape(shape2),
        speculative=spec.reshape(shape2), dirty=dirty.reshape(shape2))


def fill_complete_status(cache: CacheState, slots: jax.Array,
                         pend: jax.Array, ok: jax.Array,
                         lines: jax.Array) -> CacheState:
    """Status-aware fused completion: :func:`fill` the ``pend & ok`` slots,
    :func:`clear_inflight` every ``pend`` slot, and
    :func:`invalidate_failed` the ``pend & ~ok`` slots, in ONE
    :class:`CacheState` construction.

    The fault-enabled counterpart of :func:`fill_complete` (which is the
    ``ok == True`` special case): failed fetches never reach the data
    store — their lines leave the wait un-inflighted, tag-free and
    clean, exactly as :func:`invalidate_failed` documents.  Gated on any
    pending slot, like the helpers it fuses.
    """
    def _commit():
        good = pend & ok & (slots >= 0)
        bad = pend & ~ok & (slots >= 0)
        live = pend & (slots >= 0)
        idx_g = jnp.where(good, slots, cache.num_lines)  # OOB -> dropped
        idx_p = jnp.where(live, slots, cache.num_lines)
        idx_b = jnp.where(bad, slots, cache.num_lines)
        data = cache.data.at[idx_g].set(lines.astype(cache.data.dtype),
                                        mode="drop")
        infl = cache.inflight.reshape(-1).at[idx_p].set(False, mode="drop")
        tags = cache.tags.reshape(-1).at[idx_b].set(-1, mode="drop")
        spec = cache.speculative.reshape(-1).at[idx_b].set(False,
                                                          mode="drop")
        dirty = cache.dirty.reshape(-1).at[idx_b].set(False, mode="drop")
        shape2 = (cache.num_sets, cache.ways)
        return (data, infl.reshape(shape2), tags.reshape(shape2),
                spec.reshape(shape2), dirty.reshape(shape2))

    data, infl, tags, spec, dirty = jax.lax.cond(
        jnp.any(pend), _commit,
        lambda: (cache.data, cache.inflight, cache.tags, cache.speculative,
                 cache.dirty))
    return _replace_data(cache, data=data, inflight=infl, tags=tags,
                         speculative=spec, dirty=dirty)


def mark_dirty(cache: CacheState, slots: jax.Array) -> CacheState:
    ok = slots >= 0
    idx = jnp.where(ok, slots, cache.num_lines)          # OOB -> dropped
    d = cache.dirty.reshape(-1)
    d = d.at[idx].set(True, mode="drop")
    return _replace_data(cache, dirty=d.reshape(cache.num_sets, cache.ways))


def write_line(cache: CacheState, slots: jax.Array, ok: jax.Array,
               lines: jax.Array) -> CacheState:
    """Update resident lines in place and mark them dirty (write hit path)."""
    cache = fill(cache, slots, ok, lines)
    return mark_dirty(cache, jnp.where(ok, slots, -1))


def _replace_data(cache: CacheState, **kw) -> CacheState:
    fields = dict(
        num_sets=cache.num_sets, ways=cache.ways, line_elems=cache.line_elems,
        tags=cache.tags, owner=cache.owner, refcount=cache.refcount,
        dirty=cache.dirty, speculative=cache.speculative,
        inflight=cache.inflight,
        clock_hand=cache.clock_hand, data=cache.data,
        hits=cache.hits, misses=cache.misses, bypasses=cache.bypasses,
    )
    fields.update(kw)
    return CacheState(**fields)
