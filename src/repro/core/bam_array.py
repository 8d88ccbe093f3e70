"""``BamArray<T>`` / ``BamKVStore`` — BaM's high-level abstractions (§III-E).

``BamArray`` is the paper's array whose subscript operator transparently:
coalesces the wavefront's accesses, probes the software cache, issues NVMe
reads for the misses through the high-throughput queues, fills the cache,
and returns elements.  Here the subscript is a *functional* ``read``:

    values, state' = bam.read(state, flat_indices)

with every piece of BaM state (cache, queues, I/O metrics, and — for the
in-graph backend — the storage tier itself) threaded through explicitly.

Asynchrony is first-class: the primitive surface is ``submit(st, req) ->
(st, token)`` / ``wait(st, token) -> (st, values)``, where
:class:`IORequest` is the unified op descriptor (read / write / prefetch
share one submission path) and :class:`IOToken` the redeemable future.
``submit`` probes, pins, allocates and enqueues SQ commands *without*
draining; ``wait`` drains, performs the deferred fetch DMA, fills,
gathers and unpins.  Many tokens may be outstanding at once — that is
the paper's whole point (§II-C): the queues must hold ``Q_d = T x L``
requests in flight, which a synchronous per-op API can never reach.
Duplicate blocks are coalesced *across* pending ops (a submission that
probes a line another token is already fetching rides that command — the
cache's in-flight bit is the per-line lock), and the legacy ``read`` /
``write`` / ``prefetch`` calls below are thin submit+wait shims.

The life of a wavefront (paper Fig. 3, adapted):

    element idx ──► block key + offset
        │ coalesce (warp coalescer, §III-D)          -> unique lines, leaders
        │ probe cache                                 -> hits / misses
        │   (hit on a prefetched line: promote it, count a prefetch hit)
        │ allocate victims (clock)                    -> slots (or bypass)
        │ gather evicted dirty lines                  -> write-back commands
        │ readahead detect + speculative allocate     -> low-priority fills
        │ enqueue reads+write-backs+readahead,        -> SQ rings (§III-C)
        │   ring doorbells (demand lane drains first)
        │ service (simulated NVMe drain + DMA)        -> fetched lines
        │ fill cache, update tags/dirty/speculative
        ▼ gather elements (hit: cache line, miss: fetched line)

Requests dropped by full rings are still served read-through (and counted),
so a mis-sized queue config degrades accounting, never correctness.

Prefetching is off by default (``PrefetchConfig.enabled``); when on, the
stride detector in :mod:`repro.core.prefetch` extrapolates the wavefront's
block pattern ``window`` lines ahead and brings those lines in through the
readahead lane as evict-first *speculative* residents.  The explicit
:meth:`BamArray.prefetch` API lets applications (BFS frontiers, column
scans) push known-future wavefronts directly.

Multi-tenant sharing (:class:`BamRuntime`): the paper's central claim is
that *one* software cache and *one* queue pool serve many concurrent GPU
applications.  ``BamRuntime`` registers several tenants (each a
``BamArray`` over its own storage) against a single shared
``CacheState``/``QueueState``: each tenant's :class:`TenantCtx` carries
its tenant id (namespacing cache tags and queue commands) and its cache
*way quota* (``isolation="partitioned"`` confines each tenant's clock
sweep to its own ways; ``isolation="shared"`` keeps the free-for-all so
contention is measurable).  Per-tenant :class:`IOMetrics` accumulate next
to a global view, with the invariant that additive tenant counters sum
exactly to the global counters.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import cache as C
from repro.core import queues as Q
from repro.core.coalescer import coalesce
from repro.kernels import ops as K
from repro.core.metrics import (
    IOMetrics, metrics_accumulate, metrics_delta, metrics_sum,
    recheck_token_watermark,
)
from repro.core.prefetch import PrefetchConfig, readahead_keys
from repro.core.ssd import (ArrayOfSSDs, INTEL_OPTANE_P5800X,
                            device_histogram, device_of_block)
from repro.core.storage import HBMStorage, SimStorage
from repro.utils import pad_to, pytree_dataclass, round_up

__all__ = ["BamArray", "BamState", "BamKVStore", "KVState", "KVToken",
           "PrefetchConfig",
           "TenantCtx", "TenantSpec", "BamRuntime", "RuntimeState",
           "IORequest", "IOToken", "DEFAULT_BUCKETS", "OpFamilyEntry"]

# Wavefront shape buckets for the bucketed submit/wait wrappers: ragged
# production batch sizes are padded up to the smallest bucket (masked
# lanes are provably inert), so a sweep of sizes compiles at most
# ``len(DEFAULT_BUCKETS)`` executables per op instead of one per size.
DEFAULT_BUCKETS = (64, 256, 1024, 4096)


def _op_name(key: str) -> str:
    """The jitted op's name for jit-cache key ``key``: ``"submit[donated]"``
    -> ``"bam_submit_donated"``, ``"read:a"`` -> ``"bam_read_a"``."""
    return "bam_" + "_".join(re.findall(r"[A-Za-z0-9]+", key))


def _cached_jit(cache: Dict[str, Any], counts: Dict[str, int], key: str,
                make, donate_argnums=()):
    """One ``jax.jit`` per op key, cached in ``cache``; jit itself keys
    compiled executables by argument shape/dtype/pytree structure, so
    steady-state ops at fixed shapes never retrace.

    The Python body of the traced callable bumps ``counts[key]`` — Python
    runs only while JAX *traces* (a jit cache miss), so the counter is an
    exact retrace probe (the retrace-regression tests and
    ``benchmarks/hot_path.py`` read it).  Shared by :class:`BamArray` and
    :class:`BamRuntime`.

    ``donate_argnums`` is forwarded to ``jax.jit``: a donating op key
    (``"submit[donated]"`` …) hands its state argument's buffers to the
    output, so steady-state rounds update the multi-MB ``CacheState`` /
    ``QueueState`` in place instead of copying them.  The caller must not
    touch a donated value afterwards (JAX raises on reuse; bamlint rule
    BAM106 flags it statically).

    The traced callable is named after the key (:func:`_op_name`), so the
    jitted op and every device op it holds carry ``jit(bam_submit...)``,
    ``jit(bam_wait...)`` ... in the profiler's trace, and the stage scopes
    of :meth:`BamArray.submit` / :meth:`BamArray.wait_ex` sit below it.
    """
    fn = cache.get(key)
    if fn is None:
        raw = make()

        def counted(*args, _raw=raw, _key=key, **kw):
            counts[_key] = counts.get(_key, 0) + 1
            return _raw(*args, **kw)

        counted.__name__ = counted.__qualname__ = _op_name(key)
        # this IS the per-instance jit cache the rule points at
        fn = jax.jit(counted,  # bamlint: ignore[BAM105]
                     donate_argnums=tuple(donate_argnums))
        cache[key] = fn
    return fn


def _mark_redeemed(token: "IOToken") -> None:
    """Host-side single-redemption guard for :class:`IOToken`.

    A token's pins are released exactly once, by its wait; redeeming the
    same token twice would re-run the drain/gather path and over-release
    the pin refcounts.  The guard lives on the *host* token object (jit
    bodies only run at trace time), so it is enforced at every eager
    ``wait`` call and by the ``wait_jit`` wrapper; tokens that are tracers
    (inside a scan/jit trace) are exempt — their lifecycle is the traced
    program's own.
    """
    if isinstance(token.valid, jax.core.Tracer):
        return
    # host-only from here on (tracers returned above); the attribute read
    # is trace-time Python, not a traced branch.
    if getattr(token, "_redeemed", False):  # bamlint: ignore[BAM104]
        raise ValueError(
            "IOToken has already been redeemed by wait(); a token must be "
            "waited exactly once (a second wait would over-release its "
            "cache pins)")
    object.__setattr__(token, "_redeemed", True)


def _guarded_wait(cache: Dict[str, Any], key: str, fn,
                  io_of=lambda tok: tok):
    """The cached host wrapper (cache key ``key + "#guard"``) of the
    jitted wait ``fn`` that enforces the single-redemption contract on the
    token's :class:`IOToken` (``io_of(tok)``; :func:`_mark_redeemed`)
    before dispatch.  Shared by every ``wait_jit(guard=True)``."""
    wkey = key + "#guard"
    w = cache.get(wkey)
    if w is None:
        def guarded(st, tok, _fn=fn):
            _mark_redeemed(io_of(tok))
            return _fn(st, tok)

        cache[wkey] = w = guarded
    return w


@dataclasses.dataclass(frozen=True)
class OpFamilyEntry:
    """One member of the jit-cached op family, as enumerated by
    :meth:`BamArray.iter_op_family` / :meth:`BamRuntime.iter_op_family`.

    This is the registry hook the lowered-artifact verifier
    (``tools/bamverify``) walks so it never hand-maintains the op list:
    adding a new ``*_jit`` op here automatically puts it under the BAM5xx
    rules and into the compiled-graph manifest.

    ``kind`` is ``"jit"`` for directly lowerable jit-cached callables
    (``get(donate=...)`` returns the cached ``jax.jit`` object,
    ``example_args(state, n)`` builds a canonical batch-``n`` argument
    tuple for ``.lower()``) or ``"bucketed"`` for the host-side
    shape-bucketing wrappers (``get()`` returns a ``(state, n) -> state``
    round driver; ``trace_keys`` names the jit-cache keys it compiles
    into, whose trace counts the BAM505 executable-count rule audits).

    ``pure_all_hit`` marks executables whose all-hit fast path must stay
    free of *unconditional* host callbacks (the ``lax.cond``-gated fetch
    and write-back contract) — the BAM503 rule only audits entries that
    claim it.
    """

    name: str
    kind: str = "jit"              # "jit" | "bucketed"
    donatable: bool = False        # accepts donate=True (separate cache key)
    pure_all_hit: bool = False     # callbacks must be cond-gated (BAM503)
    get: Any = None                # (donate=False) -> callable
    example_args: Any = None       # (state, n) -> positional arg tuple
    trace_keys: Tuple[str, ...] = ()   # jit-cache keys audited by BAM505


@dataclasses.dataclass(frozen=True)
class TenantCtx:
    """Static multi-tenant context of one :class:`BamArray`.

    ``tenant`` namespaces this array's cache tags and queue commands;
    ``[way_lo, way_hi)`` is its cache way quota (``way_hi=None`` = all
    ways).  The default is the single-tenant identity: tenant 0 with the
    whole cache.
    """

    tenant: int = 0
    way_lo: int = 0
    way_hi: int | None = None


@pytree_dataclass
class BamState:
    """All mutable BaM state, threaded functionally through reads/writes."""

    cache: C.CacheState
    queues: Q.QueueState
    metrics: IOMetrics
    storage: Any  # HBMStorage pytree for the in-graph backend, else None


@pytree_dataclass(meta_fields=("kind",))
class IORequest:
    """Unified op descriptor: read / write / prefetch share one submission
    path (:meth:`BamArray.submit`).

    ``idx`` is a wavefront of element indices; ``valid`` masks lanes
    (``None`` = in-bounds check); ``values`` carries the write payload for
    ``kind="write"``.  Build with the :meth:`read`/:meth:`write`/
    :meth:`prefetch` constructors rather than the raw dataclass.
    """

    kind: str                       # "read" | "write" | "prefetch"
    idx: jax.Array                  # (n,) element indices
    values: jax.Array | None = None  # (n,) write payload (kind="write")
    valid: jax.Array | None = None  # (n,) lane mask; None = bounds check

    @staticmethod
    def read(idx: jax.Array, valid: jax.Array | None = None) -> "IORequest":
        return IORequest(kind="read", idx=idx, valid=valid)

    @staticmethod
    def write(idx: jax.Array, values: jax.Array,
              valid: jax.Array | None = None) -> "IORequest":
        return IORequest(kind="write", idx=idx, values=values, valid=valid)

    @staticmethod
    def prefetch(idx: jax.Array,
                 valid: jax.Array | None = None) -> "IORequest":
        return IORequest(kind="prefetch", idx=idx, valid=valid)


@pytree_dataclass(meta_fields=("kind",))
class IOToken:
    """Future returned by :meth:`BamArray.submit`; redeem exactly once with
    :meth:`BamArray.wait`.

    A token is a fixed-shape pytree (it rides ``lax.scan`` carries), holding
    what completion needs: the request's lane geometry, the unique block
    keys, the cache slots pinned at submit (released at wait), the write
    payload, and the per-device command histograms for the accounting that
    is deferred to the drain.  Dropping a token without waiting it leaks
    its cache pins; waiting it twice over-releases them.
    """

    kind: str                       # mirrors the IORequest kind
    valid: jax.Array                # (n,) request lanes
    off: jax.Array                  # (n,) element offset within its line
    inverse: jax.Array              # (n,) request lane -> unique-key row
    ukeys: jax.Array                # (n,) coalesced block keys, -1 padded
    pin_slots: jax.Array            # (n,) flat slots pinned at submit (-1 none)
    values: jax.Array | None        # (n,) write payload (kind="write")
    ra_keys: jax.Array | None       # (window,) stride-readahead keys issued
    dev_reads: jax.Array            # (nd,) read commands issued (incl. dropped)
    dev_writes: jax.Array           # (nd,) write commands issued (incl. dropped)
    drop_dev_reads: jax.Array       # (nd,) read commands the rings rejected
    drop_dev_writes: jax.Array      # (nd,) write commands the rings rejected
    # Per-unique-row fault-hash ordinal of the row's own fetch command
    # (SubmitReceipt.ticket): -1 for rows that enqueued none — hits,
    # cross-op riders, ring-dropped and invalid rows.  wait() recomputes
    # the command's retry/error fate from (device, ticket) with the same
    # pure FaultModel.command_status the drain uses, so the two agree by
    # construction.
    ticket: jax.Array               # (n,) int32
    ra_ticket: jax.Array | None     # (window,) readahead command tickets
    # Back-pressure visibility (satellite: drops must not be silent): True
    # on every request lane whose unique line's command the rings rejected
    # this submit.  Dropped commands are still served read/write-through,
    # so the lane's *value* is unaffected — the mask is how callers see
    # that their configured queue depth is saturating.
    dropped_mask: jax.Array         # (n,) bool, per request lane


@dataclasses.dataclass
class BamArray:
    """Static description of one BaM-backed array (not a pytree)."""

    storage: Any                    # SimStorage (host) or None (in-graph)
    shape: tuple
    dtype: Any
    block_elems: int
    ssd: ArrayOfSSDs = dataclasses.field(
        default_factory=lambda: ArrayOfSSDs(INTEL_OPTANE_P5800X, 1))
    prefetch_cfg: PrefetchConfig = dataclasses.field(
        default_factory=PrefetchConfig)
    tenant_ctx: TenantCtx = dataclasses.field(default_factory=TenantCtx)
    # Deferred drain (multi-tenant): when True, ops enqueue commands but do
    # NOT drain the rings; the runtime drains once per round
    # (BamRuntime.drain), so several tenants' commands genuinely coexist
    # and the weighted-fair arbitration orders a real mixed stream.
    defer_drain: bool = False
    # Kernel dispatch policy for the probe / fused probe+allocate / gather
    # hot path: "auto" (the static rule of repro.kernels.ops.resolve_impl),
    # "pallas", or "ref" — threaded to repro.kernels.ops on every op.
    kernel_impl: str = "auto"
    # Fully traced I/O rounds (default): submit's multi-segment SQ enqueue
    # runs as one fused pass (queues.enqueue_segments), wait's ring drain
    # as closed-form accounting (queues.drain_accounting), the cache
    # bookkeeping as single-pass rebuilds, and a warm-cache wait elides
    # the host fetch DMA behind lax.cond.  False = the legacy step-by-step
    # path, kept as the differential oracle (tests pin both bit-identical).
    fused_rounds: bool = True
    # Shape buckets for submit_bucketed/wait_bucketed (ascending).
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    # Per-instance jit cache for the op family (read/write/submit/wait/…)
    # plus the trace-count probe the retrace-regression tests read.  Both
    # are identity-bound to this instance's static config — `with_prefetch`
    # resets them on the copy.
    _jit_ops: Dict[str, Any] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _trace_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    # ---------------------------------------------------------------- init
    @staticmethod
    def build(data, block_elems: int, *,
              num_sets: int, ways: int = 4,
              num_queues: int = 8, queue_depth: int = 1024,
              ssd: Optional[ArrayOfSSDs] = None,
              prefetch: Optional[PrefetchConfig] = None,
              backend: str = "sim",
              kernel_impl: str = "auto") -> Tuple["BamArray", BamState]:
        """Create the array + its initial state from a host/jnp array.

        ``backend='sim'``: data lives on the host, fetched via pure_callback
        (the NVMe DMA stand-in).  ``backend='hbm'``: data is an in-graph cold
        buffer — used by dry-runs so the compiler sees the traffic.

        The SQ pool is partitioned per storage device (``ssd.n_devices``
        equal ring groups); ``num_queues`` is rounded up to the next
        multiple of the device count so every channel gets the same depth.

        ``kernel_impl`` picks the hot-path kernels (probe, fused
        probe+allocate, line gather): ``"auto"`` picks per kernel by the
        static rule of :func:`repro.kernels.ops.resolve_impl` (Pallas for
        the probe and the gather on TPU, the bit-identical jnp oracles
        elsewhere); ``"pallas"``/``"ref"`` pin one side (tests
        pin ``"pallas"`` with interpret mode for the differential sweeps).
        """
        import numpy as np
        shape = tuple(data.shape)
        if backend == "sim":
            store = SimStorage.from_array(np.asarray(data), block_elems)
            state_store = None
            dtype = store.dtype
        elif backend == "hbm":
            hs = HBMStorage.from_array(jnp.asarray(data), block_elems)
            store, state_store, dtype = None, hs, hs.dtype
        else:
            raise ValueError(f"unknown backend {backend!r}")
        if kernel_impl not in ("auto", "pallas", "ref"):
            raise ValueError(
                f"kernel_impl must be 'auto', 'pallas' or 'ref', "
                f"got {kernel_impl!r}")
        ssd = ssd or ArrayOfSSDs(INTEL_OPTANE_P5800X, 1)
        num_queues = round_up(num_queues, ssd.n_devices)
        arr = BamArray(
            storage=store, shape=shape, dtype=dtype, block_elems=block_elems,
            ssd=ssd,
            prefetch_cfg=prefetch or PrefetchConfig(),
            kernel_impl=kernel_impl)
        st = BamState(
            cache=C.make_cache(num_sets, ways, block_elems, dtype),
            queues=Q.make_queues(num_queues, queue_depth,
                                 n_devices=ssd.n_devices,
                                 stripe_blocks=ssd.stripe_blocks,
                                 failed_devices=ssd.fault.failed_devices),
            metrics=IOMetrics.zeros(ssd.n_devices),
            storage=state_store,
        )
        return arr, st

    # ------------------------------------------------------------- helpers
    @property
    def block_bytes(self) -> int:
        return self.block_elems * jnp.dtype(self.dtype).itemsize

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n

    @property
    def num_blocks(self) -> int:
        return -(-self.size // self.block_elems)

    def with_prefetch(self, cfg: PrefetchConfig) -> "BamArray":
        """Same array, different (static) readahead policy.

        The jit-op cache is reset on the copy: its cached callables close
        over the *original* instance's static config.
        """
        return dataclasses.replace(self, prefetch_cfg=cfg,
                                   _jit_ops={}, _trace_counts={})

    # ------------------------------------------------- jit-cached op family
    def _jit_op(self, name: str, make, donate_argnums=()):
        """See :func:`_cached_jit` (the shared cache + retrace probe)."""
        return _cached_jit(self._jit_ops, self._trace_counts, name, make,
                           donate_argnums=donate_argnums)

    @property
    def trace_counts(self) -> Dict[str, int]:
        """How many times each jit-cached op has been traced (not called)."""
        return dict(self._trace_counts)

    def read_jit(self):
        """Cached ``jax.jit`` of :meth:`read` — grab it every wavefront,
        it compiles once per distinct index shape."""
        return self._jit_op(
            "read", lambda: lambda st, idx, valid=None:
                self.read(st, idx, valid))

    def write_jit(self):
        return self._jit_op(
            "write", lambda: lambda st, idx, values, valid=None:
                self.write(st, idx, values, valid))

    def prefetch_jit(self):
        return self._jit_op(
            "prefetch", lambda: lambda st, idx, valid=None:
                self.prefetch(st, idx, valid))

    def submit_jit(self, *, donate: bool = False):
        """Cached ``jax.jit`` of :meth:`submit` ``(st, req) -> (st, tok)``
        — the token API's steady-state entry point.  ``IORequest.kind`` is
        pytree metadata, so read/write/prefetch submissions share the one
        cached callable and key their compilations by request structure.

        ``donate=True`` donates the state argument's buffers to the output
        (a separate cache key — the non-donating executables are
        unaffected): steady-state rounds then update ``CacheState`` /
        ``QueueState`` in place instead of copying.  The caller must not
        use the passed-in state again (JAX raises ``Array has been
        deleted``; bamlint BAM106 flags the pattern statically)."""
        key = "submit[donated]" if donate else "submit"
        return self._jit_op(
            key, lambda: lambda st, req: self.submit(st, req),
            donate_argnums=(0,) if donate else ())

    def wait_jit(self, *, donate: bool = False, guard: bool = True):
        """Cached ``jax.jit`` of :meth:`wait` ``(st, tok) -> (st, vals)``.

        ``donate`` as in :meth:`submit_jit`.  ``guard=True`` (default)
        returns a cached wrapper enforcing the single-redemption contract
        on the host token before dispatch (see :func:`_mark_redeemed`);
        ``guard=False`` is for callers that deliberately replay a wait
        (benchmark timing loops re-time one wait against copies of the
        same pre-wait state)."""
        key = "wait[donated]" if donate else "wait"
        fn = self._jit_op(
            key, lambda: lambda st, tok: self.wait(st, tok),
            donate_argnums=(0,) if donate else ())
        return _guarded_wait(self._jit_ops, key, fn) if guard else fn

    def submit_wait_jit(self, *, donate: bool = False):
        """Cached ``jax.jit`` of a whole submit → wait round
        ``(st, req) -> (st, vals)`` as ONE executable.

        The async pair exists to *overlap* outstanding tokens; a caller
        that redeems immediately (the synchronous round-trip) would pay a
        second dispatch plus a full state flatten/unflatten between the
        two ops for a token that never outlives the call.  Fusing the
        pair runs the same two passes back to back inside one executable
        — same values, same metrics, bit-identical to
        :meth:`submit_jit` + :meth:`wait_jit` (the differential oracle
        pins it) — and the intermediate token never materialises on the
        host.  ``donate`` as in :meth:`submit_jit`."""
        key = "submit_wait[donated]" if donate else "submit_wait"

        def make():
            def op(st, req):
                st, tok = self.submit(st, req)
                return self.wait(st, tok)
            return op

        return self._jit_op(key, make,
                            donate_argnums=(0,) if donate else ())

    # --------------------------------------------- bucketed wavefronts
    def bucket_size(self, n: int) -> int:
        """Smallest configured bucket >= ``n`` (overflow: next multiple of
        the largest bucket, so giant wavefronts still reuse executables)."""
        for b in self.buckets:
            if n <= b:
                return int(b)
        return round_up(n, int(self.buckets[-1]))

    def submit_bucketed(self, st: BamState, req: IORequest, *,
                        donate: bool = False) -> Tuple[BamState, IOToken]:
        """Submit through the jit cache with the wavefront padded up to a
        bucket size, so a ragged sweep of batch sizes compiles at most
        ``len(self.buckets)`` submit executables instead of one per size.

        Padded lanes are inert by construction (``idx=-1, valid=False``):
        the coalescer drops them, they probe no tags, pin no slots, take
        no ring slots and move no metric — the retrace-regression tests
        pin bucketed execution bit-identical to unbucketed.  Zero-length
        batches short-circuit *before* padding (no size-0 executable).
        ``donate`` forwards to :meth:`submit_jit`.
        """
        n = int(req.idx.shape[0])
        if n == 0:
            return self.submit(st, req)     # eager no-op, nothing traced
        m = self.bucket_size(n)
        # Always materialise the lane mask: a request with valid=None has a
        # different pytree structure than a padded one, and structure keys
        # the jit cache — one treedef per bucket, not two.
        valid = req.valid
        if valid is None:
            valid = (req.idx >= 0) & (req.idx < self.size)
        values = None
        if req.values is not None:
            values = pad_to(req.values, m, 0)
        req = IORequest(kind=req.kind, idx=pad_to(req.idx, m, -1),
                        values=values, valid=pad_to(valid, m, False))
        st2, tok = self.submit_jit(donate=donate)(st, req)
        # host-side bookkeeping (not a pytree leaf): wait_bucketed slices
        # the values back to the caller's length
        object.__setattr__(tok, "_orig_len", n)
        return st2, tok

    def wait_bucketed(self, st: BamState, token: IOToken, *,
                      donate: bool = False) -> Tuple[BamState, jax.Array]:
        """Redeem a :meth:`submit_bucketed` token, slicing the values back
        to the original (pre-padding) wavefront length."""
        if token.ukeys.shape[0] == 0:
            return self.wait(st, token)     # eager no-op + redeem guard
        st2, vals = self.wait_jit(donate=donate)(st, token)
        n = getattr(token, "_orig_len", None)
        if n is not None:
            vals = vals[:n]
        return st2, vals

    # ------------------------------------------------- op-family registry
    def _example_wavefront(self, n: int):
        """Canonical batch-``n`` index wavefront for artifact lowering:
        strided so it spans multiple cache sets, with the lane mask always
        materialised (the same pytree structure the bucketed wrappers
        produce, so lowered artifacts and bucketed traffic share
        executables)."""
        idx = (jnp.arange(n, dtype=jnp.int32) * 7) % self.size
        return idx, idx < self.size

    def _bucketed_round(self, st: BamState, n: int,
                        donate: bool = False) -> BamState:
        """One bucketed submit+wait round at raw batch ``n`` (the BAM505
        sweep driver: ragged ``n`` must reuse at most one executable per
        configured bucket)."""
        idx, valid = self._example_wavefront(n)
        st, tok = self.submit_bucketed(st, IORequest.read(idx, valid),
                                       donate=donate)
        st, _ = self.wait_bucketed(st, tok, donate=donate)
        return st

    def iter_op_family(self):
        """Enumerate the jit-cached op family (see :class:`OpFamilyEntry`).

        This is the registry ``tools/bamverify`` lowers and lints: every
        steady-state executable this array can produce is listed here —
        the synchronous shims, the token ops with their donated variants,
        the fused whole-round op, and the shape-bucketed drivers.  Keep it
        in sync with the ``*_jit`` surface; the verifier's tests assert
        the family covers the jit cache keys actually used.
        """
        def args_read(st, n):
            idx, valid = self._example_wavefront(n)
            return (st, idx, valid)

        def args_write(st, n):
            idx, valid = self._example_wavefront(n)
            return (st, idx, jnp.ones((n,), self.dtype), valid)

        def args_req(st, n):
            idx, valid = self._example_wavefront(n)
            return (st, IORequest.read(idx, valid))

        def args_token(st, n):
            idx, valid = self._example_wavefront(n)
            st1, tok = self.submit(st, IORequest.read(idx, valid))
            return (st1, tok)

        yield OpFamilyEntry(
            name="read", get=lambda donate=False: self.read_jit(),
            example_args=args_read, trace_keys=("read",))
        yield OpFamilyEntry(
            name="write", get=lambda donate=False: self.write_jit(),
            example_args=args_write, trace_keys=("write",))
        yield OpFamilyEntry(
            name="prefetch", get=lambda donate=False: self.prefetch_jit(),
            example_args=args_read, trace_keys=("prefetch",))
        # submit's dirty write-back and wait's fetch DMA are lax.cond-gated:
        # a round that evicts nothing dirty, or misses nothing, must never
        # pay the host callback, so their executables claim pure_all_hit
        # and BAM503 audits callback placement in them.
        yield OpFamilyEntry(
            name="submit", donatable=True, pure_all_hit=True,
            get=lambda donate=False: self.submit_jit(donate=donate),
            example_args=args_req, trace_keys=("submit",))
        yield OpFamilyEntry(
            name="wait", donatable=True, pure_all_hit=True,
            get=lambda donate=False: self.wait_jit(donate=donate,
                                                   guard=False),
            example_args=args_token, trace_keys=("wait",))
        yield OpFamilyEntry(
            name="submit_wait", donatable=True,
            get=lambda donate=False: self.submit_wait_jit(donate=donate),
            example_args=args_req, trace_keys=("submit_wait",))
        yield OpFamilyEntry(
            name="bucketed_round", kind="bucketed",
            get=lambda donate=False:
                lambda st, n: self._bucketed_round(st, n, donate),
            trace_keys=("submit", "wait"))

    def _store(self, st: BamState):
        return self.storage if self.storage is not None else st.storage

    def _check_channels(self, st: BamState) -> None:
        """SQ routing and device accounting must share one striping.

        Both are static metadata, so a hand-assembled state that pairs
        mismatched ``make_queues``/``ArrayOfSSDs`` channel configs fails at
        trace time instead of silently charging metrics to the wrong
        device.
        """
        qs = st.queues
        if (qs.n_devices, qs.stripe_blocks) != (self.ssd.n_devices,
                                                self.ssd.stripe_blocks):
            raise ValueError(
                f"queue channels (n_devices={qs.n_devices}, "
                f"stripe_blocks={qs.stripe_blocks}) do not match the SSD "
                f"array (n_devices={self.ssd.n_devices}, "
                f"stripe_blocks={self.ssd.stripe_blocks}); build the state "
                "with BamArray.build or make_queues with the same config")
        # bamlint: ignore[BAM104] -- both sides are static host tuples
        if qs.failed_devices != self.ssd.fault.failed_devices:
            raise ValueError(
                f"queue failed_devices {qs.failed_devices} do not match "
                f"the SSD fault model's {self.ssd.fault.failed_devices}: "
                "SQ routing and the device-time charge would remap dead "
                "stripes differently; build the state with BamArray.build "
                "or pass the same failed_devices to make_queues")

    def _split(self, idx: jax.Array):
        return (idx // self.block_elems).astype(jnp.int32), \
               (idx % self.block_elems).astype(jnp.int32)

    # ----------------------------------------------------------- async core
    def submit(self, st: BamState, req: IORequest
               ) -> Tuple[BamState, IOToken]:
        """Issue a wavefront of storage commands without draining them.

        The submission half of every op (read/write/prefetch — one path):

            coalesce -> probe -> pin -> allocate (+mark in-flight)
                     -> write back evicted dirty lines -> enqueue SQ commands

        No DMA fetch, no ring drain, no device-time charge happens here;
        those belong to :meth:`wait`.  Multiple tokens may be outstanding at
        once: every line this op touched (hit or newly granted) is pinned
        until its wait, granted-but-unfilled lines carry the cache's
        ``inflight`` bit, and a later submission that probes a key another
        pending token is already fetching *coalesces* against it (counted in
        ``cross_op_coalesced``) instead of enqueuing a duplicate command —
        the submission-window coalescer working across ops, not just within
        one wavefront.
        """
        self._check_channels(st)
        kind = req.kind
        if kind not in ("read", "write", "prefetch"):
            raise ValueError(f"unknown IORequest kind {kind!r}")
        if kind == "write" and req.values is None:
            raise ValueError("IORequest(kind='write') needs values")
        if req.idx.shape[0] == 0:
            return self._submit_empty(st, req)
        with jax.named_scope("coalesce"):
            idx = req.idx
            valid = req.valid
            if valid is None:
                valid = (idx >= 0) & (idx < self.size)
            blk, off = self._split(jnp.where(valid, idx, 0))
            blk = jnp.where(valid, blk, -1)

            # 1) warp-coalesce the wavefront to unique cache lines.
            co = coalesce(blk, valid)
            ukeys = co.unique_keys                      # (n,) padded with -1
            uvalid = ukeys >= 0
            ctx = self.tenant_ctx
            nd = self.ssd.n_devices
            sb = self.ssd.stripe_blocks
            fd = self.ssd.fault.failed_devices
            mt = st.metrics
        if kind == "prefetch":
            return self._submit_prefetch(st, co, off, valid)

        # 2+3) fused probe + victim allocate: ONE kernel pass
        #    (repro.kernels.ops.probe_allocate, the jnp oracle under
        #    impl="auto") probes the tags and grants a victim slot per miss.
        #    This round's hits are protected in-pass; lines pinned by
        #    other outstanding tokens are refcount-protected.
        with jax.named_scope("probe_allocate"):
            cache2, pr, alloc = C.probe_allocate(
                st.cache, ukeys, uvalid, tenant=ctx.tenant,
                way_lo=ctx.way_lo, way_hi=ctx.way_hi, impl=self.kernel_impl)

            #    Demand probe accounting.  A hit on a prefetched line
            #    promotes it; a hit on an *in-flight* line is a cross-op
            #    coalesce — some pending token already has the fetch in the
            #    rings, so this op rides that command instead of issuing its
            #    own.  (Hit slots and granted slots are disjoint, so
            #    promoting after the allocation scatters is bit-identical to
            #    promoting before them.)
            n_hit = jnp.sum(pr.hit.astype(jnp.int32))
            n_pref_hit = jnp.sum(pr.speculative.astype(jnp.int32))
            n_cross = jnp.sum(pr.inflight.astype(jnp.int32))
            miss = uvalid & ~pr.hit

            # 3b) pin everything this token touched until its wait, and mark
            #     granted (not-yet-filled) lines in flight.  The four
            #     bookkeeping steps (hit count, promote, pin, in-flight)
            #     touch disjoint cache fields, so the fused path folds them
            #     into one CacheState rebuild — bit-identical to the
            #     sequential helpers.
            pin_slots = jnp.where(pr.hit, pr.slot,
                                  jnp.where(alloc.ok, alloc.slot, -1))
            grant_slots = jnp.where(alloc.ok, alloc.slot, -1)
            promote_slots = jnp.where(pr.speculative, pr.slot, -1)
            if self.fused_rounds:
                cache2 = C.grant_bookkeeping(cache2, n_hit, promote_slots,
                                             pin_slots, grant_slots)
            else:
                cache2 = C.count_hits(cache2, n_hit)
                cache2 = C.promote(cache2, promote_slots)
                cache2 = C.acquire(cache2, pin_slots)
                cache2 = C.mark_inflight(cache2, grant_slots)

        # 4) evicted dirty lines -> write-back commands + immediate DMA
        #    (the line leaves the cache now, so its bytes must be persisted
        #    now; only the *fetch* side of the op is deferred to wait()).
        with jax.named_scope("write_back"):
            wb = alloc.ok & alloc.evicted_dirty & (alloc.evicted_key >= 0)
            wb_keys = jnp.where(wb, alloc.evicted_key, -1)

        # 4b) readahead (read ops): extrapolate the wavefront's stride and
        #     speculatively claim the predicted lines — enqueued in the
        #     low-priority lane, fetched at wait.
        with jax.named_scope("readahead"):
            cfg = self.prefetch_cfg
            ra_on = kind == "read" and cfg.enabled and cfg.window > 0
            ra_keys_tok = None
            if ra_on:
                ra_cand = readahead_keys(
                    ukeys, uvalid, window=cfg.window,
                    num_blocks=self.num_blocks,
                    min_support=cfg.min_support, max_stride=cfg.max_stride,
                    raw_keys=blk, raw_valid=valid)
                # Never speculatively re-fetch a line this wavefront just
                # evicted: on the sim backend the fetch (pure_callback) is not
                # ordered against the dirty write-back (io_callback), so it
                # could observe the pre-write-back bytes — and re-fetching a
                # just-evicted line is pure thrash regardless of backend.
                evk = jnp.where(alloc.ok & (alloc.evicted_key >= 0),
                                alloc.evicted_key, -2)
                not_evicted = ~jnp.any(
                    ra_cand[:, None] == evk[None, :], axis=1)
                # Fused probe + speculative allocate for the predicted lines
                # (probe hits are NOT protected here — only the demand
                # wavefront's hit and granted slots are, as before).
                cache2, _, ra_alloc = C.probe_allocate(
                    cache2, ra_cand, ra_cand >= 0, alloc_mask=not_evicted,
                    protect_slots=jnp.concatenate([pr.slot, alloc.slot]),
                    protect_hits=False, speculative=True,
                    tenant=ctx.tenant, way_lo=ctx.way_lo, way_hi=ctx.way_hi,
                    impl=self.kernel_impl)
                ra_keys = jnp.where(ra_alloc.ok, ra_cand, -1)
                ra_wb = ra_alloc.ok & ra_alloc.evicted_dirty \
                    & (ra_alloc.evicted_key >= 0)
                ra_wb_keys = jnp.where(ra_wb, ra_alloc.evicted_key, -1)
                cache2 = C.mark_inflight(
                    cache2, jnp.where(ra_alloc.ok, ra_alloc.slot, -1))
                ra_keys_tok = ra_keys

        # 5) enqueue reads + write-backs into the SQ rings; ring doorbells.
        #    Readahead goes last and in the low-priority lane: it is the
        #    first thing dropped under back-pressure and the last retired.
        #    The rings are NOT drained here — that is wait()'s job, so
        #    commands from several outstanding tokens genuinely coexist and
        #    the queues fill toward the Little's-law depth.
        with jax.named_scope("enqueue"):
            read_keys = jnp.where(miss, ukeys, -1)
            segs = [(read_keys, alloc.slot, None, None, Q.PRIO_DEMAND),
                    (wb_keys, None, jnp.ones_like(wb), None, Q.PRIO_DEMAND)]
            if kind == "write":
                # Bypassed lines (no slot granted) are written through at wait;
                # their commands ride the rings like every other write.
                byp = miss & ~alloc.ok
                bt_keys = jnp.where(byp, ukeys, -1)
                segs.append((bt_keys, None, jnp.ones_like(byp), None,
                             Q.PRIO_DEMAND))
            if ra_on:
                segs.append((ra_wb_keys, None, jnp.ones_like(ra_wb), None,
                             Q.PRIO_DEMAND))
                segs.append((ra_keys, ra_alloc.slot, None, None,
                             Q.PRIO_READAHEAD))
            if self.fused_rounds:
                # one fused pass: one combined scatter per SQ ring field, one
                # QueueState rebuild (bit-identical to the sequential enqueues
                # — the differential oracle pins it)
                qs2, recs = Q.enqueue_segments(st.queues, segs,
                                               tenant=ctx.tenant,
                                               impl=self.kernel_impl)
            else:
                qs2, recs = st.queues, []
                # static unroll: segs has trace-time-constant length (2-4)
                for keys_s, dst_s, w_s, v_s, p_s in segs:  # bamlint: ignore[BAM104]
                    qs2, rec = Q.enqueue(qs2, keys_s, dst=dst_s,
                                         is_write=w_s, valid=v_s, prio=p_s,
                                         tenant=ctx.tenant)
                    recs.append(rec)
            it = iter(recs)
            rec_r, rec_w = next(it), next(it)
            n_doorbells = rec_r.n_doorbells + rec_w.n_doorbells
            n_dropped = rec_r.n_dropped + rec_w.n_dropped
            dev_reads_tok = device_histogram(ukeys, nd, miss, sb, fd)
            dev_writes_tok = device_histogram(wb_keys, nd, stripe_blocks=sb,
                                              failed_devices=fd)
            drop_reads = device_histogram(read_keys, nd, ~rec_r.accepted,
                                          sb, fd)
            drop_writes = device_histogram(wb_keys, nd, ~rec_w.accepted,
                                           sb, fd)
            # Per-unique-row command tickets (the fault-hash counter) and the
            # rows whose demand command the rings rejected: both feed the token
            # so wait() can resolve completion status and callers can see
            # back-pressure drops per lane instead of only as a global count.
            ticket_tok = rec_r.ticket
            drop_u = miss & ~rec_r.accepted
            if kind == "write":
                rec_bt = next(it)
                n_doorbells = n_doorbells + rec_bt.n_doorbells
                n_dropped = n_dropped + rec_bt.n_dropped
                dev_writes_tok = dev_writes_tok + device_histogram(
                    bt_keys, nd, stripe_blocks=sb, failed_devices=fd)
                drop_writes = drop_writes + device_histogram(
                    bt_keys, nd, ~rec_bt.accepted, sb, fd)
                drop_u = drop_u | (byp & ~rec_bt.accepted)
            ra_ticket_tok = None
            if ra_on:
                rec_rw, rec_ra = next(it), next(it)
                n_doorbells = (n_doorbells + rec_rw.n_doorbells
                               + rec_ra.n_doorbells)
                n_dropped = n_dropped + rec_rw.n_dropped + rec_ra.n_dropped
                dev_reads_tok = dev_reads_tok + device_histogram(
                    ra_keys, nd, stripe_blocks=sb, failed_devices=fd)
                dev_writes_tok = dev_writes_tok + device_histogram(
                    ra_wb_keys, nd, stripe_blocks=sb, failed_devices=fd)
                drop_reads = drop_reads + device_histogram(
                    ra_keys, nd, ~rec_ra.accepted, sb, fd)
                drop_writes = drop_writes + device_histogram(
                    ra_wb_keys, nd, ~rec_rw.accepted, sb, fd)
                ra_ticket_tok = rec_ra.ticket
            depth_now = Q.in_flight(qs2)
            depth_dev = Q.in_flight_per_device(qs2)

        # 6) persist evicted dirty lines (write DMA happens at submit; the
        #    fetch DMA is deferred to wait).  The grants above moved no
        #    line's bytes, so the evicted lines are still in ``cache2``.
        with jax.named_scope("write_back"):
            new_storage = self._write_back_gated(
                st.storage, cache2.data, alloc.slot, wb_keys, wb)
            if ra_on:
                new_storage = self._write_back_gated(
                    new_storage, cache2.data, ra_alloc.slot, ra_wb_keys, ra_wb)

        # 7) submission-side metrics.  Device busy time, bytes fetched and
        #    the per-device charge histograms are wait-side (they belong to
        #    the drain); everything the submission itself decides is here.
        with jax.named_scope("accounting"):
            n_valid = jnp.sum(valid.astype(jnp.int32))
            n_miss = jnp.sum(miss.astype(jnp.int32))
            n_wb = jnp.sum(wb.astype(jnp.int32))
            n_ra = jnp.zeros((), jnp.int32)
            if ra_on:
                n_ra = jnp.sum(ra_alloc.ok.astype(jnp.int32))
                n_wb = n_wb + jnp.sum(ra_wb.astype(jnp.int32))
            if kind == "write":
                n_wb = n_wb + jnp.sum(byp.astype(jnp.int32))
            itemsize = jnp.dtype(self.dtype).itemsize
            tok_new = jnp.any(valid).astype(mt.requests.dtype)
            window_now = (mt.tokens_in_flight + tok_new).astype(jnp.int32)
            metrics = dataclasses.replace(
                mt,
                requests=mt.requests + n_valid,
                bytes_requested=mt.bytes_requested + n_valid * itemsize,
                hits=mt.hits + n_hit,
                misses=mt.misses + n_miss,
                write_ops=mt.write_ops + n_wb,
                bytes_to_storage=mt.bytes_to_storage + n_wb * self.block_bytes,
                doorbells=mt.doorbells + n_doorbells,
                dropped=mt.dropped + n_dropped,
                prefetch_issued=mt.prefetch_issued + n_ra,
                prefetch_hits=mt.prefetch_hits + n_pref_hit,
                max_queue_depth=jnp.maximum(mt.max_queue_depth,
                                            depth_now.astype(jnp.int32)),
                dev_max_depth=jnp.maximum(mt.dev_max_depth,
                                          depth_dev.astype(jnp.int32)),
                tokens_submitted=mt.tokens_submitted + tok_new,
                tokens_in_flight=mt.tokens_in_flight + tok_new,
                cross_op_coalesced=mt.cross_op_coalesced + n_cross,
                max_tokens_in_flight=jnp.maximum(mt.max_tokens_in_flight,
                                                 window_now),
            )
            token = IOToken(
                kind=kind, valid=valid, off=off, inverse=co.inverse_idx,
                ukeys=ukeys, pin_slots=pin_slots,
                values=req.values if kind == "write" else None,
                ra_keys=ra_keys_tok,
                dev_reads=dev_reads_tok, dev_writes=dev_writes_tok,
                drop_dev_reads=drop_reads, drop_dev_writes=drop_writes,
                ticket=ticket_tok, ra_ticket=ra_ticket_tok,
                dropped_mask=valid & drop_u[co.inverse_idx])
        return BamState(cache=cache2, queues=qs2, metrics=metrics,
                        storage=new_storage), token

    def _submit_empty(self, st: BamState, req: IORequest
                      ) -> Tuple[BamState, IOToken]:
        """Zero-length wavefront (an exhausted BFS frontier, a drained
        producer): no commands, no cache traffic, no metrics — the state
        passes through untouched and a zero-shaped token keeps the
        submit/wait pairing uniform.  Guarded *before* any tracing or
        bucket padding so no degenerate size-0 executable is ever built.
        """
        nd = self.ssd.n_devices
        z = jnp.zeros((0,), jnp.int32)
        zh = jnp.zeros((nd,), jnp.int32)
        token = IOToken(
            kind=req.kind, valid=jnp.zeros((0,), bool), off=z, inverse=z,
            ukeys=jnp.full((0,), -1, jnp.int32),
            pin_slots=jnp.full((0,), -1, jnp.int32),
            values=req.values if req.kind == "write" else None,
            ra_keys=None, dev_reads=zh, dev_writes=zh,
            drop_dev_reads=zh, drop_dev_writes=zh,
            ticket=jnp.full((0,), -1, jnp.int32), ra_ticket=None,
            dropped_mask=jnp.zeros((0,), bool))
        return st, token

    def _submit_prefetch(self, st: BamState, co, off, valid
                         ) -> Tuple[BamState, IOToken]:
        """Prefetch submission: speculative insert-without-pin through the
        readahead lane.  Unlike demand ops the granted lines are *not*
        pinned (a hint that never materialises stays the clock hand's first
        victim), but they do carry the in-flight bit so demand submissions
        coalesce against them instead of double-fetching."""
        ctx = self.tenant_ctx
        nd = self.ssd.n_devices
        sb = self.ssd.stripe_blocks
        fd = self.ssd.fault.failed_devices
        mt = st.metrics
        ukeys = co.unique_keys
        uvalid = ukeys >= 0
        # Fused probe + speculative allocate (probe hits protected, as
        # before).  A hint landing on a line some pending token is already
        # fetching is a cross-op coalesce too: nothing to claim, nothing
        # to enqueue.
        with jax.named_scope("probe_allocate"):
            cache1, pr, alloc = C.probe_allocate(
                st.cache, ukeys, uvalid, speculative=True, tenant=ctx.tenant,
                way_lo=ctx.way_lo, way_hi=ctx.way_hi, impl=self.kernel_impl)
            n_cross = jnp.sum(pr.inflight.astype(jnp.int32))
            wb = alloc.ok & alloc.evicted_dirty & (alloc.evicted_key >= 0)
            wb_keys = jnp.where(wb, alloc.evicted_key, -1)
            keys = jnp.where(alloc.ok, ukeys, -1)
            cache1 = C.mark_inflight(cache1,
                                     jnp.where(alloc.ok, alloc.slot, -1))

        with jax.named_scope("enqueue"):
            segs = [(wb_keys, None, jnp.ones_like(wb), None, Q.PRIO_DEMAND),
                    (keys, alloc.slot, None, None, Q.PRIO_READAHEAD)]
            if self.fused_rounds:
                qs2, (rec_w, rec_r) = Q.enqueue_segments(
                    st.queues, segs, tenant=ctx.tenant, impl=self.kernel_impl)
            else:
                qs2, rec_w = Q.enqueue(st.queues, wb_keys,
                                       is_write=jnp.ones_like(wb),
                                       tenant=ctx.tenant)
                qs2, rec_r = Q.enqueue(qs2, keys, dst=alloc.slot,
                                       prio=Q.PRIO_READAHEAD,
                                       tenant=ctx.tenant)
            depth_now = Q.in_flight(qs2)
            depth_dev = Q.in_flight_per_device(qs2)

        with jax.named_scope("write_back"):
            new_storage = self._write_back_gated(
                st.storage, cache1.data, alloc.slot, wb_keys, wb)

        with jax.named_scope("accounting"):
            n_ra = jnp.sum(alloc.ok.astype(jnp.int32))
            n_wb = jnp.sum(wb.astype(jnp.int32))
            dev_reads_tok = device_histogram(keys, nd, stripe_blocks=sb,
                                             failed_devices=fd)
            dev_writes_tok = device_histogram(wb_keys, nd, stripe_blocks=sb,
                                              failed_devices=fd)
            drop_reads = device_histogram(keys, nd, ~rec_r.accepted, sb, fd)
            drop_writes = device_histogram(wb_keys, nd, ~rec_w.accepted,
                                           sb, fd)
            tok_new = jnp.any(valid).astype(mt.requests.dtype)
            window_now = (mt.tokens_in_flight + tok_new).astype(jnp.int32)
            metrics = dataclasses.replace(
                mt,
                write_ops=mt.write_ops + n_wb,
                bytes_to_storage=mt.bytes_to_storage + n_wb * self.block_bytes,
                doorbells=mt.doorbells + rec_r.n_doorbells + rec_w.n_doorbells,
                dropped=mt.dropped + rec_r.n_dropped + rec_w.n_dropped,
                prefetch_issued=mt.prefetch_issued + n_ra,
                max_queue_depth=jnp.maximum(mt.max_queue_depth,
                                            depth_now.astype(jnp.int32)),
                dev_max_depth=jnp.maximum(mt.dev_max_depth,
                                          depth_dev.astype(jnp.int32)),
                tokens_submitted=mt.tokens_submitted + tok_new,
                tokens_in_flight=mt.tokens_in_flight + tok_new,
                cross_op_coalesced=mt.cross_op_coalesced + n_cross,
                max_tokens_in_flight=jnp.maximum(mt.max_tokens_in_flight,
                                                 window_now),
            )
            token = IOToken(
                kind="prefetch", valid=valid, off=off, inverse=co.inverse_idx,
                ukeys=ukeys, pin_slots=jnp.full_like(ukeys, -1),
                values=None, ra_keys=None,
                dev_reads=dev_reads_tok, dev_writes=dev_writes_tok,
                drop_dev_reads=drop_reads, drop_dev_writes=drop_writes,
                ticket=rec_r.ticket, ra_ticket=None,
                dropped_mask=valid & (alloc.ok
                                      & ~rec_r.accepted)[co.inverse_idx])
        return BamState(cache=cache1, queues=qs2, metrics=metrics,
                        storage=new_storage), token

    def _fetch_gated(self, store, keys: jax.Array,
                     need: jax.Array) -> jax.Array:
        """Fetch ``keys`` (``-1`` lanes skipped), eliding the host DMA
        round-trip entirely when *no* lane needs one.

        ``SimStorage.fetch_blocks`` of an all-``-1`` wavefront returns
        zeros, so the skip branch is lane-wise value-identical to the
        fetch — and ``lax.cond`` executes exactly one branch at runtime,
        so a warm-cache wait never pays the ``pure_callback`` host
        round-trip.  Only the sim backend is gated: the HBM backend's
        fetch is an in-graph gather with nothing to elide.  The dirty
        write-back is gated the same way (:meth:`_write_back_gated`).
        """
        if not (self.fused_rounds and isinstance(store, SimStorage)):
            return store.fetch_blocks(keys)
        zeros = lambda k: jnp.zeros((k.shape[0], self.block_elems),
                                    store.dtype)
        return jax.lax.cond(jnp.any(need), store.fetch_blocks, zeros, keys)

    def _write_back_gated(self, storage, data: jax.Array, slots: jax.Array,
                          keys: jax.Array, need: jax.Array):
        """Persist the dirty lines a submission evicted: ``data[slots]``
        under ``keys`` on the lanes where ``need`` (``keys`` is ``-1``
        elsewhere).  ``storage`` is the state's in-graph store (``None``
        on the sim backend); returns it as the write leaves it.

        Only the evicted lanes' bytes reach storage (a ``-1`` key is
        dropped), so the line gather is masked by ``need`` and skipped
        when no lane needs it.  On the fused sim path the gather *and*
        the host write callback sit behind one ``lax.cond``, so a round
        that evicted nothing dirty (all read-only traffic) makes no host
        round trip.  JAX threads the ordered ``io_callback``'s token
        through the conditional, so the writes that do run stay ordered.
        The legacy path and the in-graph backend write every round.
        """
        any_need = jnp.any(need)
        gather = lambda: data[jnp.where(need, slots, 0)]
        if self.fused_rounds and isinstance(self.storage, SimStorage):
            jax.lax.cond(any_need,
                         lambda: self.storage.write_blocks(keys, gather()),
                         lambda: jnp.zeros((), jnp.int32))
            return storage
        lines = jax.lax.cond(
            any_need, gather,
            lambda: jnp.zeros((keys.shape[0], data.shape[1]), data.dtype))
        if self.storage is None:                        # in-graph backend
            return storage.write_blocks(keys, lines)
        self.storage.write_blocks(keys, lines)
        return storage

    def wait(self, st: BamState, token: IOToken
             ) -> Tuple[BamState, jax.Array]:
        """Complete a pending token: drain, fetch, fill, gather, unpin.

        Drains the SQ rings (the simulated controller retires *everything*
        pending — commands from every outstanding token, so deep submission
        windows are serviced at batched Little's-law concurrency and the
        drain's device time is charged here, to the waiter), performs the
        deferred fetch DMA for this token's lines that are still in flight,
        fills and un-flags them, gathers/applies the op's element values,
        and releases the pins taken at submit.

        Under ``defer_drain`` (the runtime's ``drain="deferred"`` mode) the
        rings are left pending for :meth:`BamRuntime.drain` and the token's
        own command histograms are charged instead, exactly like the
        pre-async deferred accounting.

        Returns ``(state', values)``: the gathered elements for a read
        token, the (masked) written values for a write token, zeros for a
        prefetch token.  A token must be redeemed exactly once: a second
        ``wait`` of the same (host) token raises ``ValueError`` instead of
        silently over-releasing its cache pins.  A zero-shaped token (from
        an empty submit) completes as a no-op.

        Thin shim over :meth:`wait_ex`, discarding the per-lane error
        mask — with the :class:`~repro.core.ssd.FaultModel` disabled (the
        default) the mask is identically False and nothing is lost.
        """
        st, vals, _ = self.wait_ex(st, token)
        return st, vals

    def wait_ex(self, st: BamState, token: IOToken
                ) -> Tuple[BamState, jax.Array, jax.Array]:
        """:meth:`wait` returning ``(state', values, error_mask)``.

        ``error_mask`` is per request lane: True where the lane's unique
        line's own fetch command retired with an error after exhausting
        the fault model's retry budget (or was routed to a hard-failed
        device).  Errored lanes read as 0 and their write payloads are
        **not** applied; the degradation contract is that a cache line is
        never filled from a failed fetch — the line is invalidated
        (un-inflighted, tag freed, never garbage-filled) and the next
        demand for that key re-fetches it.  Lanes that rode another
        token's command (cross-op coalescing) or whose command the rings
        dropped are served by this wait's own DMA and complete OK.  With
        the fault model disabled the mask is constant False and the op is
        bit-identical to the fault-free wait.
        """
        _mark_redeemed(token)
        self._check_channels(st)
        if token.ukeys.shape[0] == 0:
            # empty token: nothing was enqueued, pinned or fetched
            return st, jnp.zeros((0,), self.dtype), jnp.zeros((0,), bool)
        ctx = self.tenant_ctx
        nd = self.ssd.n_devices
        sb = self.ssd.stripe_blocks
        fault = self.ssd.fault
        fd = fault.failed_devices
        ukeys = token.ukeys
        uvalid = ukeys >= 0
        valid = token.valid
        off = token.off

        # 1) drain the rings and pick the device-time charge basis: the
        #    drained batch (plus this token's ring-rejected commands, which
        #    are still served read/write-through) — or, under deferred
        #    drain, this token's own commands.  The fused path drains with
        #    closed-form accounting (queues.drain_accounting): wait only
        #    consumes order-free reductions of the completion stream, so
        #    the WFQ arbitration sort and the per-command materialisation
        #    are skipped (BamRuntime.drain keeps service_all — it *is* the
        #    observable arbitration order).
        with jax.named_scope("drain"):
            fstats = None                   # fault accounting for this drain
            if self.defer_drain:
                qs2 = st.queues
                reads_charge = token.dev_reads
                writes_charge = token.dev_writes
                if fault.enabled:
                    # Deferred mode never drains here; account this token's
                    # OWN commands from its ticket stamps (write-backs carry
                    # no token ticket — their errors surface at the round
                    # drain, not in per-token metrics).
                    fstats = self._token_fault_stats(token)
            elif self.fused_rounds:
                qs2, dr = Q.drain_accounting(
                    st.queues, impl=self.kernel_impl,
                    fault=fault if fault.enabled else None)
                reads_charge = dr.reads_dev + token.drop_dev_reads
                writes_charge = dr.writes_dev + token.drop_dev_writes
                if fault.enabled:
                    fstats = dict(err_reads=dr.err_reads_dev,
                                  err_writes=dr.err_writes_dev,
                                  retry_reads=dr.retry_reads_dev,
                                  retry_writes=dr.retry_writes_dev,
                                  transient=dr.transient_errors)
            else:
                qs2, comps = Q.service_all(
                    st.queues, fault=fault if fault.enabled else None)
                cvalid = comps.valid
                reads_charge = device_histogram(
                    comps.keys, nd, cvalid & ~comps.is_write, sb, fd) \
                    + token.drop_dev_reads
                writes_charge = device_histogram(
                    comps.keys, nd, cvalid & comps.is_write, sb, fd) \
                    + token.drop_dev_writes
                if fault.enabled:
                    fstats = dict(err_reads=comps.err_reads_dev,
                                  err_writes=comps.err_writes_dev,
                                  retry_reads=comps.retry_reads_dev,
                                  retry_writes=comps.retry_writes_dev,
                                  transient=comps.transient)

        # 2) fresh probe: lines this token submitted may since have been
        #    filled by another token's wait (cross-op coalescing), written
        #    to, or — for unpinned speculative lines — evicted.
        with jax.named_scope("probe"):
            pr2 = C.probe(st.cache, ukeys, uvalid, tenant=ctx.tenant,
                          impl=self.kernel_impl)
            pend = pr2.hit & pr2.inflight              # resident, fill pending
            # Resolve this token's command fates from the (device, ticket)
            # stamps — the same pure function the drain accounting uses, so
            # wait and drain can never disagree about which commands failed.
            failed_u = jnp.zeros(ukeys.shape, bool)
            ok_u = jnp.ones(ukeys.shape, bool)
            if fault.enabled:
                dev_u = device_of_block(ukeys, nd, sb, fd)
                ok_u, _, _ = fault.command_status(dev_u, token.ticket)
                failed_u = uvalid & ~ok_u
            if token.kind == "prefetch":
                # only materialise lines still awaiting their speculative fill
                need = pend & ~failed_u
            else:
                # fetch everything not gatherable from the cache: still-pending
                # grants plus bypassed keys (read/write-through) — minus rows
                # whose own command errored: a failed fetch moves no data.
                need = uvalid & (~pr2.hit | pend) & ~failed_u

        # 3) the deferred fetch DMA + completion fill.  Filling only lines
        #    that are *still* in flight makes completion idempotent across
        #    tokens: whoever waits first fills; later waiters see a filled
        #    resident line and never clobber newer data with a re-fetch.
        #    Failed commands invalidate their pending line instead of
        #    filling it (never garbage-filled, never left in-flight).
        with jax.named_scope("fetch"):
            store = self._store(st)
            lines = self._fetch_gated(store, jnp.where(need, ukeys, -1), need)
        with jax.named_scope("fill"):
            if fault.enabled:
                cache1 = C.fill_complete_status(st.cache, pr2.slot, pend, ok_u,
                                                lines)
            elif self.fused_rounds:
                cache1 = C.fill_complete(st.cache, pr2.slot, pend, lines)
            else:
                cache1 = C.fill(st.cache, pr2.slot, pend, lines)
                cache1 = C.clear_inflight(cache1,
                                          jnp.where(pend, pr2.slot, -1))
            n_fetch = jnp.sum(need.astype(jnp.int32))
            new_storage = st.storage

        # 3b) stride-readahead lines issued by this token's submit.
        with jax.named_scope("readahead"):
            if token.ra_keys is not None:
                ra = token.ra_keys
                ra_pr = C.probe(cache1, ra, ra >= 0, tenant=ctx.tenant,
                                impl=self.kernel_impl)
                ra_pend = ra_pr.hit & ra_pr.inflight
                ra_need = ra_pend
                ra_ok = jnp.ones(ra.shape, bool)
                if fault.enabled and token.ra_ticket is not None:
                    dev_ra = device_of_block(ra, nd, sb, fd)
                    ra_ok, _, _ = fault.command_status(dev_ra, token.ra_ticket)
                    # a failed speculative fetch degrades silently: the line
                    # is invalidated, no lane errors (nothing demanded it yet)
                    ra_need = ra_pend & ((ra < 0) | ra_ok)
                lines_ra = self._fetch_gated(store, jnp.where(ra_need, ra, -1),
                                             ra_need)
                if fault.enabled:
                    cache1 = C.fill_complete_status(cache1, ra_pr.slot,
                                                    ra_pend, ra_ok, lines_ra)
                elif self.fused_rounds:
                    cache1 = C.fill_complete(cache1, ra_pr.slot, ra_pend,
                                             lines_ra)
                else:
                    cache1 = C.fill(cache1, ra_pr.slot, ra_pend, lines_ra)
                    cache1 = C.clear_inflight(
                        cache1, jnp.where(ra_pend, ra_pr.slot, -1))
                n_fetch = n_fetch + jnp.sum(ra_need.astype(jnp.int32))

        # 4) op-specific completion.
        with jax.named_scope("gather"):
            u = token.inverse
            # Lanes whose unique line's own command errored: they read 0, their
            # write payloads are withheld, and the caller sees them in the
            # returned error_mask.  Constant False with the fault disabled.
            err_lane = valid & failed_u[u]
            if token.kind == "read":
                # Gather the hit lanes through the kernel dispatch layer
                # (Pallas scalar-prefetch line gather on TPU — the BlockSpec
                # index map *is* the page-table walk; on the ref/XLA path the
                # `off` column keeps it an element gather, not line-wide).
                hit_u = pr2.hit[u]
                hit_vals = K.gather_blocks(
                    cache1.data, jnp.where(hit_u, pr2.slot[u], -1), off=off,
                    impl=self.kernel_impl)
                vals = jnp.where(hit_u, hit_vals, lines[u, off])
                vals = jnp.where(valid, vals, 0).astype(self.dtype)
                if fault.enabled:
                    # errored pend rows were invalidated, not filled — the
                    # stale probe still says hit, so mask their lanes to 0
                    vals = jnp.where(err_lane, jnp.zeros((), self.dtype), vals)
                cache_f = cache1
            elif token.kind == "write":
                values = token.values
                assert values is not None   # write tokens carry their payload
                # scatter the new element values into resident lines...
                # (errored lanes excluded: their line was invalidated, their
                # write did not happen — no torn lines, no phantom dirty
                # bits)
                wr_lane = valid & ~err_lane if fault.enabled else valid
                slot_r = jnp.where(pr2.hit[u], pr2.slot[u], -1)
                in_cache = slot_r >= 0
                rows = jnp.where(wr_lane & in_cache, slot_r, cache1.num_lines)
                cols = jnp.where(wr_lane & in_cache, off, 0)
                data = cache1.data.at[rows, cols].set(
                    values.astype(self.dtype), mode="drop")
                cache_f = C._replace_data(cache1, data=data)
                cache_f = C.mark_dirty(
                    cache_f, jnp.where(wr_lane & in_cache, slot_r, -1))
                # ...and write through the lines that have no slot (bypass);
                # a bypass row whose fetch errored wrote nothing (its RMW
                # background line never arrived — skipping beats corrupting
                # storage with a zero-filled line).
                byp_u = (~pr2.hit[u]) & wr_lane
                byp_rows = jnp.where(byp_u, u, lines.shape[0])
                byp_lines = lines.at[byp_rows, jnp.where(byp_u, off, 0)].set(
                    values.astype(self.dtype), mode="drop")
                bt_keys = jnp.where(uvalid & ~pr2.hit & ~failed_u, ukeys, -1)
                if self.storage is None:
                    new_storage = new_storage.write_blocks(bt_keys, byp_lines)
                else:
                    self.storage.write_blocks(bt_keys, byp_lines)
                vals = jnp.where(valid, values, 0).astype(self.dtype)
                if fault.enabled:
                    vals = jnp.where(err_lane, jnp.zeros((), self.dtype), vals)
            else:                                       # prefetch: no values
                vals = jnp.zeros(off.shape, self.dtype)
                cache_f = cache1

        # 5) release the pins taken at submit.
        with jax.named_scope("release"):
            cache_f = C.release(cache_f, token.pin_slots)

        # 6) completion-side metrics: bytes actually fetched + the drain's
        #    device busy time (max over channels gates the batch).
        with jax.named_scope("accounting"):
            mt = st.metrics
            tok_done = jnp.any(valid).astype(mt.requests.dtype)
            fault_kw = {}
            if fstats is not None:
                n_err = fstats["err_reads"] + fstats["err_writes"]
                n_retry = fstats["retry_reads"] + fstats["retry_writes"]
                fault_kw = dict(
                    transient_errors=mt.transient_errors + fstats["transient"],
                    retries=mt.retries + jnp.sum(n_retry),
                    failed_commands=mt.failed_commands + jnp.sum(n_err),
                    degraded_reads=mt.degraded_reads
                        + jnp.sum(err_lane.astype(jnp.int32)),
                    dev_errors=mt.dev_errors + n_err,
                )
            metrics = dataclasses.replace(
                mt,
                bytes_from_storage=mt.bytes_from_storage
                    + n_fetch * self.block_bytes,
                tokens_waited=mt.tokens_waited + tok_done,
                tokens_in_flight=mt.tokens_in_flight - tok_done,
                **self._charge_wait(mt, st.queues, reads_charge, writes_charge,
                                    fstats=fstats),
                **fault_kw,
            )
        return BamState(cache=cache_f, queues=qs2, metrics=metrics,
                        storage=new_storage), vals, err_lane

    def _token_fault_stats(self, token: IOToken) -> dict:
        """Fault accounting from a token's OWN command tickets (deferred
        drain: the shared rings are not drained here, so the whole-batch
        receipt does not exist yet).  Read commands only — write-backs are
        not ticket-stamped on the token."""
        fault = self.ssd.fault
        nd = self.ssd.n_devices
        sb = self.ssd.stripe_blocks
        fd = fault.failed_devices
        devs = jnp.arange(nd, dtype=jnp.int32)

        def _per_dev(dev, w):
            oh = (dev[:, None] == devs[None, :]).astype(jnp.int32)
            return jnp.sum(oh * w[:, None].astype(jnp.int32),
                           axis=0).astype(jnp.int32)

        dev_u = device_of_block(token.ukeys, nd, sb, fd)
        ok_u, retry_u, trans_u = fault.command_status(dev_u, token.ticket)
        err_reads = _per_dev(dev_u, ~ok_u)
        retry_reads = _per_dev(dev_u, retry_u)
        transient = jnp.sum(trans_u).astype(jnp.int32)
        if token.ra_ticket is not None:
            dev_ra = device_of_block(token.ra_keys, nd, sb, fd)
            ra_ok, ra_retry, ra_trans = fault.command_status(
                dev_ra, token.ra_ticket)
            err_reads = err_reads + _per_dev(dev_ra, ~ra_ok)
            retry_reads = retry_reads + _per_dev(dev_ra, ra_retry)
            transient = transient + jnp.sum(ra_trans).astype(jnp.int32)
        zero = jnp.zeros((nd,), jnp.int32)
        return dict(err_reads=err_reads, err_writes=zero,
                    retry_reads=retry_reads, retry_writes=zero,
                    transient=transient)

    def _charge_wait(self, mt: IOMetrics, qs: Q.QueueState,
                     reads_hist: jax.Array, writes_hist: jax.Array,
                     fstats: dict | None = None) -> dict:
        """Device-time charge for a drain: each channel retires its share at
        its own Little's-law rate, the straggler gates the batch.

        With fault accounting (``fstats``) the retry/backoff cost lands on
        the device clocks — every re-issue is charged as
        ``tail_latency_mult`` extra commands' worth of service time on its
        device — while the data counters (``dev_reads``/``dev_writes``/
        ``dev_bytes``) count only commands that *completed*: an errored
        command burned time but moved no data.  ``fstats=None`` (fault
        disabled) is the exact pre-fault charge."""
        group_limit = qs.group_size * qs.depth
        t_reads, t_writes = reads_hist, writes_hist
        ok_reads, ok_writes = reads_hist, writes_hist
        if fstats is not None:
            mult = self.ssd.fault.tail_latency_mult
            t_reads = reads_hist + mult * fstats["retry_reads"]
            t_writes = writes_hist + mult * fstats["retry_writes"]
            ok_reads = reads_hist - fstats["err_reads"]
            ok_writes = writes_hist - fstats["err_writes"]
        t_read, t_read_dev = self.ssd.service_time_per_device_traced(
            t_reads, self.block_bytes, queue_depth_limit=group_limit)
        t_write, t_write_dev = self.ssd.service_time_per_device_traced(
            t_writes, self.block_bytes, write=True,
            queue_depth_limit=group_limit)
        return dict(
            sim_time_s=mt.sim_time_s + t_read + t_write,
            read_time_s=mt.read_time_s + t_read,
            write_time_s=mt.write_time_s + t_write,
            dev_reads=mt.dev_reads + ok_reads,
            dev_writes=mt.dev_writes + ok_writes,
            dev_bytes=mt.dev_bytes
                + (ok_reads + ok_writes) * self.block_bytes,
            dev_time_s=mt.dev_time_s + t_read_dev + t_write_dev,
        )

    # ----------------------------------------------- synchronous shims
    def read(self, st: BamState, idx: jax.Array,
             valid: jax.Array | None = None) -> Tuple[jax.Array, BamState]:
        """Gather ``self.flat[idx]`` for a wavefront of element indices.

        Compatibility shim: exactly ``submit`` + ``wait`` back to back (the
        op drains alone, paying full miss latency — use the token API to
        keep a multi-wavefront window in flight).
        """
        st, tok = self.submit(st, IORequest.read(idx, valid))
        st, vals = self.wait(st, tok)
        return vals, st

    # ------------------------------------------------------------- prefetch
    def prefetch(self, st: BamState, idx: jax.Array,
                 valid: jax.Array | None = None) -> BamState:
        """Hint the array at a future wavefront: warm the cache, no values.

        The lines covering ``idx`` are brought in through the low-priority
        readahead lane as *speculative* residents — inserted without pin, so
        a hint that never materialises is the first thing the clock hand
        reclaims.  Already-resident lines and invalid/out-of-range lanes
        cost nothing.  Works regardless of :class:`PrefetchConfig.enabled`
        (that flag only gates the automatic stride readahead in
        :meth:`read`).  Demand counters (requests/hits/misses) are untouched:
        a prefetch is not compute traffic.

        Compatibility shim over ``submit`` + ``wait``; submitting an
        ``IORequest.prefetch`` and waiting it later turns the hint into a
        genuinely asynchronous warm-up.
        """
        st, tok = self.submit(st, IORequest.prefetch(idx, valid))
        st, _ = self.wait(st, tok)
        return st

    # --------------------------------------------------------------- write
    def write(self, st: BamState, idx: jax.Array, values: jax.Array,
              valid: jax.Array | None = None) -> BamState:
        """Element-level writes: read-modify-write with write-allocate.

        Duplicate element indices within one wavefront are last-writer-wins
        with unspecified order (as on the GPU).  Compatibility shim over
        ``submit`` + ``wait``.
        """
        st, tok = self.submit(st, IORequest.write(idx, values, valid))
        st, _ = self.wait(st, tok)
        return st

    def flush(self, st: BamState) -> BamState:
        """Write back every dirty resident line (shutdown / barrier path).

        Write-backs go through the SQ rings like every other I/O: enqueue,
        doorbell, drain — so ``doorbells``/``max_queue_depth`` and the
        per-device counters see shutdown traffic exactly as they see
        ``read``/``write`` write-backs.  Lines the rings cannot hold this
        round are still persisted (the drop degrades accounting, never
        correctness — same contract as the read path's read-through).

        In a shared cache only *this tenant's* dirty lines are flushed (a
        foreign line's write-back belongs to its owner's storage tier);
        other tenants' dirty bits are left untouched.
        """
        self._check_channels(st)
        ctx = self.tenant_ctx
        nd = self.ssd.n_devices
        sb = self.ssd.stripe_blocks
        fault = self.ssd.fault
        fd = fault.failed_devices
        tags = st.cache.tags.reshape(-1)
        dirty = st.cache.dirty.reshape(-1)
        mine = st.cache.owner.reshape(-1) == jnp.int32(ctx.tenant)
        keys = jnp.where(dirty & mine & (tags >= 0), tags, -1)
        qs1, rec_w = Q.enqueue(st.queues, keys,
                               is_write=jnp.ones(keys.shape, bool),
                               tenant=ctx.tenant)
        depth_now = Q.in_flight(qs1)
        depth_dev = Q.in_flight_per_device(qs1)
        # Drain charges the clock, exactly as in wait(): the retired batch
        # may also carry outstanding tokens' commands (a flush inside a
        # submission window), whose device time lands here, on the
        # barrier; their own waits then drain an empty ring.  Ring-dropped
        # flush write-backs are still persisted, so they are charged too.
        fstats = None
        if self.defer_drain:
            qs2 = qs1
            reads_charge = jnp.zeros((nd,), jnp.int32)
            writes_charge = device_histogram(keys, nd, stripe_blocks=sb,
                                             failed_devices=fd)
        elif self.fused_rounds:
            qs2, dr = Q.drain_accounting(
                qs1, impl=self.kernel_impl,
                fault=fault if fault.enabled else None)
            reads_charge = dr.reads_dev
            writes_charge = dr.writes_dev \
                + device_histogram(keys, nd, ~rec_w.accepted, sb, fd)
            if fault.enabled:
                fstats = dict(err_reads=dr.err_reads_dev,
                              err_writes=dr.err_writes_dev,
                              retry_reads=dr.retry_reads_dev,
                              retry_writes=dr.retry_writes_dev,
                              transient=dr.transient_errors)
        else:
            qs2, comps = Q.service_all(
                qs1, fault=fault if fault.enabled else None)
            cvalid = comps.valid
            reads_charge = device_histogram(comps.keys, nd,
                                            cvalid & ~comps.is_write, sb, fd)
            writes_charge = device_histogram(comps.keys, nd,
                                             cvalid & comps.is_write,
                                             sb, fd) \
                + device_histogram(keys, nd, ~rec_w.accepted, sb, fd)
            if fault.enabled:
                fstats = dict(err_reads=comps.err_reads_dev,
                              err_writes=comps.err_writes_dev,
                              retry_reads=comps.retry_reads_dev,
                              retry_writes=comps.retry_writes_dev,
                              transient=comps.transient)
        store = self._store(st)
        new_storage = st.storage
        if self.storage is None:
            new_storage = store.write_blocks(keys, st.cache.data)
        else:
            self.storage.write_blocks(keys, st.cache.data)
        n_wb = jnp.sum((keys >= 0).astype(jnp.int32))
        flushed = (keys >= 0).reshape(st.cache.dirty.shape)
        cache = C._replace_data(st.cache, dirty=st.cache.dirty & ~flushed)
        mt = st.metrics
        fault_kw = {}
        if fstats is not None:
            # Errored flush commands are accounting-only degradation: the
            # barrier's host write persists every dirty line regardless,
            # so no data is lost — the counters record the burned attempts.
            n_err = fstats["err_reads"] + fstats["err_writes"]
            n_retry = fstats["retry_reads"] + fstats["retry_writes"]
            fault_kw = dict(
                transient_errors=mt.transient_errors + fstats["transient"],
                retries=mt.retries + jnp.sum(n_retry),
                failed_commands=mt.failed_commands + jnp.sum(n_err),
                dev_errors=mt.dev_errors + n_err,
            )
        metrics = dataclasses.replace(
            mt,
            write_ops=mt.write_ops + n_wb,
            bytes_to_storage=mt.bytes_to_storage + n_wb * self.block_bytes,
            doorbells=mt.doorbells + rec_w.n_doorbells,
            dropped=mt.dropped + rec_w.n_dropped,
            max_queue_depth=jnp.maximum(mt.max_queue_depth,
                                        depth_now.astype(jnp.int32)),
            dev_max_depth=jnp.maximum(mt.dev_max_depth,
                                      depth_dev.astype(jnp.int32)),
            **self._charge_wait(mt, st.queues, reads_charge, writes_charge,
                                fstats=fstats),
            **fault_kw,
        )
        # A flush can retire pending tokens' commands mid-window; re-check
        # the in-flight-token watermark so interleaved flush+wait sequences
        # never under-report it.
        metrics = recheck_token_watermark(metrics)
        return BamState(cache=cache, queues=qs2, metrics=metrics,
                        storage=new_storage)


@pytree_dataclass
class KVState:
    """All mutable state of a :class:`BamKVStore`: the values'
    :class:`BamState` and the device-resident index (``table[s]`` is the
    key placed in slot ``s``, ``-1`` where the slot is empty)."""

    bam: BamState
    table: jax.Array                # (capacity,) int32


@pytree_dataclass
class KVToken:
    """Future returned by :meth:`BamKVStore.submit`: the value rows'
    :class:`IOToken` and the keys' found mask, which is known at submit (the
    index is device memory).  Redeem exactly once with
    :meth:`BamKVStore.wait`."""

    io: IOToken
    found: jax.Array                # (n_keys,) bool


@dataclasses.dataclass
class BamKVStore:
    """Key-value abstraction: device-resident open-addressed index over
    storage-resident fixed-width values (the paper's 'key-value store').

    The index (one int32 per capacity slot) is small and lives in device
    memory; the values — the massive structure — live behind a
    :class:`BamArray`, one value row per index slot.  This is exactly the
    split used by the framework's on-demand embedding feature.

    Lookups ride the array's token API: :meth:`submit` ``(KVState, keys)
    -> (KVState, KVToken)`` probes the index (``kv_index`` scope) and
    submits the found keys' value rows as one element wavefront;
    :meth:`wait` ``(KVState, KVToken) -> (KVState, values)`` redeems it.
    ``probes`` is the lookup window: every key of the built index lies
    within ``probes`` slots of its home slot (:meth:`place`).  It has no
    default: it is the built table's, and :meth:`state` refuses a table
    that needs a longer one.
    """

    array: BamArray                 # values: (capacity, value_elems) flattened
    capacity: int
    value_elems: int
    probes: int
    _jit_ops: Dict[str, Any] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _trace_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @staticmethod
    def _hash_host(keys, capacity: int):
        """The shared hash on the host (numpy, scalar or array): Knuth
        multiply with a uint32 wrap, then mod.

        The lookup computes the identical quantity in uint32 arithmetic
        (:meth:`_hash_traced`); the wrap must happen *before* the modulo on
        both sides or roughly half of all keys (those whose wrapped product
        lands >= 2^31) probe different slots at build vs lookup time.  No
        ``abs`` anywhere: ``abs(INT32_MIN)`` is itself negative in int32.
        """
        import numpy as np
        k = np.asarray(keys, np.int64).astype(np.uint64) & np.uint64(0xFFFFFFFF)
        return (k * np.uint64(2654435761) & np.uint64(0xFFFFFFFF)) \
            % np.uint64(capacity)

    def _hash_traced(self, keys: jax.Array) -> jax.Array:
        """uint32-wrap hash of a key wavefront -> uint32 slots in [0, cap)."""
        h = keys.astype(jnp.uint32) * jnp.uint32(2654435761)
        return h % jnp.uint32(self.capacity)

    @staticmethod
    def place(keys, *, capacity: int | None = None,
              probes: int | None = None):
        """Linear-probing placement of ``keys`` into ``capacity`` slots, on
        the host and vectorised: returns ``(table, slots, probes)``, where
        ``table[s]`` is the key placed in slot ``s`` (``-1`` empty),
        ``slots[i]`` the slot of ``keys[i]`` (a repeated key has one slot),
        and ``probes`` the lookup window the table needs: its longest
        displacement from a home slot, plus one.

        Every unplaced key tries ``(home + d) % capacity`` in round ``d``;
        of the keys that try one empty slot, the least key takes it and the
        rest step on with the keys that found theirs taken.  Any set of up
        to ``capacity`` keys is placed.  An explicit ``probes`` rejects a
        set that would need a longer window.
        """
        import numpy as np
        keys = np.asarray(keys, np.int32)
        if np.any(keys == -1):
            raise ValueError("key -1 is reserved as the empty-slot sentinel")
        uniq, inverse = np.unique(keys, return_inverse=True)
        capacity = capacity or max(2 * keys.shape[0], 16)
        if uniq.shape[0] > capacity:
            raise ValueError(f"kv store: {uniq.shape[0]} keys do not fit "
                             f"capacity {capacity}")
        home = BamKVStore._hash_host(uniq, capacity).astype(np.int64)
        table = np.full((capacity,), -1, np.int32)
        slot_u = np.empty(uniq.shape, np.int64)
        todo = np.arange(uniq.shape[0])
        rounds = 0
        while todo.size:
            if probes is not None and rounds == probes:
                raise ValueError(
                    f"kv store: key {int(uniq[todo[0]])} cannot be placed "
                    f"within probes={probes} slots of its home slot; raise "
                    "capacity or probes")
            s = (home[todo] + rounds) % capacity
            free = table[s] == -1
            # uniq is sorted, so the first claimant of a slot is its least key
            ws, first = np.unique(s[free], return_index=True)
            won = todo[free][first]
            table[ws] = uniq[won]
            slot_u[won] = ws
            placed = np.zeros(uniq.shape, bool)
            placed[won] = True
            todo = todo[~placed[todo]]
            rounds += 1
        return table, slot_u[inverse.reshape(-1)], max(rounds, 1)

    @staticmethod
    def build_table(keys, values, *, capacity: int | None = None,
                    probes: int | None = None):
        """Host-side build shared by :meth:`build` and the multi-tenant
        runtime: returns ``(table, store_vals, capacity, probes)`` where
        ``store_vals[slot]`` holds the value row of the key placed in
        ``slot`` (:meth:`place`; a repeated key keeps its last row)."""
        import numpy as np
        values = np.asarray(values)
        table, slots, probes = BamKVStore.place(keys, capacity=capacity,
                                                probes=probes)
        rows = slots.shape[0] - 1 - np.unique(slots[::-1],
                                              return_index=True)[1]
        store_vals = np.zeros((table.shape[0], values.shape[1]),
                              values.dtype)
        store_vals[slots[rows]] = values[rows]
        return table, store_vals, table.shape[0], probes

    @staticmethod
    def build(keys, values, *, capacity: int | None = None,
              probes: int | None = None, **bam_kw):
        """Host-side bulk build; returns (kv, index_table, BamState)."""
        import numpy as np
        values = np.asarray(values)
        _, value_elems = values.shape
        table, store_vals, capacity, probes = BamKVStore.build_table(
            keys, values, capacity=capacity, probes=probes)
        bam_kw.setdefault("block_elems", value_elems)
        arr, st = BamArray.build(store_vals, **bam_kw)
        kv = BamKVStore(array=arr, capacity=capacity,
                        value_elems=value_elems, probes=probes)
        return kv, jnp.asarray(table), st

    @staticmethod
    def window(table) -> int:
        """The lookup window ``table`` needs: the longest displacement of
        one of its keys from its home slot, plus one (1 if empty)."""
        import numpy as np
        table = np.asarray(table)
        slots = np.flatnonzero(table != -1)
        if slots.size == 0:
            return 1
        home = BamKVStore._hash_host(table[slots], table.shape[0])
        return int(((slots - home.astype(np.int64))
                    % table.shape[0]).max()) + 1

    def state(self, bam: BamState, table) -> KVState:
        """The store's :class:`KVState` over the values' ``bam`` and the
        index ``table`` (concrete, made outside any trace).  Raises if the
        table is not ``capacity`` slots or needs a longer window than the
        store's ``probes``: a lookup would miss the keys beyond it."""
        import numpy as np
        if isinstance(table, jax.core.Tracer):
            raise TypeError("kv store: make the KVState outside the trace")
        if np.shape(table) != (self.capacity,):
            raise ValueError(f"kv store: index of shape {np.shape(table)}, "
                             f"capacity {self.capacity}")
        # host-only from here on (tracers refused above): a numpy int
        need = BamKVStore.window(table)
        if need > self.probes:  # bamlint: ignore[BAM104]
            raise ValueError(
                f"kv store: the index needs a window of {need} slots but "
                f"the store reads probes={self.probes}; pass the window "
                "place() returns")
        return KVState(bam=bam, table=jnp.asarray(table, jnp.int32))

    # ------------------------------------------------------------ token API
    @property
    def storage(self):
        """The values' block store (the array's)."""
        return self.array.storage

    @property
    def trace_counts(self) -> Dict[str, int]:
        """How many times each jit-cached lookup op has been traced."""
        return dict(self._trace_counts)

    def _jit_op(self, name: str, make, donate_argnums=()):
        """See :func:`_cached_jit` (the shared cache + retrace probe)."""
        return _cached_jit(self._jit_ops, self._trace_counts, name, make,
                           donate_argnums=donate_argnums)

    def submit_jit(self, *, donate: bool = False):
        """Cached ``jax.jit`` of :meth:`submit` ``(kst, keys) -> (kst,
        tok)``; the jitted op is ``bam_submit_kv[_donated]``.  ``donate``
        as in :meth:`BamArray.submit_jit`."""
        key = "submit:kv[donated]" if donate else "submit:kv"
        return self._jit_op(
            key, lambda: lambda kst, keys: self.submit(kst, keys),
            donate_argnums=(0,) if donate else ())

    def wait_jit(self, *, donate: bool = False, guard: bool = True):
        """Cached ``jax.jit`` of :meth:`wait` ``(kst, tok) -> (kst,
        values)``; ``donate`` and ``guard`` as in
        :meth:`BamArray.wait_jit`."""
        key = "wait:kv[donated]" if donate else "wait:kv"
        fn = self._jit_op(
            key, lambda: lambda kst, tok: self.wait(kst, tok),
            donate_argnums=(0,) if donate else ())
        return (_guarded_wait(self._jit_ops, key, fn, lambda tok: tok.io)
                if guard else fn)

    def _probe(self, table: jax.Array, keys: jax.Array) -> jax.Array:
        """Slot of each key (``-1``: not found): one gather of the
        ``probes`` slots from each key's home slot, first match."""
        win = ((self._hash_traced(keys)[:, None]
                + jnp.arange(self.probes, dtype=jnp.uint32)[None, :])
               % jnp.uint32(self.capacity)).astype(jnp.int32)
        match = table[win] == keys[:, None]
        # key -1 would "match" every empty slot (the sentinel); never found.
        found = jnp.any(match, axis=1) & (keys != -1)
        first = jnp.argmax(match, axis=1)
        slot = jnp.take_along_axis(win, first[:, None], axis=1)[:, 0]
        return jnp.where(found, slot, -1)

    def submit(self, kst: KVState, keys: jax.Array
               ) -> Tuple[KVState, KVToken]:
        """Probe the index and *submit* the found keys' value rows as one
        wavefront of element reads.  The found mask rides the token at
        once; the values arrive at :meth:`wait`.  Several lookups' tokens
        may be outstanding at once — duplicate hot keys across pending
        lookups coalesce onto one storage fetch."""
        ve = self.value_elems
        with jax.named_scope("kv_index"):
            slot = self._probe(kst.table, keys)
            found = slot >= 0
            idx = (jnp.where(found, slot, 0)[:, None] * ve
                   + jnp.arange(ve, dtype=jnp.int32)[None, :]).reshape(-1)
            vmask = jnp.repeat(found, ve)
        st, io = self.array.submit(kst.bam, IORequest.read(idx, vmask))
        with jax.named_scope("kv_index"):
            mt = st.metrics
            n_keys = jnp.sum((keys != -1).astype(jnp.int32))
            st = dataclasses.replace(st, metrics=dataclasses.replace(
                mt, kv_lookups=mt.kv_lookups + n_keys,
                kv_probe_slots=mt.kv_probe_slots + n_keys * self.probes))
        return KVState(bam=st, table=kst.table), KVToken(io=io, found=found)

    def wait(self, kst: KVState, tok: KVToken
             ) -> Tuple[KVState, jax.Array]:
        """Redeem a :meth:`submit` token: ``(kst', values)`` with values
        shaped ``(n_keys, value_elems)`` (zeros where not found)."""
        st, flat = self.array.wait(kst.bam, tok.io)
        return (KVState(bam=st, table=kst.table),
                flat.reshape(-1, self.value_elems))

    def lookup(self, st: BamState, table: jax.Array, keys: jax.Array
               ) -> Tuple[jax.Array, jax.Array, BamState]:
        """Return (values, found_mask, state') for a wavefront of keys
        (synchronous shim: :meth:`submit` + :meth:`wait` over
        :meth:`state`)."""
        kst, tok = self.submit(self.state(st, table), keys)
        kst, vals = self.wait(kst, tok)
        return vals, tok.found, kst.bam

    def iter_op_family(self):
        """Enumerate the store's jit-cached token ops (see
        :class:`OpFamilyEntry` and :meth:`BamArray.iter_op_family`).
        ``example_args`` take a :class:`KVState`; a batch of ``n`` lanes is
        ``n // value_elems`` keys, so the value wavefront is ``n`` lanes as
        in the array's family."""
        import numpy as np

        def args_keys(kst, n):
            table = np.asarray(kst.table)
            present = table[table != -1]
            nk = max(n // self.value_elems, 1)
            return (kst, jnp.asarray(present[np.arange(nk) % present.size]))

        def args_token(kst, n):
            kst1, tok = self.submit(*args_keys(kst, n))
            return (kst1, tok)

        # both ride the array's lax.cond-gated write-back and fetch
        yield OpFamilyEntry(
            name="submit:kv", donatable=True, pure_all_hit=True,
            get=lambda donate=False: self.submit_jit(donate=donate),
            example_args=args_keys, trace_keys=("submit:kv",))
        yield OpFamilyEntry(
            name="wait:kv", donatable=True, pure_all_hit=True,
            get=lambda donate=False: self.wait_jit(donate=donate,
                                                   guard=False),
            example_args=args_token, trace_keys=("wait:kv",))


# ===================================================================== runtime
@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's registration against a shared :class:`BamRuntime`.

    ``ways`` is the cache way quota under ``isolation="partitioned"``
    (``None`` = equal split of the leftover ways); ``weight`` is the
    queue-arbitration service weight (see :func:`repro.core.queues
    .service_all`).
    """

    name: str
    data: Any                      # host / jnp array backing this tenant
    block_elems: int
    ways: int | None = None
    weight: float = 1.0
    prefetch: Optional[PrefetchConfig] = None


@pytree_dataclass
class RuntimeState:
    """All mutable state of a shared multi-tenant runtime.

    One cache + one queue pool serve every tenant; metrics are kept per
    tenant *and* globally.  Invariant (checked by
    :meth:`BamRuntime.assert_metrics_consistent` and the multi-tenant
    tests): every additive counter of the global ``metrics`` equals the
    sum of the tenants' counters.
    """

    cache: C.CacheState
    queues: Q.QueueState
    metrics: IOMetrics             # global accumulator
    tenant_metrics: tuple          # per-tenant IOMetrics, indexed by tid
    storages: tuple                # per-tenant in-graph storage (None for sim)


@dataclasses.dataclass
class BamRuntime:
    """The shared multi-tenant BaM runtime (paper §I: "multiple processes
    can share" the cache and queues).

    Several tenants — each a :class:`BamArray` over its own storage tier —
    run against *one* ``CacheState`` and *one* ``QueueState``:

    * cache isolation is **way-partitioning**: under
      ``isolation="partitioned"`` each tenant's clock sweep is confined to
      its contiguous way quota, so a streaming scan tenant cannot evict a
      cache-friendly neighbour's lines; ``isolation="shared"`` keeps
      today's free-for-all (tenants evict each other's clean lines) so
      the thrash is measurable;
    * queue sharing is **weighted-fair arbitration**: commands carry their
      tenant id and the simulated controller drains tenants in proportion
      to their ``TenantSpec.weight`` within each priority class, with
      back-pressure drops accounted per tenant;
    * metrics are **per tenant + global**, additive counters summing
      exactly.

    All tenants share the cache line geometry (``block_elems``) and the
    cache data dtype: lines are stored in ``cache_dtype`` and cast back to
    each tenant's dtype on read (exact for float32 tenants and for integer
    tenants whose values fit float32's 2**24 integer range).
    """

    tenants: Dict[str, BamArray]
    tenant_ids: Dict[str, int]
    isolation: str
    ways: int
    drain_mode: str = "per_op"
    _jit_ops: Dict[str, Any] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _trace_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    # ---------------------------------------------------------------- build
    @staticmethod
    def build(specs: Sequence[TenantSpec], *,
              num_sets: int, ways: int = 8,
              num_queues: int = 8, queue_depth: int = 1024,
              ssd: Optional[ArrayOfSSDs] = None,
              isolation: str = "partitioned",
              drain: str = "per_op",
              backend: str = "sim",
              cache_dtype=jnp.float32,
              kernel_impl: str = "auto",
              ) -> Tuple["BamRuntime", RuntimeState]:
        """``drain="per_op"`` (default) drains the rings inside every
        tenant op, exactly like a standalone ``BamArray``.
        ``drain="deferred"`` leaves commands pending so several tenants'
        wavefronts coexist in the shared rings; the caller then calls
        :meth:`drain` once per round and the weighted-fair arbitration
        orders the genuinely mixed completion stream.  Values are
        identical either way (fetches bypass the simulated controller);
        only when a round's commands overflow the rings does deferred
        mode drop more — accounting degrades, never correctness."""
        import numpy as np
        if isolation not in ("partitioned", "shared"):
            raise ValueError(
                f"isolation must be 'partitioned' or 'shared', "
                f"got {isolation!r}")
        if drain not in ("per_op", "deferred"):
            raise ValueError(
                f"drain must be 'per_op' or 'deferred', got {drain!r}")
        if kernel_impl not in ("auto", "pallas", "ref"):
            raise ValueError(
                f"kernel_impl must be 'auto', 'pallas' or 'ref', "
                f"got {kernel_impl!r}")
        if not specs:
            raise ValueError("need at least one TenantSpec")
        names = [s.name for s in specs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tenant names: {names}")
        block_elems = specs[0].block_elems
        for s in specs:
            if s.block_elems != block_elems:
                raise ValueError(
                    "all tenants must share the cache line geometry: "
                    f"{s.name} wants block_elems={s.block_elems}, "
                    f"{specs[0].name} has {block_elems}")
        nt = len(specs)

        # Way quotas: explicit quotas are honoured, the rest equal-split.
        if isolation == "partitioned":
            fixed = sum(s.ways for s in specs if s.ways is not None)
            free = [s for s in specs if s.ways is None]
            rest = ways - fixed
            if rest < len(free) or (not free and fixed != ways):
                raise ValueError(
                    f"way quotas don't fit: ways={ways}, explicit={fixed}, "
                    f"{len(free)} tenants left to split the remainder")
            share = {id(s): rest // len(free) for s in free} if free else {}
            for s in free[:rest % len(free) if free else 0]:
                share[id(s)] += 1
            lo = 0
            windows = []
            for s in specs:
                q = s.ways if s.ways is not None else share[id(s)]
                if q < 1:
                    raise ValueError(f"tenant {s.name} got a zero way quota")
                windows.append((lo, lo + q))
                lo += q
        else:
            windows = [(0, ways)] * nt

        ssd = ssd or ArrayOfSSDs(INTEL_OPTANE_P5800X, 1)
        num_queues = round_up(num_queues, ssd.n_devices)
        weights = tuple(float(s.weight) for s in specs)

        tenants: Dict[str, BamArray] = {}
        tenant_ids: Dict[str, int] = {}
        storages = []
        for tid, s in enumerate(specs):
            lo, hi = windows[tid]
            if backend == "sim":
                store = SimStorage.from_array(np.asarray(s.data), block_elems)
                state_store, dtype = None, store.dtype
            elif backend == "hbm":
                hs = HBMStorage.from_array(jnp.asarray(s.data), block_elems)
                store, state_store, dtype = None, hs, hs.dtype
            else:
                raise ValueError(f"unknown backend {backend!r}")
            # Integer tenants round-trip through the shared cache's float
            # dtype: refuse values outside its exact-integer range (e.g.
            # 2^24 for float32) instead of silently corrupting them.
            if (np.issubdtype(np.dtype(dtype), np.integer)
                    and jnp.issubdtype(cache_dtype, jnp.floating)):
                exact = 1 << (jnp.finfo(cache_dtype).nmant + 1)
                host = np.asarray(s.data)
                peak = int(np.abs(host).max()) if host.size else 0
                if peak > exact:
                    raise ValueError(
                        f"tenant {s.name!r} holds integer values up to "
                        f"{peak}, beyond the exact-integer range "
                        f"(+/-{exact}) of the shared cache dtype "
                        f"{jnp.dtype(cache_dtype).name}; pass a wider "
                        "cache_dtype to BamRuntime.build")
            tenants[s.name] = BamArray(
                storage=store, shape=tuple(np.shape(s.data)), dtype=dtype,
                block_elems=block_elems, ssd=ssd,
                prefetch_cfg=s.prefetch or PrefetchConfig(),
                tenant_ctx=TenantCtx(tenant=tid, way_lo=lo, way_hi=hi),
                defer_drain=(drain == "deferred"),
                kernel_impl=kernel_impl)
            tenant_ids[s.name] = tid
            storages.append(state_store)

        rst = RuntimeState(
            cache=C.make_cache(num_sets, ways, block_elems, cache_dtype),
            queues=Q.make_queues(num_queues, queue_depth,
                                 n_devices=ssd.n_devices,
                                 stripe_blocks=ssd.stripe_blocks,
                                 n_tenants=nt, tenant_weights=weights,
                                 failed_devices=ssd.fault.failed_devices),
            metrics=IOMetrics.zeros(ssd.n_devices),
            tenant_metrics=tuple(IOMetrics.zeros(ssd.n_devices)
                                 for _ in specs),
            storages=tuple(storages),
        )
        return BamRuntime(tenants=tenants, tenant_ids=tenant_ids,
                          isolation=isolation, ways=ways,
                          drain_mode=drain), rst

    # ------------------------------------------------------------- plumbing
    def array(self, name: str) -> BamArray:
        """The tenant's :class:`BamArray` (its ``TenantCtx`` rides along) —
        hand it to scenario code (``BamGraph``, ``BamKVStore.lookup``)
        together with a :meth:`tenant_view`."""
        return self.tenants[name]

    def tenant_view(self, rst: RuntimeState, name: str) -> BamState:
        """Project the shared state into the per-tenant :class:`BamState`
        that ``BamArray.read``/``write``/... consume."""
        tid = self.tenant_ids[name]
        return BamState(cache=rst.cache, queues=rst.queues,
                        metrics=rst.tenant_metrics[tid],
                        storage=rst.storages[tid])

    def absorb(self, rst: RuntimeState, name: str,
               st: BamState) -> RuntimeState:
        """Fold a tenant op's updated :class:`BamState` back into the
        shared runtime state: cache/queues replace (they are shared), the
        tenant's metrics delta also accumulates into the global view."""
        tid = self.tenant_ids[name]
        delta = metrics_delta(st.metrics, rst.tenant_metrics[tid])
        tm = list(rst.tenant_metrics)
        tm[tid] = st.metrics
        stores = list(rst.storages)
        stores[tid] = st.storage
        # The global in-flight window is the SUM of the tenants' windows,
        # but accumulate only maxes the per-tenant watermarks — two tenants
        # each holding one token would report a global watermark of 1.
        # Re-check against the summed window after every fold.
        metrics = recheck_token_watermark(
            metrics_accumulate(rst.metrics, delta))
        return RuntimeState(
            cache=st.cache, queues=st.queues, metrics=metrics,
            tenant_metrics=tuple(tm), storages=tuple(stores))

    # ------------------------------------------------------------------ ops
    def read(self, rst: RuntimeState, name: str, idx: jax.Array,
             valid: jax.Array | None = None
             ) -> Tuple[jax.Array, RuntimeState]:
        vals, st = self.tenants[name].read(self.tenant_view(rst, name),
                                           idx, valid)
        return vals, self.absorb(rst, name, st)

    def _jit_op(self, key: str, make, donate_argnums=()):
        """Per-(op, tenant) jit cache — see :func:`_cached_jit`."""
        return _cached_jit(self._jit_ops, self._trace_counts, key, make,
                           donate_argnums=donate_argnums)

    @property
    def trace_counts(self) -> Dict[str, int]:
        return dict(self._trace_counts)

    def read_jit(self, name: str):
        """A cached ``jax.jit`` of ``lambda rst, idx: self.read(rst, name,
        idx)`` — one compilation per (tenant, shape) however often callers
        grab it (streaming drivers call this every wavefront)."""
        return self._jit_op(
            f"read:{name}",
            lambda: lambda rst, idx: self.read(rst, name, idx))

    def write_jit(self, name: str):
        return self._jit_op(
            f"write:{name}",
            lambda: lambda rst, idx, values: self.write(rst, name, idx,
                                                        values))

    def submit_jit(self, name: str, *, donate: bool = False):
        """Cached jit of :meth:`submit` for one tenant ``(rst, req) ->
        (rst, token)``.  ``donate=True`` donates the shared state's
        buffers to the output (separate cache key; see
        :meth:`BamArray.submit_jit` for the reuse contract)."""
        key = f"submit:{name}" + ("[donated]" if donate else "")
        return self._jit_op(
            key, lambda: lambda rst, req: self.submit(rst, name, req),
            donate_argnums=(0,) if donate else ())

    def wait_jit(self, name: str, *, donate: bool = False,
                 guard: bool = True):
        """Cached jit of :meth:`wait` for one tenant ``(rst, token) ->
        (rst, values)``.  ``donate``/``guard`` as in
        :meth:`BamArray.wait_jit`."""
        key = f"wait:{name}" + ("[donated]" if donate else "")
        fn = self._jit_op(
            key, lambda: lambda rst, tok: self.wait(rst, name, tok),
            donate_argnums=(0,) if donate else ())
        return _guarded_wait(self._jit_ops, key, fn) if guard else fn

    def iter_op_family(self):
        """Enumerate the runtime's per-tenant jit-cached op family (see
        :class:`OpFamilyEntry` and :meth:`BamArray.iter_op_family`) —
        the registry hook ``tools/bamverify`` lowers for multi-tenant
        artifacts.  One entry per (op, tenant); ``example_args`` take the
        shared :class:`RuntimeState`."""
        for name in self.tenants:
            arr = self.tenants[name]

            def args_read(rst, n, _arr=arr):
                idx, valid = _arr._example_wavefront(n)
                return (rst, idx)

            def args_req(rst, n, _arr=arr):
                idx, valid = _arr._example_wavefront(n)
                return (rst, IORequest.read(idx, valid))

            def args_token(rst, n, _name=name, _arr=arr):
                idx, valid = _arr._example_wavefront(n)
                rst1, tok = self.submit(rst, _name,
                                        IORequest.read(idx, valid))
                return (rst1, tok)

            yield OpFamilyEntry(
                name=f"read:{name}",
                get=lambda donate=False, _n=name: self.read_jit(_n),
                example_args=args_read, trace_keys=(f"read:{name}",))
            yield OpFamilyEntry(
                name=f"submit:{name}", donatable=True, pure_all_hit=True,
                get=lambda donate=False, _n=name:
                    self.submit_jit(_n, donate=donate),
                example_args=args_req, trace_keys=(f"submit:{name}",))
            yield OpFamilyEntry(
                name=f"wait:{name}", donatable=True, pure_all_hit=True,
                get=lambda donate=False, _n=name:
                    self.wait_jit(_n, donate=donate, guard=False),
                example_args=args_token, trace_keys=(f"wait:{name}",))

    def write(self, rst: RuntimeState, name: str, idx: jax.Array,
              values: jax.Array, valid: jax.Array | None = None
              ) -> RuntimeState:
        st = self.tenants[name].write(self.tenant_view(rst, name),
                                      idx, values, valid)
        return self.absorb(rst, name, st)

    def submit(self, rst: RuntimeState, name: str, req: IORequest
               ) -> Tuple[RuntimeState, IOToken]:
        """Asynchronously submit one tenant's op against the shared state.

        Tokens from different tenants freely interleave: their commands
        coexist in the shared rings and their pins/in-flight lines in the
        shared cache.  Under ``drain="deferred"`` the per-token wait leaves
        the rings pending and :meth:`drain` retires the WFQ-ordered mixed
        stream, exactly as with the synchronous ops.
        """
        st, tok = self.tenants[name].submit(self.tenant_view(rst, name), req)
        return self.absorb(rst, name, st), tok

    def wait(self, rst: RuntimeState, name: str, token: IOToken
             ) -> Tuple[RuntimeState, jax.Array]:
        """Complete one tenant's pending token (see :meth:`BamArray.wait`).
        ``name`` must be the tenant that submitted the token."""
        st, vals = self.tenants[name].wait(self.tenant_view(rst, name),
                                           token)
        return self.absorb(rst, name, st), vals

    def wait_ex(self, rst: RuntimeState, name: str, token: IOToken
                ) -> Tuple[RuntimeState, jax.Array, jax.Array]:
        """:meth:`wait` returning the per-lane ``error_mask`` as well (see
        :meth:`BamArray.wait_ex`).  The errored token's fault counters
        land in the tenant's own :class:`IOMetrics` first and flow into
        the global view through :meth:`absorb`'s delta accumulation, so
        per-tenant error counters keep summing exactly to the global
        ones."""
        st, vals, err = self.tenants[name].wait_ex(
            self.tenant_view(rst, name), token)
        return self.absorb(rst, name, st), vals, err

    def prefetch(self, rst: RuntimeState, name: str, idx: jax.Array,
                 valid: jax.Array | None = None) -> RuntimeState:
        st = self.tenants[name].prefetch(self.tenant_view(rst, name),
                                         idx, valid)
        return self.absorb(rst, name, st)

    def flush(self, rst: RuntimeState,
              name: str | None = None) -> RuntimeState:
        """Flush one tenant's dirty lines, or every tenant's (name=None)."""
        names = [name] if name is not None else list(self.tenants)
        for n in names:
            st = self.tenants[n].flush(self.tenant_view(rst, n))
            rst = self.absorb(rst, n, st)
        return rst

    def drain(self, rst: RuntimeState
              ) -> Tuple[RuntimeState, Q.Completions]:
        """Drain the shared rings once (the ``drain="deferred"`` round
        barrier).  The returned :class:`~repro.core.queues.Completions`
        stream is priority-major and weighted-fair across tenants — the
        observable arbitration order.  A no-op on already-empty rings
        (per-op mode), so callers may drain unconditionally.

        The completion stream carries per-command ``status`` codes when
        the tenants' shared :class:`~repro.core.ssd.FaultModel` is
        enabled (all tenants share one ``ArrayOfSSDs``, so any tenant's
        model is *the* model)."""
        fault = next(iter(self.tenants.values())).ssd.fault
        qs, comps = Q.service_all(
            rst.queues, fault=fault if fault.enabled else None)
        return RuntimeState(cache=rst.cache, queues=qs,
                            metrics=rst.metrics,
                            tenant_metrics=rst.tenant_metrics,
                            storages=rst.storages), comps

    # -------------------------------------------------------------- metrics
    def tenant_summary(self, rst: RuntimeState, name: str) -> dict:
        return rst.tenant_metrics[self.tenant_ids[name]].summary()

    def assert_metrics_consistent(self, rst: RuntimeState,
                                  time_rtol: float = 1e-4) -> None:
        """The tentpole invariant: per-tenant metrics sum to the global
        counters — exactly for the integer-valued counters, to ``time_rtol``
        for the float time accumulators (summation order differs)."""
        import numpy as np
        from repro.core.metrics import ADDITIVE_FIELDS
        total = metrics_sum(rst.tenant_metrics)
        for f in ADDITIVE_FIELDS:
            a = np.asarray(jax.device_get(getattr(total, f)), np.float64)
            b = np.asarray(jax.device_get(getattr(rst.metrics, f)),
                           np.float64)
            if f.endswith("_time_s"):
                ok = np.allclose(a, b, rtol=time_rtol, atol=1e-12)
            else:
                ok = np.array_equal(a, b)
            if not ok:
                raise AssertionError(
                    f"tenant metrics do not sum to global for {f}: "
                    f"sum(tenants)={a}, global={b}")
