"""I/O accounting — hit rates, queue depths and I/O amplification (paper §II-B).

I/O amplification = bytes moved from the storage tier / bytes the compute
actually consumed.  The paper's headline data-analytics result is that the
CPU-centric model ships whole columns (6.34x-10.36x amplification on the
taxi queries) while BaM ships cache lines on demand.

Per-device channels (paper §IV-A): every counter that touches the storage
tier also has a ``(n_devices,)`` per-device breakdown, so device skew —
one straggler SSD gating the wavefront — is observable instead of being
averaged away.  Device time is split by direction (``read_time_s`` vs
``write_time_s``): demand reads and readahead charge the read clock,
write-backs charge the write clock, and ``sim_time_s`` stays their sum, so
``read_iops`` no longer dilutes as soon as write-backs or prefetch are
active.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.utils import pytree_dataclass


@pytree_dataclass
class IOMetrics:
    requests: jax.Array          # element-level requests issued by compute
    bytes_requested: jax.Array   # bytes the compute consumed (useful bytes)
    hits: jax.Array              # cache-line hits (post-coalescing)
    misses: jax.Array            # cache-line misses -> storage reads
    bytes_from_storage: jax.Array
    write_ops: jax.Array
    bytes_to_storage: jax.Array
    doorbells: jax.Array         # batched ring-tail updates (1 per queue per round)
    dropped: jax.Array           # commands rejected by ring back-pressure
    sim_time_s: jax.Array        # simulated device service time accumulated
    read_time_s: jax.Array       # read-direction share (demand + readahead)
    write_time_s: jax.Array      # write-direction share (write-backs, flush)
    max_queue_depth: jax.Array   # high-watermark of in-flight requests
    prefetch_issued: jax.Array   # cache lines fetched speculatively (readahead)
    prefetch_hits: jax.Array     # demand line-hits served by a prefetched line
    # Async submission-window accounting (submit/wait tokens).
    tokens_submitted: jax.Array  # IOTokens issued (submits with >=1 valid lane)
    tokens_waited: jax.Array     # IOTokens completed by wait()
    tokens_in_flight: jax.Array  # running outstanding-token count (+1/-1)
    cross_op_coalesced: jax.Array  # line requests merged with a *pending*
    #                                token's in-flight fetch (saved commands)
    max_tokens_in_flight: jax.Array  # high-watermark of the in-flight window
    # Fault-injection accounting (all zero with the FaultModel disabled).
    transient_errors: jax.Array  # attempt-level failures (incl. recovered)
    retries: jax.Array           # command re-issues (bounded by retry_budget)
    failed_commands: jax.Array   # commands retired with an error status
    degraded_reads: jax.Array    # element lanes redeemed with error_mask set
    # Key-value index accounting (BamKVStore; zero on the array path).
    kv_lookups: jax.Array        # valid keys looked up in the device index
    kv_probe_slots: jax.Array    # index slots read by those lookups
    # Per-device channel breakdown, all shape (n_devices,).
    dev_reads: jax.Array         # lines fetched per device (demand + readahead)
    dev_writes: jax.Array        # lines written back per device
    dev_bytes: jax.Array         # bytes moved per device (both directions)
    dev_time_s: jax.Array        # per-device busy time (the straggler signal)
    dev_errors: jax.Array        # failed commands per device
    dev_max_depth: jax.Array     # per-device in-flight high-watermark, int32

    @staticmethod
    def zeros(n_devices: int = 1) -> "IOMetrics":
        # float64 under x64, float32 otherwise (the canonical float dtype)
        ftype = jax.dtypes.canonicalize_dtype(jnp.float64)
        f = lambda: jnp.zeros((), ftype)
        i = lambda: jnp.zeros((), jnp.int32)
        return IOMetrics(
            requests=f(), bytes_requested=f(), hits=f(), misses=f(),
            bytes_from_storage=f(), write_ops=f(), bytes_to_storage=f(),
            doorbells=f(), dropped=f(),
            sim_time_s=f(), read_time_s=f(), write_time_s=f(),
            max_queue_depth=i(),
            prefetch_issued=f(), prefetch_hits=f(),
            tokens_submitted=f(), tokens_waited=f(), tokens_in_flight=f(),
            cross_op_coalesced=f(), max_tokens_in_flight=i(),
            transient_errors=f(), retries=f(), failed_commands=f(),
            degraded_reads=f(), kv_lookups=f(), kv_probe_slots=f(),
            dev_reads=jnp.zeros((n_devices,), ftype),
            dev_writes=jnp.zeros((n_devices,), ftype),
            dev_bytes=jnp.zeros((n_devices,), ftype),
            dev_time_s=jnp.zeros((n_devices,), ftype),
            dev_errors=jnp.zeros((n_devices,), ftype),
            dev_max_depth=jnp.zeros((n_devices,), jnp.int32),
        )

    @property
    def n_devices(self) -> int:
        return int(self.dev_reads.shape[0])

    # Derived quantities (host-side, after device_get) -------------------
    def amplification(self) -> float:
        br = float(self.bytes_requested)
        return float(self.bytes_from_storage) / br if br > 0 else 0.0

    def hit_rate(self) -> float:
        tot = float(self.hits) + float(self.misses)
        return float(self.hits) / tot if tot > 0 else 0.0

    def read_iops(self) -> float:
        """Lines fetched (demand + readahead) per second of *read* time.

        Write-backs and their service time are excluded from both numerator
        and denominator, so background write traffic no longer deflates the
        reported read throughput.  Falls back to ``sim_time_s`` for states
        (e.g. external accumulators) that never split the clocks.
        """
        fetched = float(self.misses) + float(self.prefetch_issued)
        t = float(self.read_time_s)
        if t <= 0.0:
            t = float(self.sim_time_s)
        return fetched / t if t > 0 else 0.0

    def prefetch_accuracy(self) -> float:
        """Fraction of speculatively fetched lines later used by demand."""
        issued = float(self.prefetch_issued)
        return float(self.prefetch_hits) / issued if issued > 0 else 0.0

    def straggler_gap(self) -> float:
        """Max over mean of per-device busy time (1.0 = perfectly balanced).

        The Fig. 7 skew observable: a uniform stream keeps this near 1;
        a Zipfian stream concentrates load on few channels and the gap
        grows with it.  0.0 when no device time has been charged.
        """
        t = jax.device_get(self.dev_time_s)
        mean = float(t.mean())
        return float(t.max()) / mean if mean > 0 else 0.0

    def summary(self) -> dict:
        return {
            "requests": float(self.requests),
            "bytes_requested": float(self.bytes_requested),
            "hits": float(self.hits),
            "misses": float(self.misses),
            "hit_rate": self.hit_rate(),
            "bytes_from_storage": float(self.bytes_from_storage),
            "write_ops": float(self.write_ops),
            "bytes_to_storage": float(self.bytes_to_storage),
            "amplification": self.amplification(),
            "doorbells": float(self.doorbells),
            "dropped": float(self.dropped),
            "sim_time_s": float(self.sim_time_s),
            "read_time_s": float(self.read_time_s),
            "write_time_s": float(self.write_time_s),
            "read_iops": self.read_iops(),
            "max_queue_depth": int(self.max_queue_depth),
            "prefetch_issued": float(self.prefetch_issued),
            "prefetch_hits": float(self.prefetch_hits),
            "prefetch_accuracy": self.prefetch_accuracy(),
            "tokens_submitted": float(self.tokens_submitted),
            "tokens_waited": float(self.tokens_waited),
            "tokens_in_flight": float(self.tokens_in_flight),
            "cross_op_coalesced": float(self.cross_op_coalesced),
            "max_tokens_in_flight": int(self.max_tokens_in_flight),
            "transient_errors": float(self.transient_errors),
            "retries": float(self.retries),
            "failed_commands": float(self.failed_commands),
            "degraded_reads": float(self.degraded_reads),
            "kv_lookups": float(self.kv_lookups),
            "kv_probe_slots": float(self.kv_probe_slots),
            "n_devices": self.n_devices,
            "dev_reads": [float(x) for x in jax.device_get(self.dev_reads)],
            "dev_writes": [float(x) for x in jax.device_get(self.dev_writes)],
            "dev_bytes": [float(x) for x in jax.device_get(self.dev_bytes)],
            "dev_time_s": [float(x) for x in jax.device_get(self.dev_time_s)],
            "dev_errors": [float(x) for x in jax.device_get(self.dev_errors)],
            "dev_max_depth": [int(x)
                              for x in jax.device_get(self.dev_max_depth)],
            "straggler_gap": self.straggler_gap(),
        }


# Watermark (high-water) fields combine by max; everything else is an
# additive counter.  The shared-runtime facade relies on this split: it
# accumulates each tenant op's *delta* into the global IOMetrics, and the
# invariant "additive tenant counters sum exactly to the global counters"
# is what the multi-tenant tests (and the mixed_tenants gate) assert.
WATERMARK_FIELDS = ("max_queue_depth", "dev_max_depth",
                    "max_tokens_in_flight")
ADDITIVE_FIELDS = tuple(
    f for f in IOMetrics.__dataclass_fields__ if f not in WATERMARK_FIELDS)


def metrics_delta(new: IOMetrics, old: IOMetrics) -> IOMetrics:
    """Per-op increment: additive fields subtract, watermarks carry ``new``."""
    kw = {f: getattr(new, f) - getattr(old, f) for f in ADDITIVE_FIELDS}
    kw.update({f: getattr(new, f) for f in WATERMARK_FIELDS})
    return IOMetrics(**kw)


def metrics_accumulate(acc: IOMetrics, delta: IOMetrics) -> IOMetrics:
    """Fold a :func:`metrics_delta` into an accumulator (sum / max)."""
    kw = {f: getattr(acc, f) + getattr(delta, f) for f in ADDITIVE_FIELDS}
    kw.update({f: jnp.maximum(getattr(acc, f), getattr(delta, f))
               for f in WATERMARK_FIELDS})
    return IOMetrics(**kw)


def metrics_sum(parts) -> IOMetrics:
    """Combine per-tenant metrics into the global view (sum / max)."""
    parts = list(parts)
    acc = parts[0]
    for p in parts[1:]:
        acc = metrics_accumulate(acc, p)
    return acc


def recheck_token_watermark(mt: IOMetrics) -> IOMetrics:
    """Re-arm ``max_tokens_in_flight`` against the *current* window.

    Every path that changes ``tokens_in_flight`` must re-check the
    watermark, not just ``submit``: a ``flush()`` that retires tokens
    mid-window, or a shared-runtime ``absorb`` that sums several tenants'
    windows, can otherwise leave the high-water mark below a level the
    window actually reached.
    """
    return dataclasses.replace(
        mt, max_tokens_in_flight=jnp.maximum(
            mt.max_tokens_in_flight,
            mt.tokens_in_flight.astype(jnp.int32)))
