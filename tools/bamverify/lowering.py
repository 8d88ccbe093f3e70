"""Produce lowered artifacts from the live op family (the JAX half).

The canonical configuration is deliberately small — lowering is about
*structure*, not throughput, and the compiled graph of ``submit`` at a
64-lane wavefront has the same scatter/while/callback anatomy as at
4096 — so the whole family lowers in well under a minute on CPU.

Canonical buckets: the first two of ``DEFAULT_BUCKETS``.  Larger buckets
change only shapes, not graph structure, and quadratically inflate
compile time of the coalescer's one-hot matmuls; the bucketed-sweep
check (BAM505) still exercises the full bucket table.
"""
from __future__ import annotations

import pathlib
import sys
from typing import Dict, Iterable, List, Tuple

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(REPO_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "src"))

import jax                                    # noqa: E402
import numpy as np                            # noqa: E402

from repro.core.bam_array import (            # noqa: E402
    BamArray, BamKVStore, BamRuntime, TenantSpec,
)
from tools.bamverify.rules import (           # noqa: E402
    ArtifactSpec, ArtifactStats, Finding, analyze_artifact,
    check_executable_count,
)

CANONICAL_BUCKETS: Tuple[int, ...] = (64, 256)

# Ragged batch sizes for the BAM505 bucketed sweep: none equal a bucket
# size, several map to the same bucket — a leak compiles one executable
# per size, a healthy bucketing at most one per bucket.
SWEEP_SIZES: Tuple[int, ...] = (3, 17, 40, 100, 200, 250)


def canonical_array() -> Tuple[BamArray, object]:
    """The small, fixed configuration every artifact is lowered at."""
    data = np.arange(4096, dtype=np.float32)
    return BamArray.build(data, block_elems=16, num_sets=16, ways=4,
                          num_queues=4, queue_depth=256)


def canonical_runtime() -> Tuple[BamRuntime, object]:
    """A two-tenant runtime for the per-tenant op family."""
    a = np.arange(1024, dtype=np.float32)
    b = np.arange(1024, dtype=np.float32) * 2
    return BamRuntime.build(
        [TenantSpec("a", a, block_elems=16),
         TenantSpec("b", b, block_elems=16)],
        num_sets=8, ways=4, num_queues=4, queue_depth=128)


def canonical_kv() -> Tuple[BamKVStore, object]:
    """A key-value store of 64 keys, one 16-element value a line, and its
    :class:`KVState`, for the store's token ops."""
    keys = np.arange(64, dtype=np.int32) * 7 + 1
    values = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    kv, table, st = BamKVStore.build(keys, values, capacity=128,
                                     num_sets=16, ways=4, num_queues=4,
                                     queue_depth=256)
    return kv, kv.state(st, table)


# The store's token ops are the array's submit/wait behind the index
# probe, so one bucket shows their structure.
KV_BUCKETS: Tuple[int, ...] = CANONICAL_BUCKETS[:1]


def lower_all() -> List[Tuple[ArtifactSpec, str]]:
    """Lower every registry: the canonical array's, runtime's and key-value
    store's op families."""
    arr, st = canonical_array()
    rt, rst = canonical_runtime()
    kv, kst = canonical_kv()
    return (lower_op_family(arr, st) + lower_op_family(rt, rst)
            + lower_op_family(kv, kst, KV_BUCKETS))


def lower_op_family(owner, state,
                    buckets: Iterable[int] = CANONICAL_BUCKETS,
                    ) -> List[Tuple[ArtifactSpec, str]]:
    """Lower + compile every ``kind="jit"`` entry of ``owner``'s
    ``iter_op_family()`` registry (donated variants included) at each
    canonical bucket; returns ``(spec, compiled_hlo_text)`` pairs."""
    out: List[Tuple[ArtifactSpec, str]] = []
    for entry in owner.iter_op_family():
        if entry.kind != "jit":
            continue
        variants = (False, True) if entry.donatable else (False,)
        for donate in variants:
            fn = entry.get(donate=donate)
            for n in buckets:
                args = entry.example_args(state, n)
                lowered = fn.lower(*args)
                # pre-optimization IR (the jaxpr/StableHLO side): an f64
                # op DCE'd by XLA still means live dtype creep in source
                traced_f64 = "f64" in lowered.as_text()
                txt = lowered.compile().as_text()
                declared = (len(jax.tree_util.tree_leaves(args[0]))
                            if donate else 0)
                name = entry.name + ("[donated]" if donate else "")
                out.append((ArtifactSpec(
                    op=name, bucket=n, donated=donate,
                    declared_donated=declared,
                    pure_all_hit=entry.pure_all_hit,
                    traced_f64=traced_f64), txt))
    return out


def collect_stats(artifacts: List[Tuple[ArtifactSpec, str]]
                  ) -> Dict[str, ArtifactStats]:
    return {spec.key: analyze_artifact(txt) for spec, txt in artifacts}


def sweep_bucketed(sizes: Iterable[int] = SWEEP_SIZES) -> List[Finding]:
    """Drive the ``kind="bucketed"`` registry entries over a ragged batch
    sweep on a FRESH canonical instance (its jit cache starts empty, so
    trace counts are exactly the executable count), then apply BAM505."""
    arr, st = canonical_array()
    findings: List[Finding] = []
    for entry in arr.iter_op_family():
        if entry.kind != "bucketed":
            continue
        drive = entry.get()
        s = st
        for n in sizes:
            s = drive(s, n)
        for key in entry.trace_keys:
            findings.extend(check_executable_count(
                f"{entry.name}[{key}]", len(arr.buckets),
                arr.trace_counts.get(key, 0)))
    return findings
