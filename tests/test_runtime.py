"""Multi-tenant BamRuntime: sharing, isolation, and metric accounting."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BamKVStore, BamRuntime, TenantSpec, in_flight_per_tenant, metrics_sum,
)

BE = 8      # shared cache line geometry (elements)


def _specs(n=2, size=256, **kw):
    return [TenantSpec(f"t{i}", (1000 * i) + np.arange(size,
                                                       dtype=np.float32),
                       block_elems=BE, **kw)
            for i in range(n)]


def build(n=2, size=256, num_sets=8, ways=4, isolation="partitioned", **kw):
    return BamRuntime.build(_specs(n, size), num_sets=num_sets, ways=ways,
                            isolation=isolation, **kw)


# ------------------------------------------------------------- correctness --
@pytest.mark.parametrize("isolation", ["partitioned", "shared"])
def test_overlapping_keyspaces_read_their_own_data(isolation):
    """Both tenants read blocks 0..N of *their own* array — the shared
    cache must namespace the identical block keys."""
    rt, rst = build(isolation=isolation)
    idx = jnp.arange(64, dtype=jnp.int32)
    # interleave so both tenants' lines are simultaneously resident
    for _ in range(3):
        v0, rst = rt.read(rst, "t0", idx)
        v1, rst = rt.read(rst, "t1", idx)
        np.testing.assert_array_equal(np.asarray(v0), np.arange(64))
        np.testing.assert_array_equal(np.asarray(v1), 1000 + np.arange(64))


@pytest.mark.parametrize("isolation", ["partitioned", "shared"])
def test_writes_land_in_the_right_storage(isolation):
    rt, rst = build(isolation=isolation)
    rst = rt.write(rst, "t0", jnp.asarray([5], jnp.int32),
                   jnp.asarray([-1.0]))
    rst = rt.write(rst, "t1", jnp.asarray([5], jnp.int32),
                   jnp.asarray([-2.0]))
    rst = rt.flush(rst)
    # dirty lines fully flushed for every tenant
    assert not bool(rst.cache.dirty.any())
    v0, rst = rt.read(rst, "t0", jnp.asarray([5], jnp.int32))
    v1, rst = rt.read(rst, "t1", jnp.asarray([5], jnp.int32))
    assert float(v0[0]) == -1.0 and float(v1[0]) == -2.0
    # and the storage tiers themselves diverge correctly
    s0 = np.asarray(rt.array("t0").storage.data).reshape(-1)
    s1 = np.asarray(rt.array("t1").storage.data).reshape(-1)
    assert s0[5] == -1.0 and s1[5] == -2.0


def test_flush_one_tenant_leaves_other_tenants_dirty_lines():
    rt, rst = build(isolation="shared")
    rst = rt.write(rst, "t0", jnp.asarray([0], jnp.int32), jnp.asarray([9.]))
    rst = rt.write(rst, "t1", jnp.asarray([0], jnp.int32), jnp.asarray([8.]))
    assert int(np.asarray(rst.cache.dirty).sum()) == 2
    rst = rt.flush(rst, "t0")
    dirty = np.asarray(rst.cache.dirty)
    owner = np.asarray(rst.cache.owner)
    assert int(dirty.sum()) == 1
    assert (owner[dirty] == 1).all(), "flush('t0') touched t1's dirty line"
    # t1's write still reaches its storage on its own flush
    rst = rt.flush(rst, "t1")
    assert not bool(rst.cache.dirty.any())
    assert np.asarray(rt.array("t1").storage.data).reshape(-1)[0] == 8.0


def test_partitioned_streaming_tenant_cannot_evict_neighbour():
    """Stream 10x the cache through t1; t0's resident lines survive and
    keep hitting (the benchmarks/mixed_tenants.py property in miniature)."""
    rt, rst = build(n=2, size=4096, num_sets=4, ways=4)
    hot = jnp.arange(32, dtype=jnp.int32)         # 4 blocks for t0
    v0, rst = rt.read(rst, "t0", hot)             # warm t0's partition
    h0 = float(rst.tenant_metrics[0].hits)
    for start in range(0, 4096, 64):
        idx = start + jnp.arange(64, dtype=jnp.int32)
        _, rst = rt.read(rst, "t1", idx)          # the adversarial scan
    v0b, rst = rt.read(rst, "t0", hot)
    np.testing.assert_array_equal(np.asarray(v0b), np.arange(32))
    hits_gained = float(rst.tenant_metrics[0].hits) - h0
    assert hits_gained == 4.0, "t0's hot lines were evicted by t1's scan"
    rt.assert_metrics_consistent(rst)


# ---------------------------------------------------------------- metrics --
def test_tenant_metrics_sum_to_global():
    rt, rst = build(n=3, ways=6)
    idx = jnp.arange(48, dtype=jnp.int32)
    for rnd in range(3):
        for name in ("t0", "t1", "t2"):
            _, rst = rt.read(rst, name, idx + 8 * rnd)
    rst = rt.write(rst, "t1", jnp.asarray([3, 9], jnp.int32),
                   jnp.asarray([1.0, 2.0]))
    rst = rt.prefetch(rst, "t2", jnp.arange(64, 96, dtype=jnp.int32))
    rst = rt.flush(rst)
    rt.assert_metrics_consistent(rst)
    # integer counters match exactly
    total = metrics_sum(rst.tenant_metrics)
    for f in ("requests", "hits", "misses", "write_ops", "doorbells",
              "dropped", "prefetch_issued"):
        assert float(getattr(total, f)) == float(getattr(rst.metrics, f)), f
    # and the queue pool agrees with the metrics layer on drops
    assert float(rst.metrics.dropped) \
        == float(np.asarray(rst.queues.tenant_dropped).sum())


def test_queue_accounting_per_tenant_after_ops():
    rt, rst = build(n=2)
    _, rst = rt.read(rst, "t0", jnp.arange(32, dtype=jnp.int32))
    _, rst = rt.read(rst, "t1", jnp.arange(16, dtype=jnp.int32))
    qs = rst.queues
    enq = np.asarray(qs.tenant_enqueued)
    comp = np.asarray(qs.tenant_completed)
    assert np.array_equal(enq, comp)            # every read drains its ring
    assert enq[0] == 4 and enq[1] == 2          # 32/8 and 16/8 lines
    assert np.asarray(in_flight_per_tenant(qs)).sum() == 0


# ------------------------------------------------------------ composition --
def test_kv_store_rides_a_runtime_tenant():
    keys = np.arange(64, dtype=np.int32)
    values = np.arange(64 * BE, dtype=np.float32).reshape(64, BE)
    table, store_vals, cap, probes = BamKVStore.build_table(
        keys, values, capacity=128)
    specs = [TenantSpec("kv", store_vals, block_elems=BE),
             TenantSpec("other", np.zeros(64, np.float32), block_elems=BE)]
    rt, rst = BamRuntime.build(specs, num_sets=8, ways=4)
    kv = BamKVStore(array=rt.array("kv"), capacity=cap, value_elems=BE,
                    probes=probes)
    tj = jnp.asarray(table)
    st = rt.tenant_view(rst, "kv")
    vals, found, st = kv.lookup(st, tj, jnp.asarray([0, 17, 63, 99],
                                                    jnp.int32))
    rst = rt.absorb(rst, "kv", st)
    assert bool(found[0]) and bool(found[1]) and bool(found[2])
    assert not bool(found[3])
    np.testing.assert_array_equal(np.asarray(vals[1]), values[17])
    rt.assert_metrics_consistent(rst)


def test_int_tenant_roundtrips_through_float_cache():
    """An int32 tenant (graph edges) shares the float32 cache exactly."""
    edges = np.arange(512, dtype=np.int32)[::-1].copy()
    specs = [TenantSpec("edges", edges, block_elems=BE),
             TenantSpec("col", np.zeros(64, np.float32), block_elems=BE)]
    rt, rst = BamRuntime.build(specs, num_sets=8, ways=4)
    idx = jnp.arange(512, dtype=jnp.int32)
    v, rst = rt.read(rst, "edges", idx)
    assert v.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(v), edges)
    v2, rst = rt.read(rst, "edges", idx)     # now from cache lines
    np.testing.assert_array_equal(np.asarray(v2), edges)


# ------------------------------------------------------------- validation --
def test_build_rejects_bad_configs():
    with pytest.raises(ValueError):
        BamRuntime.build([], num_sets=4)
    with pytest.raises(ValueError):
        BamRuntime.build(_specs(2), num_sets=4, ways=4,
                         isolation="exclusive")
    with pytest.raises(ValueError):            # quotas exceed ways
        BamRuntime.build([TenantSpec("a", np.zeros(8, np.float32),
                                     block_elems=BE, ways=3),
                          TenantSpec("b", np.zeros(8, np.float32),
                                     block_elems=BE, ways=3)],
                         num_sets=4, ways=4)
    with pytest.raises(ValueError):            # mismatched line geometry
        BamRuntime.build([TenantSpec("a", np.zeros(8, np.float32),
                                     block_elems=4),
                          TenantSpec("b", np.zeros(8, np.float32),
                                     block_elems=8)],
                         num_sets=4, ways=4)
    with pytest.raises(ValueError):            # duplicate names
        BamRuntime.build([TenantSpec("a", np.zeros(8, np.float32),
                                     block_elems=BE),
                          TenantSpec("a", np.zeros(8, np.float32),
                                     block_elems=BE)],
                         num_sets=4, ways=4)


def test_way_quotas_partition_the_cache():
    rt, _ = BamRuntime.build(
        [TenantSpec("a", np.zeros(64, np.float32), block_elems=BE, ways=3),
         TenantSpec("b", np.zeros(64, np.float32), block_elems=BE),
         TenantSpec("c", np.zeros(64, np.float32), block_elems=BE)],
        num_sets=4, ways=8)
    ctxs = {n: rt.array(n).tenant_ctx for n in ("a", "b", "c")}
    spans = sorted((c.way_lo, c.way_hi) for c in ctxs.values())
    assert spans[0][0] == 0 and spans[-1][1] == 8
    for (lo1, hi1), (lo2, hi2) in zip(spans, spans[1:]):
        assert hi1 == lo2                       # contiguous, disjoint
    assert ctxs["a"].way_hi - ctxs["a"].way_lo == 3


def test_bfs_on_runtime_tenant_matches_oracle():
    """A full BFS traversal through BamGraph.from_runtime returns the same
    depths as the host oracle while another tenant shares the cache."""
    from repro.graph.analytics import BamGraph, bfs, bfs_oracle, random_graph

    indptr, dst = random_graph(128, 4.0, seed=3)
    specs = [TenantSpec("bfs", dst.astype(np.int32), block_elems=BE),
             TenantSpec("noise", np.arange(256, dtype=np.float32),
                        block_elems=BE)]
    rt, rst = BamRuntime.build(specs, num_sets=8, ways=4)
    # neighbour traffic first, so the shared cache is not pristine
    _, rst = rt.read(rst, "noise", jnp.arange(64, dtype=jnp.int32))
    g = BamGraph.from_runtime(rt, rst, "bfs", indptr)
    depth, g_state = bfs(g, source=0)
    rst = rt.absorb(rst, "bfs", g_state)
    np.testing.assert_array_equal(depth, bfs_oracle(indptr, dst, 0))
    # neighbour still reads its own data afterwards
    v, rst = rt.read(rst, "noise", jnp.arange(64, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(v), np.arange(64))
    rt.assert_metrics_consistent(rst)


def test_scan_column_runtime_sums_and_isolates():
    from repro.analytics.taxi import scan_column_runtime

    col = np.arange(512, dtype=np.float32)
    specs = [TenantSpec("scan", col, block_elems=BE),
             TenantSpec("hot", 7 * np.ones(64, np.float32), block_elems=BE)]
    rt, rst = BamRuntime.build(specs, num_sets=4, ways=4)
    vh, rst = rt.read(rst, "hot", jnp.arange(32, dtype=jnp.int32))
    total, rst, nxt = scan_column_runtime(rt, rst, "scan", n_rows=512,
                                          wavefront=128)
    assert total == float(col.sum())
    assert nxt == 0                               # full wrap
    h_before = float(rst.tenant_metrics[1].hits)
    vh2, rst = rt.read(rst, "hot", jnp.arange(32, dtype=jnp.int32))
    assert float(rst.tenant_metrics[1].hits) - h_before == 4.0, \
        "scan tenant evicted the partitioned hot tenant"
    rt.assert_metrics_consistent(rst)


def test_deferred_drain_mixes_tenants_in_one_stream():
    """drain='deferred': both tenants' commands coexist in the rings and
    one drain retires them weighted-fair; values and conservation are
    unchanged from per-op mode."""
    rt, rst = BamRuntime.build(_specs(2), num_sets=8, ways=4,
                               drain="deferred")
    idx = jnp.arange(64, dtype=jnp.int32)
    v0, rst = rt.read(rst, "t0", idx)
    v1, rst = rt.read(rst, "t1", idx)
    np.testing.assert_array_equal(np.asarray(v0), np.arange(64))
    np.testing.assert_array_equal(np.asarray(v1), 1000 + np.arange(64))
    # both tenants pending simultaneously — the situation per-op mode
    # can never produce
    pend = np.asarray(in_flight_per_tenant(rst.queues))
    assert pend[0] == 8 and pend[1] == 8, pend
    rst, comps = rt.drain(rst)
    ten = np.asarray(comps.tenant)[np.asarray(comps.valid)]
    assert len(ten) == 16
    # equal weights: 1:1 interleave across the drained stream
    for k in range(1, 17):
        assert abs(int((ten[:k] == 0).sum())
                   - int((ten[:k] == 1).sum())) <= 1, ten.tolist()
    qs = rst.queues
    assert np.array_equal(np.asarray(qs.tenant_enqueued),
                          np.asarray(qs.tenant_completed))
    assert np.asarray(in_flight_per_tenant(qs)).sum() == 0
    rt.assert_metrics_consistent(rst)


def test_deferred_and_per_op_modes_agree_on_values():
    idx = jnp.asarray(np.random.default_rng(5).integers(0, 256, 40),
                      jnp.int32)
    outs = {}
    for mode in ("per_op", "deferred"):
        rt, rst = BamRuntime.build(_specs(2), num_sets=8, ways=4, drain=mode)
        v0, rst = rt.read(rst, "t0", idx)
        rst = rt.write(rst, "t1", idx[:8], jnp.arange(8, dtype=jnp.float32))
        rst = rt.flush(rst)
        rst, _ = rt.drain(rst)
        v1, rst = rt.read(rst, "t1", idx)
        outs[mode] = (np.asarray(v0), np.asarray(v1))
        rt.assert_metrics_consistent(rst)
    np.testing.assert_array_equal(outs["per_op"][0], outs["deferred"][0])
    np.testing.assert_array_equal(outs["per_op"][1], outs["deferred"][1])


def test_build_rejects_integers_beyond_float_cache_range():
    big = np.asarray([0, 1 << 25], np.int32)       # > 2^24: not f32-exact
    with pytest.raises(ValueError, match="exact-integer range"):
        BamRuntime.build([TenantSpec("edges", big, block_elems=BE)],
                         num_sets=4, ways=2)
    # a wider cache dtype (or values in range) is accepted
    rt, rst = BamRuntime.build(
        [TenantSpec("edges", np.asarray([0, (1 << 24)], np.int32),
                    block_elems=BE)], num_sets=4, ways=2)
    v, rst = rt.read(rst, "edges", jnp.asarray([1], jnp.int32))
    assert int(v[0]) == 1 << 24


def test_scan_column_runtime_non_divisible_tail():
    from repro.analytics.taxi import scan_column_runtime

    col = np.arange(500, dtype=np.float32)         # 500 % 128 != 0
    rt, rst = BamRuntime.build([TenantSpec("scan", col, block_elems=BE)],
                               num_sets=4, ways=2)
    total, rst, nxt = scan_column_runtime(rt, rst, "scan", n_rows=500,
                                          wavefront=128)
    assert total == float(col.sum())               # no head double-count
    assert nxt == 0
