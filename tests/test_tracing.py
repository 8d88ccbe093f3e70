"""The request path's own instrumentation: the host-boundary counters of
``SimStorage``, the stage scopes of ``submit`` / ``wait_ex`` and the names
of the jitted ops.  Tiny shapes; the scopes are checked on lowered text
only, nothing is compiled for them."""
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BamArray, IORequest, PrefetchConfig

BLOCK = 8
LANES = 16

SUBMIT_STAGES = ("coalesce", "probe_allocate", "readahead", "write_back",
                 "enqueue", "accounting")
WAIT_STAGES = ("drain", "probe", "fetch", "fill", "gather", "release",
               "accounting")


def _tiny():
    data = np.arange(64 * BLOCK, dtype=np.float32)
    return BamArray.build(data, BLOCK, num_sets=8, ways=2, num_queues=2,
                          queue_depth=32, backend="sim")


def test_storage_counters_count_rows_and_live_rows():
    """One cold read of 5 distinct lines: the fetch ships a row per lane
    and 5 of them are live; read-only traffic dirties nothing, so the
    write-back makes no host call at all."""
    arr, st = _tiny()
    blocks = np.array([0, 0, 3, 7, 7, 7, 9, 12] * 2)
    idx = jnp.asarray(blocks * BLOCK + np.arange(LANES) % BLOCK, jnp.int32)
    assert arr.storage.counters() == dict.fromkeys(
        ("fetch_calls", "fetch_rows", "fetch_live_rows",
         "write_calls", "write_rows", "write_live_rows"), 0)

    vals, st = arr.read_jit()(st, idx)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(idx))
    assert int(st.metrics.misses) == 5
    assert arr.storage.counters() == {
        "fetch_calls": 1, "fetch_rows": LANES, "fetch_live_rows": 5,
        "write_calls": 0, "write_rows": 0, "write_live_rows": 0}


RA_WINDOW = 4
# Two rounds of 16 lines each whose gaps (1, 2, 3, ...) hold no stride the
# readahead detector trusts, and stride-1 scans of 4 lines a round.
_SCATTERED = np.array([32, 33, 35, 38, 39, 41, 44, 45,
                       47, 50, 51, 53, 56, 57, 59, 62])
_ROUNDS = {
    "demand": [_SCATTERED, np.setdiff1d(np.arange(32, 64), _SCATTERED),
               _SCATTERED],
    "readahead": [np.repeat(np.arange(b, b + 4), 4) for b in range(32, 64, 4)],
}
_ROUNDS["prefetch"] = _ROUNDS["demand"]


@pytest.fixture(scope="module")
def readahead_array():
    """One readahead-enabled array for every case, so that each executable
    compiles once; a case restores the bytes and starts a fresh state."""
    arr, _ = _tiny()
    arr = arr.with_prefetch(PrefetchConfig(enabled=True, window=RA_WINDOW))
    return arr, arr.storage.data.copy()


def _round(arr, st, req):
    st, tok = arr.submit_jit(donate=True)(st, req)
    return arr.wait_jit(donate=True)(st, tok)


@pytest.mark.parametrize("path", ["demand", "prefetch", "readahead"])
def test_write_back_calls_host_only_when_a_dirty_line_is_evicted(
        readahead_array, path):
    """Warm read-only rounds make no write-back call.  After writes, every
    round that evicts dirty lines makes write calls whose live rows are
    exactly the lines it evicted, and those lines reach storage byte for
    byte; a round that evicts none makes no call."""
    arr, original = readahead_array
    arr.storage.data[...] = original
    _, st = _tiny()
    kind = IORequest.prefetch if path == "prefetch" else IORequest.read
    rounds = [kind(jnp.asarray(b * BLOCK, jnp.int32)) for b in _ROUNDS[path]]

    c0 = arr.storage.counters()
    for req in rounds[:2]:
        st, _ = _round(arr, st, req)
    c1 = arr.storage.counters()
    assert c1["write_calls"] == c0["write_calls"]
    assert c1["fetch_calls"] > c0["fetch_calls"]

    expected = original.reshape(-1).copy()
    for lo in (0, 16):
        idx = np.arange(lo, lo + 16) * BLOCK + np.arange(16) % BLOCK
        expected[idx] = -(idx + 1.0)
        st, _ = _round(arr, st, IORequest.write(
            jnp.asarray(idx, jnp.int32), jnp.asarray(expected[idx])))
    before = arr.storage.data.copy()
    c0, w0 = arr.storage.counters(), int(st.metrics.write_ops)

    ra_calls = 0
    for req in rounds:
        c1, w1 = arr.storage.counters(), int(st.metrics.write_ops)
        st, _ = _round(arr, st, req)
        c2, w2 = arr.storage.counters(), int(st.metrics.write_ops)
        assert (c2["write_calls"] > c1["write_calls"]) == (w2 > w1)
        assert c2["write_live_rows"] - c1["write_live_rows"] == w2 - w1
        ra_calls += (c2["write_rows"] - c1["write_rows"]) % LANES == RA_WINDOW
    n_wb = int(st.metrics.write_ops) - w0
    assert n_wb > 0
    assert arr.storage.counters()["write_live_rows"] - c0["write_live_rows"] \
        == n_wb
    assert (ra_calls > 0) == (path == "readahead")

    changed = np.flatnonzero(np.any(arr.storage.data != before, axis=1))
    assert changed.size == n_wb
    np.testing.assert_array_equal(
        arr.storage.data[changed], expected.reshape(before.shape)[changed])
    idx = np.arange(32) * BLOCK + np.arange(32) % BLOCK
    for half in (idx[:16], idx[16:]):
        st, vals = _round(arr, st,
                          IORequest.read(jnp.asarray(half, jnp.int32)))
        np.testing.assert_array_equal(np.asarray(vals), expected[half])


def test_submit_and_wait_lower_with_stage_scopes_and_op_names():
    """Readahead on, so that every stage holds operations."""
    arr, st = _tiny()
    arr = arr.with_prefetch(PrefetchConfig(enabled=True))
    req = IORequest.read(jnp.arange(LANES, dtype=jnp.int32) * 3)
    lowered = arr.submit_jit().lower(st, req)
    submit = lowered.as_text(debug_info=True)
    wait = (arr.wait_jit(guard=False).lower(*lowered.out_info)
            .as_text(debug_info=True))
    for stage in SUBMIT_STAGES:
        assert f"jit(bam_submit)/{stage}/" in submit, stage
    for stage in WAIT_STAGES:
        assert f"jit(bam_wait)/{stage}/" in wait, stage
    assert arr.trace_counts == {"submit": 1, "wait": 1}


def test_storage_counters_lose_no_update_across_threads():
    """The callbacks may run on several host threads at once."""
    arr, _ = _tiny()
    keys = np.array([3, -1, 5, -1], np.int32)
    lines = np.zeros((4, BLOCK), np.float32)
    threads, calls = 8, 200
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def hammer():
            for _ in range(calls):
                arr.storage._host_fetch(keys)
                arr.storage._host_write(keys, lines)

        workers = [threading.Thread(target=hammer) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(switch)
    n = threads * calls
    assert arr.storage.counters() == {
        "fetch_calls": n, "fetch_rows": 4 * n, "fetch_live_rows": 2 * n,
        "write_calls": n, "write_rows": 4 * n, "write_live_rows": 2 * n}
