"""The request path's own instrumentation: the host-boundary counters of
``SimStorage``, the stage scopes of ``submit`` / ``wait_ex`` and the names
of the jitted ops.  Tiny shapes; the scopes are checked on lowered text
only, nothing is compiled for them."""
import sys
import threading

import jax.numpy as jnp
import numpy as np

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
    and 5 of them are live; the write-back ships a row per lane, none live
    (read-only traffic dirties nothing)."""
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
        "write_calls": 1, "write_rows": LANES, "write_live_rows": 0}


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
