"""YCSB workload C on ``BamKVStore`` through the donated token pair, at a CPU
size: FNV-hashed keys (``bench/ycsb.py``, YCSB's ``insertorder=hashed``),
scrambled-zipfian reads, two tokens in flight, every value checked against
a plain dict of records."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# appended, so that no module of the program is shadowed by a bench/ one
sys.path.append(str(pathlib.Path(__file__).resolve().parents[1] / "bench"))
import ycsb  # noqa: E402

from repro.core import BamArray, BamKVStore, IORequest  # noqa: E402

N_KEYS, CAP, VE = 1 << 12, 1 << 13, 8      # load 0.5
# YCSB workload C's request distribution, over N_KEYS records
WORKLOAD_C = dict(json.loads((pathlib.Path(__file__).resolve().parents[1]
                              / "bench/configs/ycsb_c_1kb_zipf.json")
                             .read_text()), record_count=N_KEYS)
KEYS_PER_TOKEN, TOKENS, IN_FLIGHT = 64, 6, 2


@pytest.fixture(scope="module")
def built():
    keys = ycsb.record_key(np.arange(N_KEYS))
    values = np.random.default_rng(1).standard_normal(
        (N_KEYS, VE)).astype(np.float32)
    kv, table, st = BamKVStore.build(keys, values, capacity=CAP,
                                     num_sets=16, ways=4, num_queues=4,
                                     queue_depth=256)
    return dict(keys=keys, values=values, kv=kv, table=np.asarray(table),
                kst=kv.state(st, table))


def _token_keys(k: int, keys: np.ndarray) -> np.ndarray:
    """Scrambled-zipfian keys, a few absent keys and a -1 pad lane."""
    ids = np.random.default_rng([5, k]).integers(0, ycsb.N_ITEMS,
                                                  KEYS_PER_TOKEN)
    out = ycsb.request_keys(ids, WORKLOAD_C)
    absent = ycsb.record_key(np.arange(N_KEYS, N_KEYS + 3) + k)
    assert not np.isin(absent, keys).any()
    out[:3] = absent
    out[3] = -1
    return out


@pytest.fixture(scope="module")
def served(built):
    """Run TOKENS lookups with IN_FLIGHT outstanding; returns the requests,
    what came back, and the metrics before and after."""
    kv, kst = built["kv"], built["kst"]
    submit, wait = kv.submit_jit(donate=True), kv.wait_jit(donate=True)
    m0 = jax.device_get(kst.bam.metrics)
    reqs = [_token_keys(k, built["keys"]) for k in range(TOKENS)]
    pending, out = [], []
    for req in reqs:
        kst, tok = submit(kst, jnp.asarray(req))
        pending.append(tok)
        if len(pending) == IN_FLIGHT:
            tok = pending.pop(0)
            kst, vals = wait(kst, tok)
            out.append((np.asarray(vals), np.asarray(tok.found)))
    for tok in pending:
        kst, vals = wait(kst, tok)
        out.append((np.asarray(vals), np.asarray(tok.found)))
    return reqs, out, m0, jax.device_get(kst.bam.metrics)


def test_zipfian_lookups_match_a_dict_of_records(built, served):
    records = dict(zip(built["keys"].tolist(), built["values"]))
    reqs, out, _, _ = served
    assert len(out) == TOKENS
    for req, (vals, found) in zip(reqs, out):
        want_found = np.asarray([k in records for k in req.tolist()])
        np.testing.assert_array_equal(found, want_found)
        want = np.stack([records.get(k, np.zeros(VE, np.float32))
                         for k in req.tolist()])
        np.testing.assert_array_equal(vals.view(np.uint32),
                                      want.view(np.uint32))
    assert all(f[4:].all() and not f[:4].any() for _, f in out)


def test_bound_is_longest_displacement_plus_one(built):
    kv, table = built["kv"], built["table"]
    slots = np.flatnonzero(table != -1)
    home = BamKVStore._hash_host(table[slots], CAP).astype(np.int64)
    disp = (slots - home) % CAP
    assert kv.probes == disp.max() + 1 == BamKVStore.window(table)
    assert kv.probes > 8                 # some key lies beyond 8 slots


def test_store_with_a_short_window_is_rejected(built):
    """A store that reads fewer slots than the table needs would miss the
    keys beyond them: its state is refused, and the window has no default."""
    kv, table = built["kv"], built["table"]
    short = BamKVStore(array=kv.array, capacity=CAP, value_elems=VE,
                       probes=kv.probes - 1)
    with pytest.raises(ValueError, match="window"):
        short.state(None, table)
    with pytest.raises(TypeError):
        BamKVStore(array=kv.array, capacity=CAP, value_elems=VE)


@pytest.mark.parametrize("field,per_key", [("kv_lookups", 1),
                                           ("kv_probe_slots", None)])
def test_kv_counters_count_exactly(built, served, field, per_key):
    kv = built["kv"]
    reqs, _, m0, m1 = served
    valid = sum(int(np.count_nonzero(r != -1)) for r in reqs)
    per_key = kv.probes if per_key is None else per_key
    assert float(getattr(m1, field) - getattr(m0, field)) == valid * per_key


def test_array_path_leaves_kv_counters_at_zero():
    arr, st = BamArray.build(np.arange(512, dtype=np.float32), 8,
                             num_sets=4, ways=2, num_queues=2,
                             queue_depth=64)
    st, vals = arr.submit_wait_jit()(
        st, IORequest.read(jnp.arange(0, 512, 7, dtype=jnp.int32)))
    np.testing.assert_array_equal(np.asarray(vals), np.arange(0, 512, 7))
    m = jax.device_get(st.metrics)
    assert float(m.requests) > 0
    assert float(m.kv_lookups) == 0 and float(m.kv_probe_slots) == 0
