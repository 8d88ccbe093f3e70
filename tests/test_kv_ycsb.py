"""YCSB workload C on ``BamKVStore`` through the donated token pair, at a CPU
size: FNV-hashed keys (``bench/ycsb.py``, YCSB's ``insertorder=hashed``),
scrambled-zipfian reads, two tokens in flight, every value checked against
a plain dict of records."""
import dataclasses
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


def _in_flight(reqs, submit, wait, st):
    """Submit ``reqs`` in order with IN_FLIGHT outstanding; returns the final
    state, the tokens in submit order, and each wait's values."""
    pending, toks, out = [], [], []
    for req in reqs:
        st, tok = submit(st, req)
        pending.append(tok)
        toks.append(tok)
        if len(pending) == IN_FLIGHT:
            st, vals = wait(st, pending.pop(0))
            out.append(np.asarray(vals))
    for tok in pending:
        st, vals = wait(st, tok)
        out.append(np.asarray(vals))
    return st, toks, out


@pytest.fixture(scope="module")
def served(built):
    """Run TOKENS lookups with IN_FLIGHT outstanding; returns the requests,
    what came back, the tokens, the metrics and the storage counters before
    and after, and a copy of the state the lookups started from."""
    kv, kst = built["kv"], built["kst"]
    kst0 = jax.tree.map(jnp.copy, kst)
    submit, wait = kv.submit_jit(donate=True), kv.wait_jit(donate=True)
    m0 = jax.device_get(kst.bam.metrics)
    c0 = kv.storage.counters()
    reqs = [_token_keys(k, built["keys"]) for k in range(TOKENS)]
    kst, toks, vals = _in_flight([jnp.asarray(r) for r in reqs], submit,
                                 wait, kst)
    return dict(reqs=reqs, out=[(v, np.asarray(t.found))
                                for v, t in zip(vals, toks)],
                toks=toks, m0=m0, m1=jax.device_get(kst.bam.metrics),
                c0=c0, c1=kv.storage.counters(), kst0=kst0)


def test_zipfian_lookups_match_a_dict_of_records(built, served):
    records = dict(zip(built["keys"].tolist(), built["values"]))
    reqs, out = served["reqs"], served["out"]
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
    reqs, m0, m1 = served["reqs"], served["m0"], served["m1"]
    valid = sum(int(np.count_nonzero(r != -1)) for r in reqs)
    per_key = kv.probes if per_key is None else per_key
    assert float(getattr(m1, field) - getattr(m0, field)) == valid * per_key


@pytest.fixture(scope="module")
def small_array():
    return BamArray.build(np.arange(512, dtype=np.float32), 8, num_sets=4,
                          ways=2, num_queues=2, queue_depth=64)


def test_array_path_leaves_kv_counters_at_zero(small_array):
    arr, st = small_array
    st, vals = arr.submit_wait_jit()(
        st, IORequest.read(jnp.arange(0, 512, 7, dtype=jnp.int32)))
    np.testing.assert_array_equal(np.asarray(vals), np.arange(0, 512, 7))
    m = jax.device_get(st.metrics)
    assert float(m.requests) > 0
    assert float(m.kv_lookups) == 0 and float(m.kv_probe_slots) == 0


@pytest.mark.parametrize("lines", [None, 74, 100])
def test_array_token_rows_stay_lane_sized(small_array, lines):
    """A read with no line bound, or one no smaller than its lanes, keeps
    one unique-line row a lane: the array's path is not compacted."""
    arr, st = small_array
    idx = jnp.arange(0, 512, 7, dtype=jnp.int32)
    _, tok = jax.eval_shape(arr.submit, st,
                            IORequest.read(idx, lines=lines))
    for field in ("ukeys", "pin_slots", "ticket"):
        assert getattr(tok, field).shape == idx.shape


@pytest.mark.parametrize("lines", [0, -3])
def test_non_positive_line_bound_is_rejected(small_array, lines):
    arr, st = small_array
    idx = jnp.arange(0, 512, 7, dtype=jnp.int32)
    with pytest.raises(ValueError, match="lines must be positive"):
        jax.eval_shape(arr.submit, st, IORequest.read(idx, lines=lines))


@pytest.mark.parametrize("field", ["ukeys", "pin_slots", "ticket",
                                   "fetch_rows"])
def test_kv_token_rows_are_line_sized(built, served, field):
    """A token of KEYS_PER_TOKEN one-line values (absent and -1 keys
    included) carries one row a key, not one a lane, and each fetch
    callback ships exactly that many rows."""
    kv = built["kv"]
    width = KEYS_PER_TOKEN * kv.lines_per_value
    assert kv.lines_per_value == 1 and width < KEYS_PER_TOKEN * VE
    if field == "fetch_rows":
        c0, c1 = served["c0"], served["c1"]
        calls = c1["fetch_calls"] - c0["fetch_calls"]
        assert calls > 0
        assert c1["fetch_rows"] - c0["fetch_rows"] == calls * width
    else:
        for tok in served["toks"]:
            assert getattr(tok.io, field).shape == (width,)
            assert tok.io.valid.shape == (KEYS_PER_TOKEN * VE,)


@pytest.mark.parametrize("ve,be,span", [(24, 16, 2), (6, 16, 2)])
def test_values_that_straddle_lines_match_a_dict_of_records(built, ve, be,
                                                            span):
    """A value of ``ve`` elements over lines of ``be`` can straddle a line
    boundary: ``span`` lines a key bound the token, and the lookups still
    return every record bit for bit."""
    keys = built["keys"][:256]
    values = np.random.default_rng(2).standard_normal(
        (keys.shape[0], ve)).astype(np.float32)
    kv, table, st = BamKVStore.build(keys, values, capacity=512,
                                     block_elems=be, num_sets=4, ways=2,
                                     num_queues=2, queue_depth=256)
    assert kv.lines_per_value == span
    records = dict(zip(keys.tolist(), values))
    req = np.concatenate([keys[::3][:KEYS_PER_TOKEN - 2],
                          ycsb.record_key(np.asarray([N_KEYS + 1])), [-1]])
    kst, tok = kv.submit_jit()(kv.state(st, table), jnp.asarray(req))
    assert tok.io.ukeys.shape == (KEYS_PER_TOKEN * span,)
    _, vals = kv.wait_jit()(kst, tok)
    want = np.stack([records.get(k, np.zeros(ve, np.float32))
                     for k in req.tolist()])
    np.testing.assert_array_equal(np.asarray(vals).view(np.uint32),
                                  want.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(tok.found),
                                  [k in records for k in req.tolist()])


def test_line_bound_leaves_the_state_bit_identical(built, served):
    """The served traffic through ``BamArray.submit`` with and without the
    line bound: the same values, and the same cache, queues and metrics
    after every wait."""
    kv, kst0 = built["kv"], served["kst0"]
    arr, table = kv.array, kst0.table
    reqs = [kv._value_request(table, jnp.asarray(r))[1]
            for r in served["reqs"]]
    wait = arr.wait_jit(guard=False)
    runs = [_in_flight(rs, arr.submit_jit(), wait, kst0.bam)
            for rs in (reqs, [dataclasses.replace(r, lines=None)
                              for r in reqs])]
    (st_b, toks_b, out_b), (st_n, toks_n, out_n) = runs
    assert toks_b[0].ukeys.shape[0] < toks_n[0].ukeys.shape[0]
    for vb, vn in zip(out_b, out_n):
        np.testing.assert_array_equal(vb.view(np.uint32), vn.view(np.uint32))
    for part in ("cache", "queues", "metrics"):
        for a, b in zip(jax.tree.leaves(getattr(st_b, part)),
                        jax.tree.leaves(getattr(st_n, part))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
