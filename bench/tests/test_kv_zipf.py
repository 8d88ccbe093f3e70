"""``kv_zipf`` at a tiny size on the CPU: a sound run is correct, and the
control and each fault its comparison must catch fail it; YCSB's
scrambled-zipfian map gives the hottest record its share."""
import collections

import numpy as np
import pytest

import run
import ycsb
from conftest import SEED

CELL = "kv_zipf"


def tiny():
    """``(cell, cfg, mix, per_layer)`` of ``kv_zipf`` at a CPU size: the
    same key set formula, value width and distribution, fewer records."""
    bench, cell, cfg, mix = run.load_cell(CELL)
    cfg.update(record_count=1 << 10, index_capacity=1 << 11, num_sets=32,
               num_queues=4, queue_depth=256)
    mix.update(lanes=16, warmup_tokens=2)
    return cell, cfg, mix, bench["per_layer"]


def run_tiny(**kw):
    cell, cfg, mix, per_layer = tiny()
    kw.setdefault("seconds", 0.5)
    kw.setdefault("trace", False)
    return run.run_cell(cell, cfg, mix, per_layer, seed=SEED,
                        require_tpu=False, log=lambda _: None, **kw)


def test_tiny_run_is_correct():
    r = run_tiny()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {"value_mismatches", "found_mismatches"}


def test_traced_run_reads_the_index_counter():
    from repro.core import BamKVStore
    r = run_tiny(trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert {"hit_rate", "fetch_lines_per_token", "io_amplification",
            "submit_host_ms", "kv_probe_slots_per_key"} <= set(m)
    # every key reads the index window once: the built table's bound
    _, cfg, _, _ = tiny()
    keys = ycsb.record_key(np.arange(cfg["record_count"]))
    _, _, probes = BamKVStore.place(keys, capacity=cfg["index_capacity"])
    assert m["kv_probe_slots_per_key"]["value"] == probes


def test_bf16_control_fails():
    r = run_tiny(control="bf16")
    assert r["correct"] is False
    assert r["checks"]["value_mismatches"]["value"] > 0


def broken(fault):
    """The cell's system with ``fault`` planted where answers are made."""
    from systems.bam_kv import System

    class Broken(System):
        def wait(self, handle):
            out = super().wait(handle)
            v, found = out["values"], out["found"]
            flat = v.reshape(-1)
            if fault == "altered":          # one answer changed
                flat = flat.at[flat.shape[0] // 3].add(1.0)
            elif fault == "half_left_out":  # half the values never served
                flat = flat.at[flat.shape[0] // 2:].set(0.0)
            elif fault == "found_flipped":  # one key reported missing
                found = found.at[found.shape[0] // 2].set(False)
            return dict(out, values=flat.reshape(v.shape), found=found)

    return Broken


@pytest.mark.parametrize("fault,check", [
    ("altered", "value_mismatches"), ("half_left_out", "value_mismatches"),
    ("found_flipped", "found_mismatches")])
def test_fault_fails(fault, check):
    r = run_tiny(make_system=broken(fault))
    assert r["correct"] is False, r["checks"]
    assert r["failed"] > 0 and r["checks"][check]["value"] > 0


def workload_c(**kw) -> dict:
    """The cell's configuration as it is run, with ``kw`` changed."""
    _, _, cfg, _ = run.load_cell(CELL)
    return dict(cfg, **kw)


def test_hottest_record_takes_one_over_zetan():
    """Rank 0 has probability 1/ZETAN (3.78%); over 10**6 draws its count
    has a standard deviation of 0.019 points, so 0.1 points is five."""
    cfg = workload_c()
    n, records = 10**6, cfg["record_count"]
    ids = np.random.default_rng(SEED).integers(0, ycsb.N_ITEMS, n)
    keynums = ycsb.request_keynums(ids, cfg)
    assert keynums.min() >= 0 and keynums.max() < records
    top, count = collections.Counter(keynums.tolist()).most_common(1)[0]
    assert top == int(ycsb.fnvhash64(0) % np.uint64(records))
    assert abs(100 * count / n - 100 / cfg["zetan"]) < 0.1


def test_uniform_distribution_is_read_from_the_configuration():
    """``request_distribution: uniform`` spreads 10**6 draws over 2**10
    records: each count's standard deviation is about 31 around 977, so
    200 is six and a half."""
    cfg = workload_c(request_distribution="uniform", record_count=1 << 10)
    ids = np.random.default_rng(SEED).integers(0, ycsb.N_ITEMS, 10**6)
    counts = np.bincount(ycsb.request_keynums(ids, cfg), minlength=1 << 10)
    assert counts.shape == (1 << 10,)
    assert abs(counts - 10**6 / (1 << 10)).max() < 200


@pytest.mark.parametrize("key,value", [
    ("request_distribution", "latest"), ("read_proportion", 0.95),
    ("insert_order", "ordered")])
def test_workload_the_generator_lacks_is_refused(key, value):
    from systems.bam_kv import System
    with pytest.raises(ValueError, match=key):
        System(workload_c(**{key: value}), SEED)


def test_fnvhash64_matches_ycsb():
    """The numpy hash against Python's unbounded integers: FNV-1a-64 over
    the eight low-first bytes, wrapped to 64 bits, then Java's Math.abs."""
    want = []
    for v in (0, 1, -1):
        h = ycsb.FNV_OFFSET_BASIS_64
        for i in range(8):
            h = ((h ^ ((v >> (8 * i)) & 0xFF)) * ycsb.FNV_PRIME_64) % 2**64
        want.append(h if h < 2**63 else 2**64 - h)
    np.testing.assert_array_equal(ycsb.fnvhash64([0, 1, -1]),
                                  np.asarray(want, np.uint64))
