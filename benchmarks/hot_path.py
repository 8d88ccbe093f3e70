"""Hot-path wall-clock microbenchmark: fused kernels + the jit-cached op
family (§III-D/E — "the software request path must be cheap enough to
keep the queues full").

BaM's throughput claim is a *request-rate* claim: the Little's-law math
of §II-C only holds if submitting an I/O costs less than servicing it.
This module measures the host-side software cost of every stage of this
repo's request path, at several wavefront sizes:

* ``probe``          — kernel-dispatched tag probe (`cache.probe`);
* ``alloc_fused``    — the fused probe+allocate pass
                       (`cache.probe_allocate`, argsort-free);
* ``alloc_argsort``  — the legacy two-step probe + argsort clock sweep
                       (`cache.allocate`), kept as the baseline the fused
                       pass replaces;
* ``submit`` / ``wait`` — the token API halves, jit-cached
                       (`BamArray.submit_jit` / `wait_jit`);
* ``submit_wait_fused`` — one full fused round as ONE executable
                       (`BamArray.submit_wait_jit(donate=True)`), state
                       threaded through donated buffers — the
                       steady-state hot path after the fused-round
                       refactor;
* ``submit_wait_pair`` — the same round as the two-executable donated
                       ``submit_jit`` + ``wait_jit`` pair: the delta to
                       ``submit_wait_fused`` is the second dispatch plus
                       the host-side state/token round-trip the fusion
                       removes;
* ``submit_wait_legacy`` — the same round on the step-by-step
                       (``fused_rounds=False``) path, no donation — the
                       pre-fusion baseline, measured at the max batch;
* ``read_jit``       — end-to-end read through the jit-cached op family;
* ``read_eager``     — the identical read with NO jit: every jnp op
                       dispatches one by one, the state of the hot path
                       before this PR's jit-cached op family.

All numbers are host wall-clock µs per call (``time_us`` blocks on every
iteration's output), with derived ops/sec.  The driver (`run.py`) writes
them to ``BENCH_hot_path.json``, a machine-local artifact (not committed).

Standalone (``python benchmarks/hot_path.py``) prints a JSON report and
exits nonzero unless (the PR acceptance gate, CI-runnable):

* the jit-cached end-to-end read is ≥ 2× faster than the eager path at
  the largest swept batch (CPU ref backend);
* the fused donated submit+wait round at the max batch is ≥ 3× faster
  than the PR 5 recorded baseline (``PR5_SUBMIT_WAIT_B4096_US``) and
  clears the elems/s floor (``ROUND_ELEMS_PER_S_FLOOR``);
* the fused ``probe_allocate`` kernel (``impl='pallas', interpret=True``)
  is bit-identical to the jnp oracle across a differential mini-sweep;
* steady-state ``read``/``submit``/``wait`` at fixed shapes trigger zero
  retraces after the first call (the trace-count probe), and a ragged
  bucketed sweep compiles at most one executable per shape bucket with
  zero steady-state retraces.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import SMOKE, scaled, time_us, time_us_state
except ImportError:        # standalone: python benchmarks/<module>.py
    from common import SMOKE, scaled, time_us, time_us_state
from repro.core import BamArray, IORequest
from repro.core import cache as C
from repro.kernels import ops

BLOCK_ELEMS = 128                       # 512B lines of float32
BATCHES = scaled((256, 1024, 4096), (32, 64))
WAYS = 8
NUM_SETS = scaled(512, 16)
N_BLOCKS = 4 * NUM_SETS * WAYS          # 4x oversubscribed storage tier
READ_ITERS = scaled(5, 2)

# PR 5 trajectory point: submit_b4096 (20206.0µs) + wait_b4096 (15213.5µs)
# from BENCH_hot_path.json as committed at PR 5 — the step-by-step token
# round the fused passes replace.  The acceptance gate requires the fused
# donated round at batch 4096 to beat this by >= 3x.
PR5_SUBMIT_WAIT_B4096_US = 35419.5
ROUND_SPEEDUP_GATE = 3.0
# CI floor on steady-state async throughput (elements retired per second
# through one submit+wait round at the max batch).
ROUND_ELEMS_PER_S_FLOOR = 150_000.0


def _build():
    data = np.random.default_rng(7).standard_normal(
        (N_BLOCKS, BLOCK_ELEMS)).astype(np.float32)
    return BamArray.build(data, block_elems=BLOCK_ELEMS,
                          num_sets=NUM_SETS, ways=WAYS,
                          num_queues=8, queue_depth=4096)


def _warm_cache(m: int):
    """A cache directory with a resident working set + the request mix."""
    rng = np.random.default_rng(m)
    cache = C.make_cache(NUM_SETS, WAYS, BLOCK_ELEMS)
    resident = jnp.asarray(
        rng.choice(N_BLOCKS, NUM_SETS * WAYS // 2, replace=False), jnp.int32)
    cache, _, _ = C.probe_allocate(cache, resident, impl="ref")
    # request mix: half the resident set, half fresh keys
    keys = jnp.asarray(np.concatenate([
        rng.choice(np.asarray(resident), m // 2),
        rng.integers(0, N_BLOCKS, m - m // 2),
    ]).astype(np.int32))
    return jax.block_until_ready(cache), keys


def _stage_times(m: int) -> dict:
    cache, keys = _warm_cache(m)
    # the benchmark deliberately times freshly-built wrappers
    probe = jax.jit(lambda c, k: C.probe(c, k))  # bamlint: ignore[BAM105]
    fused = jax.jit(lambda c, k: C.probe_allocate(c, k))  # bamlint: ignore[BAM105]

    def _two_step(c, k):
        pr = C.probe(c, k)
        return C.allocate(c, k, (k >= 0) & ~pr.hit, protect_slots=pr.slot)

    argsort = jax.jit(_two_step)  # bamlint: ignore[BAM105]
    return {
        "probe_us": time_us(probe, cache, keys),
        "alloc_fused_us": time_us(fused, cache, keys),
        "alloc_argsort_us": time_us(argsort, cache, keys),
    }


def _op_times(arr, st, m: int) -> dict:
    rng = np.random.default_rng(100 + m)
    idx = jnp.asarray(rng.integers(0, arr.size, m), jnp.int32)
    submit = arr.submit_jit()
    # guard=False: the timing loop deliberately redeems the same concrete
    # token on every iteration — exactly what the single-redemption guard
    # exists to reject in real code.
    wait = arr.wait_jit(guard=False)
    read = arr.read_jit()
    st1, tok = submit(st, IORequest.read(idx))
    jax.block_until_ready(st1)
    out = {
        "submit_us": time_us(submit, st, IORequest.read(idx)),
        "wait_us": time_us(wait, st1, tok),
        "read_jit_us": time_us(read, st, idx, warmup=1, iters=READ_ITERS),
        "read_eager_us": time_us(arr.read, st, idx, warmup=1,
                                 iters=READ_ITERS),
    }
    out["jit_speedup"] = out["read_eager_us"] / max(out["read_jit_us"], 1e-9)
    out["elems_per_s"] = m / (out["read_jit_us"] * 1e-6)
    return out


def _fresh_state(st):
    """Deep-copy a BamState so a donated run can't kill shared buffers."""
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), st)


def _round_times(arr, st, m: int) -> dict:
    """One full fused async round — submit + wait, donated state threading.

    This is the steady-state shape of the token hot path: every round
    rebinds the state from the previous round's output, so
    ``donate=True`` lets XLA reuse the cache/queue buffers in place
    instead of copying the full state per op.  The round itself is the
    op family's ``submit_wait_jit`` — submit and wait back to back in ONE
    executable, so the round pays one dispatch and the token never
    materialises on the host (``round_pair_us`` keeps the two-executable
    pair as a reference point for what the fusion saves).
    """
    rng = np.random.default_rng(200 + m)
    idx = jnp.asarray(rng.integers(0, arr.size, m), jnp.int32)
    req = IORequest.read(idx)
    rnd = arr.submit_wait_jit(donate=True)
    submit = arr.submit_jit(donate=True)
    wait = arr.wait_jit(donate=True)

    def round_step(s):
        s, _ = rnd(s, req)
        return s

    def pair_step(s):
        s, tok = submit(s, req)
        s, _ = wait(s, tok)
        return s

    us = time_us_state(round_step, _fresh_state(st), iters=READ_ITERS)
    pair_us = time_us_state(pair_step, _fresh_state(st), iters=READ_ITERS)
    return {"round_fused_us": us,
            "round_pair_us": pair_us,
            "round_elems_per_s": m / (us * 1e-6)}


def _legacy_round_us(arr, st, m: int) -> float:
    """The same submit+wait round on the step-by-step path, no donation."""
    legacy = dataclasses.replace(arr, fused_rounds=False,
                                 _jit_ops={}, _trace_counts={})
    rng = np.random.default_rng(200 + m)
    idx = jnp.asarray(rng.integers(0, arr.size, m), jnp.int32)
    req = IORequest.read(idx)
    submit = legacy.submit_jit()
    wait = legacy.wait_jit()

    def round_step(s):
        s, tok = submit(s, req)
        s, _ = wait(s, tok)
        return s

    return time_us_state(round_step, _fresh_state(st), iters=READ_ITERS)


def _differential_sweep() -> bool:
    """Fused kernel (pallas, interpret) vs jnp oracle: bit-identical."""
    rng = np.random.default_rng(0)
    cases = [(4, 1, 7), (8, 4, 33), (16, 8, 64)]
    variants = [dict(), dict(tenant=1), dict(way_lo=1, way_hi=3),
                dict(spec_insert=True), dict(protect_hits=False)]
    for S, W, m in cases:
        tags = jnp.asarray(rng.integers(-1, 200, (S, W)), jnp.int32)
        owner = jnp.asarray(rng.integers(0, 2, (S, W)), jnp.int32)
        refc = jnp.asarray(rng.integers(0, 2, (S, W)), jnp.int32)
        dirty = jnp.asarray(rng.integers(0, 2, (S, W)).astype(bool))
        spec = jnp.asarray(rng.integers(0, 2, (S, W)).astype(bool))
        hand = jnp.asarray(rng.integers(0, W, (S,)), jnp.int32)
        keys = jnp.asarray(rng.integers(-1, 250, m), jnp.int32)
        prot = jnp.asarray(rng.integers(-1, S * W, 5), jnp.int32)
        for kw in variants:
            if W == 1 and "way_hi" in kw:
                continue
            args = (tags, owner, refc, dirty, spec, hand, keys)
            r = ops.probe_allocate(*args, protect_slots=prot, impl="ref",
                                   **kw)
            p = ops.probe_allocate(*args, protect_slots=prot, impl="pallas",
                                   interpret=True, **kw)
            for a, b in zip(r, p):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    return False
    return True


def _retrace_check() -> bool:
    """Fixed-shape steady state must trace each op exactly once (a fresh
    array, so the sweep's other batch shapes don't pollute the counts)."""
    arr, st = _build()
    idx = jnp.asarray(np.arange(64) * 3 % arr.size, jnp.int32)
    read, submit, wait = arr.read_jit(), arr.submit_jit(), arr.wait_jit()
    for _ in range(3):
        _, st = read(st, idx)
        st, tok = submit(st, IORequest.read(idx))
        st, _ = wait(st, tok)
    tc = arr.trace_counts
    return tc.get("read") == 1 and tc.get("submit") == 1 \
        and tc.get("wait") == 1


def _bucketed_retrace_check() -> bool:
    """Ragged wavefronts through the bucketed round: at most one
    executable per shape bucket actually used, and a replay of the same
    ragged sweep at steady state must trigger zero further retraces."""
    arr, st = _build()
    sizes = [3, 17, 64, 65, 130, 200, 9, 77]   # all land in buckets {64, 256}

    def ragged_sweep(st):
        for n in sizes:
            idx = jnp.asarray(np.arange(n) * 5 % arr.size, jnp.int32)
            st, tok = arr.submit_bucketed(st, IORequest.read(idx))
            st, _ = arr.wait_bucketed(st, tok)
        return st

    st = ragged_sweep(st)
    used = {arr.bucket_size(n) for n in sizes}
    tc = dict(arr.trace_counts)
    if tc.get("submit") != len(used) or tc.get("wait") != len(used):
        return False
    ragged_sweep(st)
    return dict(arr.trace_counts) == tc


def sweep() -> dict:
    arr, st = _build()
    report = {
        "workload": {"block_bytes": BLOCK_ELEMS * 4, "num_sets": NUM_SETS,
                     "ways": WAYS, "n_blocks": N_BLOCKS,
                     "batches": list(BATCHES)},
        "batches": [],
    }
    for m in BATCHES:
        point = {"batch": m}
        point.update(_stage_times(m))
        point.update(_op_times(arr, st, m))
        point.update(_round_times(arr, st, m))
        report["batches"].append(point)
    last = report["batches"][-1]
    last["round_legacy_us"] = _legacy_round_us(arr, st, last["batch"])
    last["round_speedup_vs_legacy"] = (
        last["round_legacy_us"] / max(last["round_fused_us"], 1e-9))
    report["jit_speedup_at_max"] = last["jit_speedup"]
    report["jit_beats_eager_2x"] = last["jit_speedup"] >= 2.0
    report["round_fused_us_at_max"] = last["round_fused_us"]
    report["round_speedup_vs_pr5"] = (
        PR5_SUBMIT_WAIT_B4096_US / max(last["round_fused_us"], 1e-9))
    # PR5_SUBMIT_WAIT_B4096_US is a batch-4096 number: the comparison only
    # means anything at full sizes (smoke shrinks the sweep to b<=64).
    report["submit_wait_3x_vs_pr5"] = (
        report["round_speedup_vs_pr5"] >= ROUND_SPEEDUP_GATE)
    report["round_elems_floor_ok"] = (
        last["round_elems_per_s"] >= ROUND_ELEMS_PER_S_FLOOR)
    report["differential_ok"] = _differential_sweep()
    report["no_retrace"] = _retrace_check()
    report["bucketed_no_retrace"] = _bucketed_retrace_check()
    report["gate_ok"] = (report["jit_beats_eager_2x"]
                         and report["submit_wait_3x_vs_pr5"]
                         and report["round_elems_floor_ok"]
                         and report["differential_ok"]
                         and report["no_retrace"]
                         and report["bucketed_no_retrace"])
    return report


def run():
    rep = sweep()
    rows = []
    for p in rep["batches"]:
        m = p["batch"]
        for stage in ("probe", "alloc_fused", "alloc_argsort", "submit",
                      "wait", "read_eager"):
            us = p[f"{stage}_us"]
            rows.append((
                f"hot_path/{stage}_b{m}", us,
                f"ops_per_s={1e6 / max(us, 1e-9):.0f}"))
        rows.append((
            f"hot_path/read_jit_b{m}", p["read_jit_us"],
            f"ops_per_s={1e6 / max(p['read_jit_us'], 1e-9):.0f} "
            f"speedup_vs_eager={p['jit_speedup']:.2f}x "
            f"elems_per_s={p['elems_per_s']:.0f}"))
        derived = (f"ops_per_s={1e6 / max(p['round_fused_us'], 1e-9):.0f} "
                   f"elems_per_s={p['round_elems_per_s']:.0f}")
        if "round_speedup_vs_legacy" in p:
            derived += (f" speedup_vs_legacy="
                        f"{p['round_speedup_vs_legacy']:.2f}x")
        rows.append((f"hot_path/submit_wait_fused_b{m}",
                     p["round_fused_us"], derived))
        rows.append((
            f"hot_path/submit_wait_pair_b{m}", p["round_pair_us"],
            f"ops_per_s={1e6 / max(p['round_pair_us'], 1e-9):.0f}"))
        if "round_legacy_us" in p:
            rows.append((
                f"hot_path/submit_wait_legacy_b{m}", p["round_legacy_us"],
                f"ops_per_s={1e6 / max(p['round_legacy_us'], 1e-9):.0f}"))
    return rows


if __name__ == "__main__":
    rep = sweep()
    print(json.dumps(rep, indent=2))
    # Speedup thresholds are calibrated for full sizes; at smoke sizes only
    # correctness (differential + retrace probes) must hold.
    ok = rep["differential_ok"] and rep["no_retrace"] \
        and rep["bucketed_no_retrace"] \
        and (SMOKE or (rep["jit_beats_eager_2x"]
                       and rep["submit_wait_3x_vs_pr5"]
                       and rep["round_elems_floor_ok"]))
    raise SystemExit(0 if ok else 1)
