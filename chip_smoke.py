"""Drive the BaM request path once on one TPU chip, at deployment size.

    python chip_smoke.py [--seed 0]

Phase A  a ``BamArray`` (``backend="sim"``) over a 4 GiB float32 storage
         tier in host memory, with a 1 GiB HBM cache (65,536 sets x 4 ways
         x 4 KiB lines) and 16 x 1024 SQ rings.  Uniform-random reads, a
         sequential scan, a write and its read-back, all through donated
         ``submit_jit``/``wait_jit`` tokens with two outstanding at once;
         every value is checked against a numpy reference.
Phase B  the hot-path kernels (``probe_allocate``, ``cache_probe``,
         ``gather_blocks``) on Phase A's directory and a 4096-lane
         wavefront, as the main path runs them and as the jnp oracle,
         compared bitwise; plus the Pallas ``probe_allocate`` at a shape
         it compiles for, against the oracle.
Phase C  BFS (async tokens) and CC over ``random_graph`` at the size
         ``examples/graph_analytics.py`` uses by default, against the
         oracles.  A functional check, not a deployment size.

Times printed are wall time on the named device kind (compile = backend
compilation as ``jax.monitoring`` reports it; run = the rest, tracing
included), with how many bytes the persistent compile cache held at the
start; they are not benchmark metrics.  All data comes from ``--seed``.

Exits nonzero, printing no result, unless JAX's first device is a TPU.
The last line of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# Phase A: the storage tier is four times the cache.
N_ELEMS = 1 << 30            # 4 GiB of float32 in host memory
BLOCK_ELEMS = 1024           # 4 KiB cache lines
NUM_SETS, WAYS = 65536, 4    # 262,144 lines = 1 GiB of HBM
NUM_QUEUES, QUEUE_DEPTH = 16, 1024
WAVEFRONT = 4096             # the largest of DEFAULT_BUCKETS

class CompileClock:
    """Sums backend-compile seconds from JAX's monitoring events; register
    an instance with ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs


class Phase:
    """Times one phase: compile seconds from ``clock``, run seconds as the
    rest of the wall clock."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.c0, self.t0 = self.clock.total, time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.total - self.c0
        return False

    def report(self, dev, **fields) -> None:
        stats = dev.memory_stats() or {}
        print(json.dumps({
            "phase": self.name, "wall_time_on": dev.device_kind,
            "compile_s": self.compile, "run_s": self.wall - self.compile,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            **fields}), flush=True)


def _check_equal(what: str, got, want) -> None:
    """Bitwise equality of two arrays (floats compared as raw bits)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{what}: got {got.dtype}{got.shape}, "
                             f"want {want.dtype}{want.shape}")
    if got.dtype.kind == "f":
        bits = np.dtype(f"u{got.dtype.itemsize}")
        got, want = got.view(bits), want.view(bits)
    bad = np.flatnonzero(got.reshape(-1) != want.reshape(-1))
    if bad.size:
        raise AssertionError(f"{what}: {bad.size} of {got.size} values "
                             f"differ (first at flat index {bad[0]})")


def _kernel_impls() -> dict:
    from repro.kernels import ops
    return {k: ops.resolve_impl(k)
            for k in ("probe_allocate", "cache_probe", "gather_blocks")}


def phase_a(seed: int, *, n_elems: int = N_ELEMS,
            block_elems: int = BLOCK_ELEMS, num_sets: int = NUM_SETS,
            ways: int = WAYS, wavefront: int = WAVEFRONT):
    """The store at deployment size; returns ``(arr, state, fields)``."""
    from repro.core import BamArray, IORequest

    rng = np.random.default_rng(seed)
    data = rng.random(n_elems, dtype=np.float32)   # SimStorage writes here
    ref = data.copy()                              # the reference
    arr, st = BamArray.build(data, block_elems, num_sets=num_sets,
                             ways=ways, num_queues=NUM_QUEUES,
                             queue_depth=QUEUE_DEPTH, backend="sim")
    submit = arr.submit_jit(donate=True)
    wait = arr.wait_jit(donate=True)

    def idx_arr(x):
        return jnp.asarray(np.asarray(x, np.int32))

    def read_pair(st, idx_a, idx_b):
        """Two read tokens outstanding at once, each checked on return."""
        st, tok_a = submit(st, IORequest.read(idx_arr(idx_a)))
        st, tok_b = submit(st, IORequest.read(idx_arr(idx_b)))
        st, val_a = wait(st, tok_a)
        st, val_b = wait(st, tok_b)
        _check_equal("read", val_a, ref[idx_a])
        _check_equal("read", val_b, ref[idx_b])
        return st

    def rand_idx():
        return rng.integers(0, n_elems, wavefront)

    n_waves = 0
    for _ in range(2):                             # uniform-random reads
        st = read_pair(st, rand_idx(), rand_idx())
        n_waves += 2
    start = int(rng.integers(0, n_elems - 8 * wavefront))
    scan = start + np.arange(8 * wavefront).reshape(8, wavefront)
    for k in range(0, 8, 2):                       # sequential scan
        st = read_pair(st, scan[k], scan[k + 1])
        n_waves += 2

    w_idx = np.unique(rng.integers(0, n_elems, 2 * wavefront))
    w_idx = rng.permutation(w_idx)[:wavefront]
    w_val = rng.random(wavefront, dtype=np.float32) + np.float32(2.0)
    st, tok = submit(st, IORequest.write(idx_arr(w_idx),
                                         jnp.asarray(w_val)))
    st, _ = wait(st, tok)
    ref[w_idx] = w_val
    st = read_pair(st, w_idx, rand_idx())          # read-back
    mixed = np.concatenate([w_idx[: wavefront // 2],
                            rand_idx()[: wavefront - wavefront // 2]])
    st = read_pair(st, mixed, rand_idx())
    n_waves += 5

    m = st.metrics.summary()
    if m["max_tokens_in_flight"] < 2:
        raise AssertionError(f"expected >= 2 tokens in flight, saw "
                             f"{m['max_tokens_in_flight']}")
    keep = ("requests", "hits", "misses", "hit_rate", "amplification",
            "bytes_from_storage", "doorbells", "dropped", "max_queue_depth",
            "tokens_submitted", "max_tokens_in_flight")
    fields = dict(
        wavefronts=n_waves, wavefront_lanes=wavefront,
        storage_bytes=int(data.nbytes),
        cache_bytes=int(st.cache.data.nbytes),
        values_checked=n_waves * wavefront,
        metrics={k: m[k] for k in keep})
    return arr, st, fields


def phase_b(st, seed: int, *, wavefront: int = WAVEFRONT) -> dict:
    """Hot-path kernels against the jnp oracle, bitwise, on one device."""
    from repro.kernels import ops

    rng = np.random.default_rng(seed + 1)
    cache = st.cache
    tags = np.asarray(cache.tags).reshape(-1)
    resident = tags[tags >= 0]
    keys = np.concatenate([
        rng.choice(resident, wavefront // 2),
        rng.integers(0, 2 * cache.num_sets * cache.ways,
                     wavefront - wavefront // 2)]).astype(np.int32)
    keys = jnp.asarray(rng.permutation(keys))
    valid = keys >= 0
    impls = _kernel_impls()
    checked = {}

    def both(name, fn, *args):
        outs = [jax.jit(lambda *a, i=impl: fn(*a, impl=i))(*args)
                for impl in (impls[name], "ref")]
        for k, (got, want) in enumerate(zip(*map(jax.tree.leaves, outs))):
            _check_equal(f"{name} output {k}", got, want)
        checked[name] = impls[name] + " == ref"
        return outs[1]

    both("probe_allocate",
         lambda t, o, r, d, s, h, k, v, impl: ops.probe_allocate(
             t, o, r, d, s, h, k, valid=v, impl=impl),
         cache.tags, cache.owner, cache.refcount, cache.dirty,
         cache.speculative, cache.clock_hand, keys, valid)
    hit, slot = both("cache_probe",
                     lambda t, o, k, impl: ops.cache_probe(
                         t, k, owner=o, impl=impl),
                     cache.tags, cache.owner, keys)
    off = jnp.asarray(rng.integers(0, cache.line_elems, wavefront),
                      jnp.int32)
    both("gather_blocks",
         lambda d, s, o, impl: ops.gather_blocks(d, s, off=o, impl=impl),
         cache.data, slot, off)
    lines_p = jax.jit(lambda d, s: ops.gather_blocks(
        d, s, impl=impls["gather_blocks"]))(cache.data, slot)
    _check_equal("gather_blocks lines", lines_p,
                 jax.jit(lambda d, s: ops.gather_blocks(d, s, impl="ref"))(
                     cache.data, slot))

    # The Pallas probe_allocate at a shape it compiles for: its one-hot
    # gathers carry 16-bit halves, exact only at full f32 matmul precision.
    s_sets, s_ways, s_m = 64, 4, 256
    small = dict(
        tags=rng.integers(-1, 6 * s_sets * s_ways, (s_sets, s_ways)),
        owner=np.zeros((s_sets, s_ways)),
        refcount=rng.integers(0, 2, (s_sets, s_ways)),
        dirty=rng.integers(0, 2, (s_sets, s_ways)).astype(bool),
        speculative=rng.integers(0, 2, (s_sets, s_ways)).astype(bool),
        clock_hand=rng.integers(0, s_ways, (s_sets,)))
    small = {k: jnp.asarray(v if v.dtype == bool else v.astype(np.int32))
             for k, v in small.items()}
    s_keys = jnp.asarray(np.concatenate([
        rng.choice(np.asarray(small["tags"]).reshape(-1), s_m // 2),
        rng.integers(-1, 6 * s_sets * s_ways, s_m - s_m // 2)]), jnp.int32)
    outs = [jax.jit(lambda kw, k, i=impl: ops.probe_allocate(
                kw["tags"], kw["owner"], kw["refcount"], kw["dirty"],
                kw["speculative"], kw["clock_hand"], k, impl=i))(
                small, s_keys) for impl in ("pallas", "ref")]
    for k, (got, want) in enumerate(zip(*outs)):
        _check_equal(f"probe_allocate[pallas {s_sets}x{s_ways}, m={s_m}] "
                     f"output {k}", got, want)
    checked[f"probe_allocate[{s_sets}x{s_ways}, m={s_m}]"] = "pallas == ref"
    return dict(kernels=impls, checked=checked,
                probe_hits=int(jnp.sum(hit)), wavefront_lanes=wavefront)


def phase_c(seed: int, *, nodes: int = 3000, avg_deg: float = 12.0
            ) -> dict:
    """BFS (async tokens) and CC against the oracles — functional only."""
    from repro.core.ssd import ArrayOfSSDs, INTEL_OPTANE_P5800X
    from repro.graph import (BamGraph, bfs, bfs_oracle, cc, cc_oracle,
                             random_graph)

    indptr, dst = random_graph(nodes, avg_deg, seed=seed)

    def graph():
        return BamGraph.build(indptr, dst, cacheline_bytes=4096,
                              cache_bytes=1 << 18,
                              ssd=ArrayOfSSDs(INTEL_OPTANE_P5800X, 4))

    depth, _ = bfs(graph(), 0, async_tokens=True)
    _check_equal("bfs depth", np.asarray(depth, np.int32),
                 bfs_oracle(indptr, dst, 0).astype(np.int32))
    labels, _ = cc(graph())
    want = cc_oracle(indptr, dst)
    pairs = set(zip(np.asarray(labels).tolist(), want.tolist()))
    if not (len(pairs) == len(set(want.tolist()))
            == len({a for a, _ in pairs})):
        raise AssertionError("cc partition differs from cc_oracle")
    return dict(nodes=nodes, edges=int(len(dst)),
                reached=int((np.asarray(depth) >= 0).sum()),
                components=len(set(want.tolist())),
                size="functional check, not a deployment size")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import enable_compile_cache

    cache_dir = pathlib.Path(enable_compile_cache())
    cached = sum(f.stat().st_size for f in cache_dir.rglob("*")
                 if f.is_file()) if cache_dir.is_dir() else 0
    print(json.dumps({"compile_cache": str(cache_dir),
                      "bytes_at_start": cached}), flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    with Phase("A: store, 4 GiB tier / 1 GiB HBM cache", clock) as ph:
        _, st, fields = phase_a(args.seed)
    ph.report(dev, kernels=_kernel_impls(), **fields)
    with Phase("B: kernels vs ref, bitwise", clock) as ph:
        fields = phase_b(st, args.seed)
    ph.report(dev, **fields)
    del st
    with Phase("C: graph path, functional", clock) as ph:
        fields = phase_c(args.seed)
    ph.report(dev, **fields)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
