"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/<name>.json``,
whose ``system`` picks ``systems/<kind>.py`` and ``reference/<kind>.py``) and
a traffic mix (``traffic/<mix>.json``, read by ``generator.py``).  Set-up
makes the data from the seed, builds the system, and runs the mix's warm-up
tokens, which compile every program the window uses.  The window then runs
the closed loop for ``--seconds``, with ``in_flight`` tokens outstanding,
each timed from its submit call until its wait output is ready.  Once the
window has closed, every value it returned is compared with the numpy
reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the first seconds of the window are traced and the result
carries the per-layer metrics (``metrics/<name>.py``).  The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.  ``--control bf16`` rounds every served value to bfloat16
before the comparison (the check's control; never part of a cell's runs).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse                    # noqa: E402
import collections                 # noqa: E402
import contextlib                  # noqa: E402
import dataclasses                 # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import pathlib                     # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

SPANS = ("make_traffic", "submit", "wait", "check")
TRACE_SECONDS = 3.0     # of the window traced with --trace 1
LIMIT = 0               # every comparison is exact: reads are exact


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, mix)`` of workload ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return bench, cell, cfg, mix


class Spans:
    """The benchmark's host spans: kept in memory, and with tracing on
    written into the profiler's trace too."""

    def __init__(self):
        self.times = collections.defaultdict(list)
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with (jax.profiler.TraceAnnotation(name) if self.tracing
              else contextlib.nullcontext()):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.times[name].append(time.perf_counter() - t0)


@dataclasses.dataclass
class Done:
    items: object
    latency_s: float
    out: dict


def closed_loop(system, traffic, spans, k0: int, *, tokens: int | None = None,
                seconds: float | None = None, on_done=None):
    """Run tokens ``k0, k0+1, ...`` with ``traffic.in_flight`` outstanding:
    ``tokens`` of them, or as many as start within ``seconds``.  Returns
    ``(done, next_k, elapsed_s)``; every submitted token is waited."""
    import jax
    pending = collections.deque()
    done = []
    k = k0

    def issue():
        nonlocal k
        with spans("make_traffic"):
            items = traffic.token(k)
            req = system.request(items)
        t_sub = time.perf_counter()
        with spans("submit"):
            handle = system.submit(req)
        pending.append((items, t_sub, handle))
        k += 1

    def more(now):
        if tokens is not None:
            return k - k0 < tokens
        return now - t0 < seconds

    t0 = time.perf_counter()
    for _ in range(traffic.in_flight):
        if more(t0):
            issue()
    while pending:
        items, t_sub, handle = pending.popleft()
        with spans("wait"):
            out = system.wait(handle)
            jax.block_until_ready(out)
        now = time.perf_counter()
        done.append(Done(items, now - t_sub, out))
        if on_done is not None:
            on_done(now - t0, len(done))
        if more(now):
            issue()
    return done, k, time.perf_counter() - t0


@dataclasses.dataclass
class Window:
    """What the per-layer readers read."""
    counters: dict
    tokens: int
    submit_s: list
    trace: dict | None
    kernel_shapes: dict
    peak: dict


def check(ref, cfg: dict, seed: int, done: list, control: str | None):
    """Compare every value the window returned with the reference.
    Returns ``(failed tokens, {number: total})``."""
    import jax
    import numpy as np
    got_all = jax.device_get([d.out for d in done])
    totals, failed = collections.Counter(), 0
    for d, got in zip(done, got_all):
        if control == "bf16":
            got = dict(got, values=np.asarray(
                jax.numpy.asarray(got["values"]).astype(jax.numpy.bfloat16)
                .astype(jax.numpy.float32)))
        want = ref.expected(cfg, seed, d.items)
        diff = ref.compare(got, want)
        totals.update(diff)
        failed += any(v > LIMIT for v in diff.values())
    return failed, dict(totals)


def percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_cell(cell: dict, cfg: dict, mix: dict, per_layer: list, *,
             seed: int, seconds: float, trace: bool,
             control: str | None = None, require_tpu: bool = True,
             make_system=None, log=print) -> dict | None:
    """One run of one cell; returns the result object, or ``None`` where
    the chips are missing.  ``make_system`` replaces the configured system
    (the harness's own tests break the timed path through it)."""
    import jax
    import numpy as np

    import generator
    import roofline

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < int(cell["chips"])):
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    dev = devs[0]
    peak = roofline.peaks(dev.device_kind) if require_tpu else {}
    kind = cfg["system"]
    ref = importlib.import_module(f"reference.{kind}")
    if make_system is None:
        make_system = importlib.import_module(f"systems.{kind}").System
    spans = Spans()

    t_build = time.perf_counter()
    system = make_system(cfg, seed)
    traffic = generator.Traffic(mix, seed, system.n_items)
    t_warm = time.perf_counter()
    _, k, _ = closed_loop(system, traffic, spans, 0,
                          tokens=traffic.warmup_tokens)
    spans.times.clear()
    c0 = system.counters()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s!r}: start {t_build - T_START!r}, data and build "
        f"{t_warm - t_build!r}, warm-up ({traffic.warmup_tokens} tokens, "
        f"compiles) {time.perf_counter() - t_warm!r}")

    # hit rate and tokens by quarter of the window (printed; not metrics)
    marks, marks_done = [], []

    def on_done(elapsed, n_done):
        q = int(4 * elapsed / seconds)
        if q > len(marks) and q <= 3:
            marks.append(system.counters())
            marks_done.append(n_done)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        spans.tracing = True
        with spans("window"):
            part, k, _ = closed_loop(system, traffic, spans, k,
                                     seconds=min(seconds, TRACE_SECONDS))
        spans.tracing = False
        jax.profiler.stop_trace()
        rest, k, _ = closed_loop(system, traffic, spans, k,
                                 seconds=max(0.0, seconds - TRACE_SECONDS))
        done = part + rest
    else:
        done, k, elapsed = closed_loop(system, traffic, spans, k,
                                       seconds=seconds, on_done=on_done)
    c1 = system.counters()
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    counters = {key: c1[key] - c0[key] for key in c0}
    shapes = system.kernel_shapes(traffic.lanes)
    del system

    with spans("check"):
        failed, totals = check(ref, cfg, seed, done, control)
    lat = [d.latency_s for d in done]
    elems = sum(int(np.size(d.out["values"])) for d in done)
    log(f"tokens {len(done)}  token_ms p50 {1e3 * percentile(lat, 50)!r} "
        f"p95 {1e3 * percentile(lat, 95)!r}  samples {len(lat)}")
    quarters = [c0] + marks + [c1]
    if len(quarters) == 5:
        def hr(a, b):
            h, m = b["hits"] - a["hits"], b["misses"] - a["misses"]
            return h / (h + m) if h + m else float("nan")
        log(f"hit_rate first quarter {hr(quarters[0], quarters[1])!r} "
            f"last quarter {hr(quarters[3], quarters[4])!r}")
        ends = [0] + marks_done + [len(done)]
        log("tokens by quarter "
            f"{[b - a for a, b in zip(ends, ends[1:])]!r}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": None, "attempted": len(done), "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        import tracereduce
        xp = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        red = (tracereduce.reduce(tracereduce.load_xplane(str(xp[-1]),
                                                          SPANS))
               if xp else {})
        shutil.rmtree(tdir, ignore_errors=True)
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        w = Window(counters=counters, tokens=len(done),
                   submit_s=spans.times["submit"], trace=red or None,
                   kernel_shapes=shapes, peak=peak)
        for m in per_layer:
            v = importlib.import_module(f"metrics.{m['name']}").read(w)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    else:
        result["metrics"] = {
            "served_elems_per_s": {"value": elems / elapsed,
                                   "unit": "elems/s"},
            "token_p95_ms": {"value": 1e3 * percentile(lat, 95),
                             "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    checks = {name: {"value": v, "limit": LIMIT}
              for name, v in sorted(totals.items())}
    result["correct"] = (bool(done)
                         and all(c["value"] <= c["limit"]
                                 for c in checks.values()))
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bf16",), default=None)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix = load_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(cell, cfg, mix, bench["per_layer"], seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      control=args.control, log=log)
    if result is None:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
