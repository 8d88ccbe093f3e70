"""Where a cell's device time goes by stage, what the storage callbacks do
on the host, and how much of set-up is compiling: read from the program's
own spans, scopes and counters.

    python3 bench/stages.py --workload <cell> --seed <n> [--windows 4]
                            [--out <dir>]

Set-up is ``run.py``'s (data, build, warm-up tokens), with a listener on
JAX's backend-compile event that sums the compile seconds in it.  Then
``--windows`` windows of ``run.TRACE_SECONDS`` each are traced, the
``bam.storage.*`` host spans on in the even ones and off in the odd ones,
so that their cost with the profiler on shows in the token p50.  Prints
one JSON object; ``--out`` also keeps there the first window's ops with
their scopes and its host spans (``trace.json.gz``).

Reduction.  ``load_xplane`` keeps what ``tracereduce.load_xplane`` keeps
and, besides, each device op's scope and the host spans whose name starts
with ``bam.``.  The scope is the op-name metadata of the op's HLO
instruction (``jit(bam_wait_donated)/gather/...``), read from the compiled
text of the executables that ran (``hlo_op_names``): on the chip,
``jax.profiler.ProfileData`` gives an ``XLA Ops`` event only its own
stats (``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``), not the op-name.  ``reduce`` puts every busy second of the
window to the innermost op running then, by its jitted op and stage; a
second in which the device waits on a host transfer is not busy, as in
``tracereduce``.  So the stages sum to ``tracereduce``'s ``busy_s``, and
what no stage scope holds is ``unscoped``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse                    # noqa: E402
import bisect                      # noqa: E402
import collections                 # noqa: E402
import contextlib                  # noqa: E402
import dataclasses                 # noqa: E402
import gzip                        # noqa: E402
import heapq                       # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import pathlib                     # noqa: E402
import re                          # noqa: E402
import shutil                      # noqa: E402
import statistics                  # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tracereduce as T            # noqa: E402

HOST_PREFIX = "bam."
STORAGE_PREFIX = "bam.storage."
STAGES = ("coalesce", "probe_allocate", "readahead", "write_back", "enqueue",
          "drain", "probe", "fetch", "fill", "gather", "release",
          "accounting")
UNSCOPED = "unscoped"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JIT = re.compile(r"jit\(([^)]*)\)")
MODULES_LINE = "XLA Modules"
MODULE = re.compile(r"HloModule ([\w.-]+)")
INSTR = re.compile(r"%([\w.-]+) = ")
HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (.*)$", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')
REF = re.compile(r"%([\w.-]+)")


@dataclasses.dataclass
class Scoped:
    ops: dict     # device -> [(op name, start_s, dur_s, scope)]
    spans: list   # [(span name, start_s, end_s)]: the benchmark's, on the host
    host: list    # [(span name, start_s, end_s, rows, live)]: ``bam.*``

    def trace(self) -> T.Trace:
        """What ``tracereduce`` reads of the same trace."""
        return T.Trace(ops={d: [(n, s, dur) for n, s, dur, _ in v]
                            for d, v in self.ops.items()},
                       spans=self.spans)


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction: op-name}`` of a compiled module's HLO.  An instruction
    whose op-name names no stage (a copy XLA put in to change a layout, or
    one of an argument) takes that of the nearest user that names one, else
    that of the nearest operand."""
    names, users, operands = {}, collections.defaultdict(list), {}
    for name, rest in HLO_INSTR.findall(hlo_text):
        m = OP_NAME.search(rest)
        names[name] = m.group(1) if m else ""
        operands[name] = REF.findall(rest.split("metadata=")[0])
        for ref in operands[name]:
            users[ref].append(name)

    def nearest(start, edges):
        seen, queue = {start}, collections.deque(edges.get(start, ()))
        while queue:
            n = queue.popleft()
            if n in seen or n not in names:
                continue
            seen.add(n)
            if stage_of(names[n])[1] != UNSCOPED:
                return names[n]
            queue.extend(edges.get(n, ()))
        return None

    return {n: op if stage_of(op)[1] != UNSCOPED
            else nearest(n, users) or nearest(n, operands) or op
            for n, op in names.items()}


def load_xplane(path: str, span_names, op_names: dict) -> Scoped:
    """``op_names``: ``{module: hlo_op_names(...)}`` of the executables that
    ran.  An op's module is the ``XLA Modules`` event it starts in; its
    instruction name heads its own name (``%fusion.5 = ...``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, host = {}, [], []
    span_names = set(span_names) | {T.WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            dev = ops[plane.name] = []
            for e in lines.get(T.OPS_LINE, []):
                i = bisect.bisect(starts, e.start_ns) - 1
                m = INSTR.match(e.name)
                scope = ""
                if i >= 0 and m and e.start_ns < mods[i][1]:
                    scope = op_names.get(mods[i][2], {}).get(m.group(1), "")
                dev.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                            scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    end = s + e.duration_ns * 1e-9
                    if e.name in span_names:
                        spans.append((e.name, s, end))
                    elif e.name.startswith(HOST_PREFIX):
                        st = dict(e.stats)
                        host.append((e.name, s, end, int(st.get("rows", 0)),
                                     int(st.get("live", 0))))
    return Scoped(ops=ops, spans=spans, host=host)


def stage_of(scope: str) -> tuple[str, str]:
    """``(jitted op, stage)`` of an op's scope: the outermost ``jit(...)``
    and the stage scope right below it (``jit(op)/<stage>/<primitive>``)."""
    m = JIT.search(scope)
    parts = scope.split("/")
    while parts and parts[0].startswith("jit("):
        parts.pop(0)
    stage = parts[0] if len(parts) > 1 and parts[0] in STAGES else UNSCOPED
    return (m.group(1) if m else "?"), stage


def attribute(dev_ops, lo: float, hi: float) -> collections.Counter:
    """Busy seconds of ``[lo, hi]`` by the ``(jitted op, stage)`` of the
    innermost op running (the latest to start); none while a host-transfer
    wait runs."""
    events = []
    for i, (name, s, d, _) in enumerate(dev_ops):
        s0, s1 = max(s, lo), min(s + d, hi)
        if s1 > s0:
            events += [(s0, 1, i), (s1, 0, i)]
    events.sort()
    out = collections.Counter()
    active, heap, waits, t_prev = set(), [], 0, lo
    for t, starts, i in events:
        if active and not waits and t > t_prev:
            while heap[0][2] not in active:
                heapq.heappop(heap)
            out[stage_of(dev_ops[heap[0][2]][3])] += t - t_prev
        name, s, d, _ = dev_ops[i]
        wait = T.HOST_TRANSFER in name
        if starts:
            active.add(i)
            heapq.heappush(heap, (-s, s + d, i))
            waits += wait
        else:
            active.discard(i)
            waits -= wait
        t_prev = t
    return out


def _measure(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += T._overlap(s, e, *b[k])
            k += 1
    return total


def reduce(tr: Scoped) -> dict:
    """Per-stage busy seconds (averaged over chips), the ``bam.*`` host
    spans, and how much of the device's host-transfer waits they cover.

    ``tokens`` counts the benchmark's ``submit`` and ``wait`` spans that
    start inside the window."""
    win = [(s, e) for n, s, e in tr.spans if n == T.WINDOW_SPAN]
    all_ops = [o for v in tr.ops.values() for o in v]
    if not all_ops:
        return {}
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for _, s, _, _ in all_ops)
        hi = max(s + d for _, s, d, _ in all_ops)
    n_dev = len(tr.ops)
    stage_s, waited, covered = collections.Counter(), 0.0, 0.0
    host = [h for h in tr.host if h[2] > lo and h[1] < hi]
    storage = T.union(((s, e) for n, s, e, _, _ in host
                       if n.startswith(STORAGE_PREFIX)), lo, hi)
    for dev_ops in tr.ops.values():
        stage_s.update(attribute(dev_ops, lo, hi))
        waits = T.union(((s, s + d) for n, s, d, _ in dev_ops
                         if T.HOST_TRANSFER in n), lo, hi)
        waited += sum(e - s for s, e in waits)
        covered += _measure(storage, waits)
    spans = {}
    for name, s, e, rows, live in host:
        c = spans.setdefault(name, {"calls": 0, "s": 0.0, "rows": 0,
                                    "live": 0})
        c["calls"] += 1
        c["s"] += T._overlap(s, e, lo, hi)
        c["rows"] += rows
        c["live"] += live
    tokens = collections.Counter(n for n, s, _ in tr.spans
                                 if n in ("submit", "wait") and lo <= s < hi)
    return {
        "window_s": hi - lo,
        "stage_s": {f"{j}/{st}": t / n_dev
                    for (j, st), t in sorted(stage_s.items())},
        "host_spans": spans,
        "host_callback_s": waited / n_dev,
        "storage_in_callback_s": covered / n_dev,
        "tokens": {"submit": tokens["submit"], "wait": tokens["wait"]},
    }


def op_device_ms(red: dict, op: str) -> float | None:
    """Device ms per token in the jitted ops named ``op...``."""
    kind = "submit" if op.startswith("bam_submit") else "wait"
    n = red["tokens"][kind]
    if not n:
        return None
    return 1e3 * sum(t for k, t in red["stage_s"].items()
                     if k.startswith(op)) / n


def host_callback_body_share(red: dict) -> float | None:
    """% of the device's host-transfer waits that the host spent inside a
    ``bam.storage.*`` span."""
    w = red["host_callback_s"]
    return 100.0 * red["storage_in_callback_s"] / w if w > 0 else None


def callback_useful_share(counts: dict) -> float | None:
    """% of the rows the storage callbacks moved that were live."""
    rows = counts["fetch_rows"] + counts["write_rows"]
    live = counts["fetch_live_rows"] + counts["write_live_rows"]
    return 100.0 * live / rows if rows else None


def window_metrics(red: dict, counts: dict) -> dict:
    """The per-layer numbers of one traced window (no device numbers where
    the trace holds no device)."""
    out = {"callback_useful_share": callback_useful_share(counts)}
    if red:
        out.update(submit_device_ms=op_device_ms(red, "bam_submit"),
                   wait_device_ms=op_device_ms(red, "bam_wait"),
                   host_callback_body_share=host_callback_body_share(red))
    return out


def executables(system, items) -> dict:
    """``{module: hlo_op_names(...)}`` of the submit and wait executables
    that the window runs, from one more token.  Both are in JAX's cache
    already, so nothing compiles again."""
    arr = system.arr
    req = system.request(items)
    texts = [arr.submit_jit(donate=True).lower(system.st, req)
             .compile().as_text()]
    tok = system.submit(req)
    texts.append(arr.wait_jit(donate=True, guard=False)
                 .lower(system.st, tok).compile().as_text())
    system.wait(tok)
    return {MODULE.search(t).group(1): hlo_op_names(t) for t in texts}


@contextlib.contextmanager
def _no_span(*_, **__):
    yield


def measure(cfg: dict, mix: dict, seed: int, windows: int,
            out_dir: str | None = None) -> dict:
    """Set-up, then ``windows`` traced windows (see the module's text)."""
    import jax

    import generator
    import run
    from repro.core import storage as storage_mod

    compile_s = []

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    system = importlib.import_module(f"systems.{cfg['system']}").System(
        cfg, seed)
    traffic = generator.Traffic(mix, seed, system.n_items)
    spans = run.Spans()
    _, k, _ = run.closed_loop(system, traffic, spans, 0,
                              tokens=traffic.warmup_tokens)
    out = {"setup_s": time.perf_counter() - T_START,
           "setup_compile_s": sum(compile_s),
           "setup_compiles": len(compile_s), "windows": []}
    op_names = executables(system, traffic.token(k))
    k += 1
    n_compiled = len(compile_s)
    arr = system.arr
    annotate = storage_mod.TraceAnnotation
    try:
        for i in range(windows):
            on = i % 2 == 0
            storage_mod.TraceAnnotation = annotate if on else _no_span
            c0, n0 = arr.storage.counters(), sum(arr.trace_counts.values())
            tdir = tempfile.mkdtemp(prefix="bench_stages_")
            jax.profiler.start_trace(tdir)
            spans.tracing = True
            with spans("window"):
                done, k, _ = run.closed_loop(system, traffic, spans, k,
                                             seconds=run.TRACE_SECONDS)
            spans.tracing = False
            jax.profiler.stop_trace()
            c1 = arr.storage.counters()
            xp = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))[-1]
            tr = load_xplane(str(xp), run.SPANS, op_names)
            red = reduce(tr)
            counts = {key: c1[key] - c0[key] for key in c0}
            out["windows"].append({
                "storage_spans": on, "tokens": len(done),
                "token_p50_ms": 1e3 * statistics.median(
                    d.latency_s for d in done),
                "retraces": sum(arr.trace_counts.values()) - n0,
                "busy_s": T.reduce(tr.trace()).get("busy_s"),
                "storage_counters": counts, **red,
                **window_metrics(red, counts)})
            if i == 0 and out_dir:
                _keep(out_dir, tr)
            shutil.rmtree(tdir, ignore_errors=True)
    finally:
        storage_mod.TraceAnnotation = annotate
        jax.monitoring.unregister_event_duration_listener(on_duration)
    out["window_compiles"] = len(compile_s) - n_compiled
    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "memory_peak_bytes": (dev.memory_stats() or {})
                     .get("peak_bytes_in_use")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    import run
    _, _, cfg, mix = run.load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(run.ROOT / ".jax_cache"))
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 3
    print(json.dumps(measure(cfg, mix, args.seed, args.windows, args.out)),
          flush=True)
    return 0


def _keep(out_dir: str, tr: Scoped) -> None:
    """Keep the traced window's ops with their scopes and its host spans."""
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with gzip.open(out / "trace.json.gz", "wt") as f:
        json.dump({"ops": tr.ops, "spans": tr.spans, "host": tr.host}, f)


if __name__ == "__main__":
    sys.exit(main())
