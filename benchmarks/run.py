"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Each module's ``run()`` returns
``[(name, us_per_call, derived), ...]``.

  littles_law              §II-C   T x L = Q_d worked numbers
  ssd_cost                 Tab III $/GB advantage over DRAM
  uvm_bound                Fig 1   UVM fault ceiling vs BaM issue rate
  analytics_amplification  Fig 2   I/O amplification Q1..Q6
  iops_scaling             Fig 6   512B random IOPS vs #SSDs
  graph_analytics          Fig 7   BFS/CC vs DRAM-only target T
  cacheline_sweep          Fig 8   512B..8KB granularity
  ssd_scaling              Fig 9   1..8 SSDs
  device_channels          Fig 7/§IV-A per-device channels: scaling + skew
  taxi_queries             Fig 10  Q1..Q6 end-to-end
  paged_kv                 (beyond paper) KV spill/fetch
  moe_paging               (beyond paper) expert paging
  prefetch_sweep           (beyond paper) readahead window sweep
  mixed_tenants            (§I sharing claim) multi-tenant isolation
  async_overlap            (§II-C) submit/wait token window depth sweep
  hot_path                 (§III-D/E) wall-clock µs/op: fused kernels + jit
  fault_sweep              (robustness) error-rate x retry-budget sweep

Alongside the CSV, every module that runs writes a machine-readable
``BENCH_<module>.json`` artifact (one object per row: name / value /
units / derived, plus backend + versions metadata) — the repo's measured
perf trajectory.  Artifacts land in the repo root by default; set
``BAM_BENCH_OUT=<dir>`` to redirect them, or ``BAM_BENCH_OUT=`` (empty)
to disable writing.

Set ``BAM_BENCH_SMOKE=1`` to shrink every module to smoke-test sizes
(CI); smoke artifacts are stamped ``"smoke": true`` so a tiny-size run is
never mistaken for a trajectory point.
"""
import importlib
import json
import os
import pathlib
import sys
import traceback

MODULES = [
    "littles_law", "ssd_cost", "uvm_bound", "analytics_amplification",
    "iops_scaling", "graph_analytics", "cacheline_sweep", "ssd_scaling",
    "device_channels", "taxi_queries", "paged_kv", "moe_paging",
    "prefetch_sweep", "mixed_tenants", "async_overlap", "hot_path",
    "fault_sweep",
]

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _artifact_dir() -> pathlib.Path | None:
    out = os.environ.get("BAM_BENCH_OUT")
    if out is None:
        return _REPO_ROOT
    if not out:
        return None
    path = pathlib.Path(out)
    path.mkdir(parents=True, exist_ok=True)
    return path


def write_artifact(mod_name: str, rows, out_dir: pathlib.Path) -> pathlib.Path:
    """Write ``BENCH_<module>.json``: the module's rows plus run metadata."""
    import platform
    import time

    import jax

    from benchmarks.common import SMOKE

    payload = {
        "schema": "bam-bench-v1",
        "module": mod_name,
        "smoke": SMOKE,
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "unix_time": time.time(),
        },
        "rows": [
            {"name": name, "value": float(us), "units": "us_per_call",
             "derived": derived}
            for name, us, derived in rows
        ],
    }
    path = out_dir / f"BENCH_{mod_name}.json"
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def main() -> None:
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    only = sys.argv[1:] or MODULES
    out_dir = _artifact_dir()
    print("name,us_per_call,derived")
    failed = []
    artifact_failed = []
    for mod_name in MODULES:
        if mod_name not in only:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows = list(mod.run())
            for name, us, derived in rows:
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:
            failed.append(mod_name)
            print(f"{mod_name},nan,FAILED: {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
            continue
        if out_dir is not None:
            # An unwritable artifact must not masquerade as a benchmark
            # failure: the rows above are real — report it separately.
            try:
                write_artifact(mod_name, rows, out_dir)
            except OSError as e:
                artifact_failed.append(mod_name)
                print(f"bench: could not write BENCH_{mod_name}.json: {e}",
                      file=sys.stderr)
    if failed or artifact_failed:
        raise SystemExit(
            f"benchmarks failed: {failed}; artifacts failed: "
            f"{artifact_failed}")


if __name__ == "__main__":
    main()
