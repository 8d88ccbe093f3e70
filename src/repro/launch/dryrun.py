import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST be the first lines, before any other import: jax locks the device
#   count at first init.  512 placeholder host devices back the production
#   meshes (16x16 single-pod slice, 2x16x16 multi-pod).  The CPU platform
#   is pinned so neither this process nor the per-cell children it starts
#   (which inherit the environment) can claim an attached TPU.

"""Multi-pod dry-run: ``lower().compile()`` every (arch x shape x mesh) cell.

For each cell this builds the full distributed step function — train_step
(fwd+bwd+AdamW) for train cells, last-token prefill forward for prefill
cells, one-token ``serve_step`` with the BaM-paged KV cache for decode
cells — entirely against ShapeDtypeStructs (no allocation), lowers it for
the production mesh, compiles it, and records:

  * ``memory_analysis()``  — proves the cell fits per-device HBM,
  * ``cost_analysis()``    — XLA's own FLOPs/bytes (loop bodies counted 1x),
  * the trip-count-aware HLO walk — FLOPs/bytes/collective-bytes per device
    (feeds EXPERIMENTS.md §Roofline),
  * the collective schedule summary.

Results append incrementally to a JSON file so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (SHAPES, get_config, input_specs, list_archs)
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis, roofline
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.training import optimizer as opt
from repro.training.train_loop import (batch_shardings, make_train_step,
                                       state_shardings)

KEY = jax.random.PRNGKey(0)


def _eval_shape_with_axes(fn):
    """eval_shape a (value, axes) returning fn; capture axes via closure."""
    box = {}

    def wrapped(*args):
        v, a = fn(*args)
        box["axes"] = a
        return v

    sds = jax.eval_shape(wrapped)
    return sds, box["axes"]


def build_cell(cfg, cell, mesh, rules=None, pod_compression=False,
               microbatches: int = 1):
    """Returns (jittable, example_args, in_shardings, out_shardings,
    donate_argnums) for one cell — everything as ShapeDtypeStructs.

    ``pod_compression`` defaults off here: the int8-EF cross-pod reduction
    (tested on 8 fake devices in tests/test_distributed.py) trips an XLA
    SPMD-partitioner CHECK at 512 devices on some graphs (see DESIGN.md
    §known-issues); the baseline multi-pod pass uses the plain reduction.
    """
    api = build_model(cfg, impl="ref")
    with shd.activate(mesh, rules):
        if cell.kind == "train":
            params_sds, axes = _eval_shape_with_axes(
                lambda: api.init(KEY, cell.seq_len))
            acfg = opt.AdamWConfig(
                pod_compression=(pod_compression
                                 and "pod" in mesh.axis_names))
            opt_sds = jax.eval_shape(lambda: opt.adamw_init(params_sds,
                                                            acfg))
            state_sds = {"params": params_sds, "opt": opt_sds}
            st_sh = state_shardings(cfg, axes, mesh, params_sds, acfg)
            batch_sds = input_specs(cfg, cell)
            b_sh = batch_shardings(batch_sds, mesh)
            step = make_train_step(cfg, api, adamw=acfg, mesh=mesh,
                                   microbatches=microbatches)
            return (step, (state_sds, batch_sds), (st_sh, b_sh),
                    (st_sh, None), (0,))

        if cell.kind == "prefill":
            params_sds, axes = _eval_shape_with_axes(
                lambda: api.init(KEY, cell.seq_len))
            p_sh = shd.param_shardings(axes, mesh, shapes_tree=params_sds)
            batch_sds = input_specs(cfg, cell)
            b_sh = batch_shardings(batch_sds, mesh)

            def prefill_step(params, batch):
                from repro.models import hymba, transformer, xlstm
                mod = {"ssm": xlstm, "hybrid": hymba}.get(
                    cfg.family, transformer)
                logits, _ = mod.forward(cfg, params, batch, "ref",
                                        last_only=True)
                return logits

            return (prefill_step, (params_sds, batch_sds), (p_sh, b_sh),
                    None, ())

        # decode
        B, S = cell.global_batch, cell.seq_len
        params_sds, axes = _eval_shape_with_axes(lambda: api.init(KEY, S))
        p_sh = shd.param_shardings(axes, mesh, shapes_tree=params_sds)
        cache_sds, cache_axes = _eval_shape_with_axes(
            lambda: api.init_decode_cache(B, S))
        c_sh = shd.param_shardings(cache_axes, mesh, shapes_tree=cache_sds)
        tok_sds = input_specs(cfg, cell)["tokens"]
        t_sh = NamedSharding(
            mesh, shd._spec_for_shape(["batch"], tok_sds.shape, mesh,
                                      shd.current_rules()))

        def serve_step(params, cache, tokens):
            return api.decode_step(params, cache, tokens)

        return (serve_step, (params_sds, cache_sds, tok_sds),
                (p_sh, c_sh, t_sh), (None, c_sh), (1,))


def run_cell(arch: str, shape: str, multi_pod: bool, rules=None,
             cfg_override=None, pod_compression=False,
             microbatches: int = 1):
    cell = SHAPES[shape]
    cfg = cfg_override or get_config(arch)
    cfg = cfg.replace(use_pallas="ref")
    out = {"arch": arch, "shape": shape,
           "mesh": "multi" if multi_pod else "single"}
    if not cfg.supports_cell(cell):
        out["skipped"] = ("long_500k needs sub-quadratic attention; "
                          f"{arch} is pure full-attention (see DESIGN.md)")
        return out
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with shd.activate(mesh, rules):
        fn, args, in_sh, out_sh, donate = build_cell(
            cfg, cell, mesh, rules, pod_compression=pod_compression,
            microbatches=microbatches)
        # one-shot lowering probe: jitted once per dryrun invocation
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,  # bamlint: ignore[BAM105]
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "per_device_total": (mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes
                             - mem.alias_size_in_bytes),
    }
    try:
        ca = compiled.cost_analysis()
        out["cost_analysis"] = {"flops": ca.get("flops", 0.0),
                                "bytes_accessed": ca.get("bytes accessed",
                                                         0.0)}
    except Exception as e:           # pragma: no cover
        out["cost_analysis"] = {"error": str(e)}
    hc = hlo_analysis.analyze_compiled(compiled)
    out["hlo"] = {"flops": hc.flops, "mem_bytes": hc.mem_bytes,
                  "coll_bytes": hc.coll_bytes,
                  "coll_bytes_effective": hc.total_coll_bytes}
    rf = roofline.Roofline(
        flops_per_device=hc.flops,
        mem_bytes_per_device=hc.mem_bytes,
        coll_bytes_per_device=hc.total_coll_bytes,
        model_flops=roofline.model_flops_for_cell(cfg, cell),
        chips=int(mesh.size))
    out["roofline"] = rf.to_dict()
    out["timings"] = {"lower_s": t_lower, "compile_s": t_compile}
    return out


def _run_cell_subprocess(arch, shape, mp, timeout=1500):
    """Isolate one cell in a child process: a native XLA CHECK abort then
    costs one cell, not the sweep."""
    import subprocess
    import sys
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    Path(tmp).unlink(missing_ok=True)      # child must not read it as JSON
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape,
           "--mesh", "multi" if mp else "single", "--out", tmp, "--force"]
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout)
        data = json.loads(Path(tmp).read_text()) if Path(tmp).exists() \
            else {}
        key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
        if key in data:
            return data[key]
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if mp else "single",
                "error": f"subprocess died rc={p.returncode}",
                "traceback": (p.stderr or "")[-2000:]}
    except subprocess.TimeoutExpired:
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if mp else "single",
                "error": "subprocess timeout"}
    finally:
        Path(tmp).unlink(missing_ok=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--subproc", action="store_true",
                    help="isolate each cell in a child process")
    ap.add_argument("--compressed", action="store_true",
                    help="enable int8-EF pod compression in train cells")
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = {}
    out_path = Path(args.out) if args.out else None
    if out_path and out_path.exists():
        try:
            results = json.loads(out_path.read_text())
        except json.JSONDecodeError:
            results = {}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
                if key in results and not args.force \
                        and "error" not in results[key]:
                    print(f"[skip cached] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                if args.subproc:
                    r = _run_cell_subprocess(arch, shape, mp)
                else:
                    try:
                        r = run_cell(arch, shape, mp,
                                     pod_compression=args.compressed)
                    except Exception as e:
                        r = {"arch": arch, "shape": shape,
                             "mesh": "multi" if mp else "single",
                             "error": f"{type(e).__name__}: {e}",
                             "traceback": traceback.format_exc()[-2000:]}
                results[key] = r
                if out_path:
                    out_path.parent.mkdir(parents=True, exist_ok=True)
                    out_path.write_text(json.dumps(results, indent=1))
                if "error" in r:
                    print(f"  ERROR: {r['error']}")
                elif "skipped" in r:
                    print(f"  SKIPPED: {r['skipped']}")
                else:
                    m = r["memory"]["per_device_total"] / 2**30
                    rf = r["roofline"]
                    print(f"  ok mem/dev={m:.2f}GiB bound={rf['bound']} "
                          f"compute={rf['compute_s']:.4f}s "
                          f"mem={rf['memory_s']:.4f}s "
                          f"coll={rf['collective_s']:.4f}s "
                          f"(compile {r['timings']['compile_s']:.0f}s)")
    n_err = sum(1 for r in results.values() if "error" in r)
    print(f"done: {len(results)} cells, {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
