"""Harness tests: run by hand on the CPU at tiny sizes,
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

SEED = 2**31 + 12345          # seeds run past 32 signed bits


def tiny(workload: str):
    """``(cell, cfg, mix, per_layer)`` of ``workload`` at a CPU size: the
    same kinds, mixes and shapes of traffic, scaled down."""
    bench, cell, cfg, mix = run.load_cell(workload)
    cfg.update(n_elems=1 << 16, block_elems=128, num_sets=32,
               num_queues=4, queue_depth=256)
    mix.update(lanes=128, warmup_tokens=2)
    return cell, cfg, mix, bench["per_layer"]


def run_tiny(workload: str, seed: int = SEED, **kw):
    cell, cfg, mix, per_layer = tiny(workload)
    kw.setdefault("seconds", 0.5)
    kw.setdefault("trace", False)
    return run.run_cell(cell, cfg, mix, per_layer, seed=seed,
                        require_tpu=False, log=lambda _: None, **kw)
