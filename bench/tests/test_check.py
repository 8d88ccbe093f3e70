"""The comparison that decides ``correct``: sound runs pass it, and the
control and each fault the cells can have fail it.  Each runs the whole of
a run at a tiny size on the CPU, skipping only the look for a chip."""
import importlib

import pytest

from conftest import run_tiny, tiny

CELLS = ("array_rand",)


@pytest.mark.parametrize("workload", CELLS)
def test_program_matches_reference(workload):
    r = run_tiny(workload)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"served_elems_per_s", "token_p95_ms",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_counters(workload):
    r = run_tiny(workload, trace=True)
    assert r["correct"] is True
    assert {"hit_rate", "fetch_lines_per_token", "io_amplification",
            "submit_host_ms"} <= set(r["metrics"])


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_fails(workload):
    r = run_tiny(workload, control="bf16")
    assert r["correct"] is False
    assert r["checks"]["value_mismatches"]["value"] > 0


def broken(workload, fault):
    """The cell's system with ``fault`` planted where answers are made."""
    _, cfg, _, _ = tiny(workload)
    base = importlib.import_module(f"systems.{cfg['system']}").System

    class Broken(base):
        def wait(self, handle):
            out = super().wait(handle)
            v = out["values"]
            flat = v.reshape(-1)
            if fault == "altered":          # one answer changed
                flat = flat.at[flat.shape[0] // 3].add(1.0)
            elif fault == "half_left_out":  # half the lanes never served
                flat = flat.at[flat.shape[0] // 2:].set(0.0)
            return dict(out, values=flat.reshape(v.shape))

    return Broken


@pytest.mark.parametrize("workload,fault", [
    ("array_rand", "altered"), ("array_rand", "half_left_out")])
def test_fault_fails(workload, fault):
    r = run_tiny(workload, make_system=broken(workload, fault))
    assert r["correct"] is False, r["checks"]
    assert r["failed"] > 0

