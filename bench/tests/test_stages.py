import gzip
import json
import pathlib

import pytest

import stages as S
import tracereduce as T

RECV = ("%pure_callback.11 = (f32[8], token[]) recv-done(%cb), "
        "channel_id=3, is_host_transfer=true")


def test_stage_of():
    assert S.stage_of("jit(bam_wait_donated)/gather/jit(gather_blocks)"
                      "/pallas_call") == ("bam_wait_donated", "gather")
    assert S.stage_of("jit(bam_submit)/probe_allocate/cond/gather") == (
        "bam_submit", "probe_allocate")
    assert S.stage_of("jit(bam_wait)/gather") == ("bam_wait", S.UNSCOPED)
    assert S.stage_of("") == ("?", S.UNSCOPED)


HLO = """HloModule jit_bam_wait, entry_computation_layout={...}
  %copy.1 = s32[8,4]{1,0} copy(%p.0), metadata={op_name="args[0].tags"}
  %probe.2 = s32[8] custom-call(%copy.1), metadata={op_name="jit(bam_wait)/probe/pallas_call"}
  %fusion.3 = s32[8] fusion(%probe.2), kind=kLoop, calls=%fused.1, metadata={op_name="jit(bam_wait)/gather/select_n"}
  %copy.4 = s32[8,4]{0,1} copy(%probe.2)
  ROOT %tuple.5 = (s32[8], s32[8,4]) tuple(%fusion.3, %copy.4)
"""


def test_hlo_op_names():
    """A copy XLA put in takes the stage of its user, else of its
    operand."""
    assert S.MODULE.search(HLO).group(1) == "jit_bam_wait"
    assert S.hlo_op_names(HLO) == {
        "copy.1": "jit(bam_wait)/probe/pallas_call",
        "probe.2": "jit(bam_wait)/probe/pallas_call",
        "fusion.3": "jit(bam_wait)/gather/select_n",
        "copy.4": "jit(bam_wait)/probe/pallas_call",
        "tuple.5": "jit(bam_wait)/gather/select_n"}


def test_reduce_synthetic():
    """Each busy second goes to the innermost op; a host-transfer wait is
    idle, and the stages sum to ``tracereduce``'s busy time."""
    sub, wait = "jit(bam_submit)", "jit(bam_wait)"
    tr = S.Scoped(
        ops={"/device:TPU:0": [
            ("sort.1", 1.0, 1.0, f"{sub}/coalesce/sort"),
            ("cond.8", 3.0, 4.0, f"{wait}/fetch/cond"),
            (RECV, 4.0, 2.5, f"{wait}/fetch/cond/pure_callback"),
            ("fusion.2", 3.5, 0.25, f"{wait}/fill/select"),
            ("copy.3", 8.0, 1.0, ""),
            ("late", 11.0, 1.0, f"{wait}/gather/x")]},
        spans=[("window", 0.0, 10.0), ("submit", 0.5, 2.0),
               ("wait", 2.5, 9.5)],
        host=[("bam.storage.fetch", 4.5, 5.5, 8, 3),
              ("bam.other", 6.0, 6.1, 0, 0)])
    r = S.reduce(tr)
    assert r["stage_s"] == pytest.approx({
        "bam_submit/coalesce": 1.0, "bam_wait/fetch": 0.5 + 0.25 + 0.5,
        "bam_wait/fill": 0.25, "?/unscoped": 1.0})
    assert sum(r["stage_s"].values()) == pytest.approx(
        T.reduce(tr.trace())["busy_s"])
    assert r["host_callback_s"] == pytest.approx(2.5)
    assert r["storage_in_callback_s"] == pytest.approx(1.0)
    assert r["tokens"] == {"submit": 1, "wait": 1}
    assert r["host_spans"]["bam.storage.fetch"] == {
        "calls": 1, "s": 1.0, "rows": 8, "live": 3}
    assert S.op_device_ms(r, "bam_wait") == pytest.approx(1500.0)
    assert S.host_callback_body_share(r) == pytest.approx(40.0)


def test_callback_useful_share():
    counts = {"fetch_rows": 4096, "fetch_live_rows": 3000,
              "write_rows": 4096, "write_live_rows": 0}
    assert S.callback_useful_share(counts) == pytest.approx(
        100.0 * 3000 / 8192)
    assert S.callback_useful_share(dict.fromkeys(counts, 0)) is None


def _recorded():
    p = (pathlib.Path(__file__).with_name("data")
         / "trace_array_rand_v5e_scoped.json.gz")
    d = json.loads(gzip.open(p, "rt").read())
    return S.Scoped(ops={k: [tuple(o) for o in v]
                         for k, v in d["ops"].items()},
                    spans=[tuple(s) for s in d["spans"]],
                    host=[tuple(h) for h in d["host"]])


def test_reduce_recorded_scoped_chip_trace():
    """Four tokens recorded on a TPU v5 lite with the stage scopes and the
    storage callbacks' spans."""
    from metrics import _kernels

    tr = _recorded()
    r, base = S.reduce(tr), T.reduce(tr.trace())
    assert r["tokens"] == {"submit": 4, "wait": 4}
    assert sum(r["stage_s"].values()) == pytest.approx(base["busy_s"])
    assert r["stage_s"]["?/unscoped"] < 0.005 * base["busy_s"]
    assert S.op_device_ms(r, "bam_submit") == pytest.approx(1.72111175)
    assert S.op_device_ms(r, "bam_wait") == pytest.approx(10.94306025)
    assert S.host_callback_body_share(r) == pytest.approx(50.03058880)
    spans = r["host_spans"]
    fetch, write = spans["bam.storage.fetch"], spans["bam.storage.write_back"]
    assert S.callback_useful_share({
        "fetch_rows": fetch["rows"], "fetch_live_rows": fetch["live"],
        "write_rows": write["rows"], "write_live_rows": write["live"],
    }) == pytest.approx(37.09411621)

    # one clock: each fetch body lies inside the device's wait for it
    dev_ops = tr.ops["/device:TPU:0"]
    waits = T.union(((s, s + d) for n, s, d, _ in dev_ops
                     if T.HOST_TRANSFER in n), 0.0, float("inf"))
    bodies = [(s, e) for n, s, e, _, _ in tr.host
              if n == "bam.storage.fetch"]
    assert len(bodies) == 4
    for s, e in bodies:
        assert S._measure([(s, e)], waits) >= 0.95 * (e - s)

    class W:
        trace = base
    assert _kernels.calls(W, "cache_probe")[0] == 4
    assert _kernels.calls(W, "gather_blocks")[0] == 4
