import pytest

import tracereduce as T


def test_union_and_gaps():
    busy = T.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (9.0, 12.0)],
                   0.0, 10.0)
    assert busy == [(0.0, 2.0), (3.0, 4.0), (9.0, 10.0)]
    assert T.gaps(busy, 0.0, 10.0) == [(2.0, 3.0), (4.0, 9.0)]


def test_reduce_synthetic():
    tr = T.Trace(
        ops={"/device:TPU:0": [("fusion.1", 1.0, 1.0), ("probe", 1.5, 1.0),
                               ("probe", 6.0, 2.0), ("late", 11.0, 1.0)]},
        spans=[("window", 0.0, 10.0), ("submit", 0.0, 1.0),
               ("wait", 2.5, 5.9), ("make_traffic", 8.0, 10.0)])
    r = T.reduce(tr)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(1.5 + 2.0)
    assert r["op_calls"] == {"fusion.1": 1, "probe": 2}
    assert r["op_time"]["probe"] == pytest.approx(3.0)
    idle = dict(r["idle_gaps"])
    assert idle == pytest.approx({"submit": 1.0, "wait": 3.5,
                                  "make_traffic": 2.0})


def test_host_transfer_waits_are_idle():
    """The device computes nothing while it waits on a host callback: a
    ``recv-done`` inside an enclosing conditional counts as idle."""
    recv = ("%pure_callback.11 = (f32[8], token[]) recv-done(%cb), "
            "channel_id=3, is_host_transfer=true")
    tr = T.Trace(
        ops={"/device:TPU:0": [("cond.8", 1.0, 4.0), (recv, 2.0, 2.5),
                               ("probe", 6.0, 1.0)]},
        spans=[("window", 0.0, 10.0), ("wait", 0.0, 10.0)])
    r = T.reduce(tr)
    assert r["busy_s"] == pytest.approx(4.0 - 2.5 + 1.0)
    assert r["op_time"][recv] == pytest.approx(2.5)
    idle = dict(r["idle_gaps"])
    assert idle == pytest.approx({"host_callback": 2.5, "wait": 5.0})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def _recorded():
    import gzip
    import json
    import pathlib
    p = pathlib.Path(__file__).with_name("data") / "trace_array_rand_v5e.json.gz"
    d = json.loads(gzip.open(p, "rt").read())
    return T.Trace(ops=d["ops"], spans=[tuple(s) for s in d["spans"]])


def test_reduce_recorded_chip_trace():
    """A window of about three tokens recorded on a TPU v5 lite: every
    submit/wait pair holds one call of each kernel."""
    from metrics import _kernels

    r = T.reduce(_recorded())
    assert r["window_s"] == pytest.approx(0.3920094)
    assert r["busy_s"] == pytest.approx(0.05091513800000835)
    idle = dict(r["idle_gaps"])
    assert set(idle) <= {"host_callback", "submit", "wait", "other"}
    assert idle["host_callback"] == pytest.approx(0.30584489100000023)
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert max(t for _, t in r["device_ops"]) <= r["window_s"]

    class W:
        trace = r
    assert _kernels.calls(W, "cache_probe")[0] == 4
    assert _kernels.calls(W, "gather_blocks")[0] == 4
    assert _kernels.calls(W, "cache_probe")[1] == pytest.approx(
        0.034573091000000056)
