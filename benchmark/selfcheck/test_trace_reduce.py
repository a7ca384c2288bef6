import json
import os

import pytest

import harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
GF_APPLY = ("%_gf_apply.1 = u8[4,1048576]{1,0:T(4,128)(4,1)} custom-call("
            "s8[32,128]{1,0:T(8,128)(4,1)} %bitmat.1, u8[10,1048576]{1,0} "
            "%data.1), custom_call_target=\"tpu_custom_call\"")


table = harness.kernel_table


def test_reduce_on_hand_made_planes():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit__gf_apply(123)", 1.0, 1.5),
                ("jit__gf_apply(123)", 3.0, 3.25),
                ("jit_other(9)", 6.0, 6.5)]},
            {"name": "XLA Ops", "events": [
                ("%fusion.1 = u8[8]{0} fusion(u8[8]{0} %p)", 1.0, 1.2),
                (GF_APPLY, 1.1, 1.5), (GF_APPLY, 3.0, 3.25),
                ("%copy.3 = u8[8]{0} copy(u8[8]{0} %p)", 6.0, 6.5)]},
            {"name": "XLA TraceMe", "events": [
                ("barrier-cores", 0.5, 1.0)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ("outer", 0.0, 10.0), ("read", 1.5, 3.0),
                ("write", 3.3, 5.9), ("tiny", 4.0, 4.1)]}]},
    ]
    r = trace_reduce.reduce(planes, table())
    assert r["window_s"] == pytest.approx(10.0)
    (dev,) = r["devices"]
    assert dev["busy_s"] == pytest.approx(0.5 + 0.25 + 0.5)
    assert r["busy_s"] == pytest.approx(1.25)
    assert dev["kernel_s"]["gf_apply"] == pytest.approx(0.65)
    assert dev["kernel_s"]["gf_reconstruct"] == pytest.approx(0.65)
    assert "gf_apply_batch" not in dev["kernel_s"]
    ops = dict(r["device_ops"])
    assert ops["%_gf_apply.1 u8[4,1048576] custom-call"] == \
        pytest.approx(0.65)
    gaps = dict(r["idle_gaps"])
    # idle 1.5-3.0 falls to `read`, 3.25-6.0 mostly to `write`, and the
    # head and tail of the slice to the only event that covers them
    assert gaps["read"] == pytest.approx(1.5)
    assert gaps["write"] == pytest.approx(2.75)
    assert gaps["outer"] == pytest.approx(1.0 + 3.5)


def test_reduce_with_no_device_plane():
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [("a", 0.0, 1.0)]}]}]
    r = trace_reduce.reduce(planes, table())
    assert r["devices"] == [] and r["window_s"] == pytest.approx(1.0)
    assert trace_reduce.reduce([], table())["devices"] == []


def test_recorded_trace():
    """A slice recorded on a TPU v5e by this benchmark (see
    `recorded.json` beside it for what the reduction gave then)."""
    path = os.path.join(HERE, "recorded.xplane.pb")
    with open(os.path.join(HERE, "recorded.json")) as f:
        want = json.load(f)
    r = trace_reduce.reduce(trace_reduce.load_planes(path), table())
    assert len(r["devices"]) == len(want["devices"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    for got, exp in zip(r["devices"], want["devices"]):
        assert got["plane"] == exp["plane"]
        assert got["busy_s"] == pytest.approx(exp["busy_s"])
        assert got["busy_s"] <= r["window_s"]
        assert got["kernel_s"] == pytest.approx(exp["kernel_s"])
        # a kernel's module events lie inside the busy union
        assert max(got["kernel_s"].values()) <= got["busy_s"] * 1.001
    assert r["device_ops"][0][0] == want["device_ops"][0][0]


def test_device_trace_reader_on_the_recorded_trace():
    """Two 1 GB encode calls: 2.0 GB of .dat, 2.0 GB up, 0.8 GB back."""
    import run
    reader = run.load_module("readers", "device_trace")
    trace = trace_reduce.reduce(trace_reduce.load_planes(
        os.path.join(HERE, "recorded.xplane.pb")), table())

    def perf(up, back):
        return {"roofline": {"rows": [
            {"kernel": "encode_parity", "backend": "device",
             "resource": "h2d", "gbytes": up},
            {"kernel": "encode_parity", "backend": "device",
             "resource": "d2h", "gbytes": back},
            {"kernel": "shard_write", "backend": "host",
             "resource": "disk", "gbytes": 99.0}]}}

    ev = {"slice": {"trace": trace, "bytes": 2.0e9, "perf0": perf(1.0, 0.4),
                    "perf1": perf(3.0, 1.2)},
          "kernels": table()["kernels"], "peak": {"hbm": 819e9}}
    kernel_s = trace["devices"][0]["kernel_s"]["gf_apply"]
    read = reader.read
    assert read(ev, {"what": "kernel_s_per_gb", "kernels": ["gf_apply"]}) \
        == pytest.approx(kernel_s / 2.0)
    assert read(ev, {"what": "roofline", "kernel": "gf_apply"}) \
        == pytest.approx(100 * (2.8e9 / 819e9) / kernel_s)
    assert read(ev, {"what": "idle_share"}) == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]))
    assert read(ev, {"what": "devices_busy"}) == 1.0
    assert read(dict(ev, slice=None), {"what": "idle_share"}) is None
