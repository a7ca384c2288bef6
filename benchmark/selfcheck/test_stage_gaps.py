"""Reader `stage_gaps`: a synthetic plane list and the trace-finding rule.
(No slice recorded on a TPU with the program's annotations in it yet: no chip
was free in the session that wrote the reader; PERF.md section 6, PR 25.)"""

import json
import os

import pytest

import harness
import run
import trace_reduce

OP = "%_gf_apply.1 = u8[4,8]{1,0} custom-call(u8[10,8]{1,0} %data.1)"


@pytest.fixture
def reader():
    return run.load_module("readers", "stage_gaps")


kernels_table = harness.kernel_table


def planes(with_stages=True):
    """Ten seconds, one device busy 1-2 and 6-7 (8 s idle), two host
    threads.  The reader thread has `ec.encode.read` open 0-3 and 5-5.5;
    the drain has `codec.device_wait` open 2.5-4 (overlapping the read
    from 2.5 to 3) and 6.5-8.  Idle 4-5, 5.5-6 and 8-10 lies under no
    stage: 3.5 s of the 8."""
    host = [
        {"name": "ec-reader", "events": [
            ("ec.encode.read", 0.0, 3.0), ("ec.encode.read", 5.0, 5.5),
            ("XlaLinearize", 0.0, 10.0)]},
        {"name": "ec-drain", "events": [
            ("codec.device_wait", 2.5, 4.0), ("codec.device_wait", 6.5, 8.0),
            ("np.asarray(jax.Array)", 2.5, 4.0)]}]
    if not with_stages:
        host = [{"name": ln["name"], "events": [
            ev for ev in ln["events"] if not ev[0].startswith(("ec.", "codec."))]}
            for ln in host]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit__gf_apply(1)", 0.5, 9.5)]},
            {"name": "XLA Ops", "events": [(OP, 1.0, 2.0)]},
            {"name": "Async XLA Ops", "events": [("copy-start", 6.0, 7.0)]}]},
        {"name": "/host:CPU", "lines": host}]


def test_idle_by_stage_on_hand_made_planes(reader):
    t = reader.table(planes(), kernels_table())
    assert t["window_s"] == pytest.approx(10.0)
    (dev,) = t["devices"]
    assert dev["plane"] == "/device:TPU:0"
    assert dev["idle_s"] == pytest.approx(8.0)
    # read: idle 0-1, 2-3 and 5-5.5; device_wait: 2.5-4 and 7-8
    assert dev["by_stage_s"] == pytest.approx(
        {"ec.encode.read": 2.5, "codec.device_wait": 2.5})
    assert dev["unattributed_s"] == pytest.approx(3.5)
    assert t["unattributed_share"] == pytest.approx(100 * 3.5 / 8.0)
    assert t["by_stage_share"]["ec.encode.read"] == pytest.approx(31.25)
    # two stages open at once from 2.5 to 3: the parts pass the whole
    assert sum(dev["by_stage_s"].values()) + dev["unattributed_s"] == \
        pytest.approx(8.0 + 0.5)
    assert t["annotations"] == {"ec.encode.read": 2, "codec.device_wait": 2}
    # the same idle seconds as the reduction the result line's breakdown uses
    r = trace_reduce.reduce(planes(), kernels_table())
    assert t["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])


def test_mean_over_devices(reader):
    two = planes()
    two.insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [(OP, 0.0, 8.0)]}]})  # idle 8-10
    t = reader.table(two, kernels_table())
    assert [d["idle_s"] for d in t["devices"]] == pytest.approx([8.0, 2.0])
    assert t["devices"][1]["unattributed_s"] == pytest.approx(2.0)
    assert t["unattributed_share"] == pytest.approx((43.75 + 100.0) / 2)
    assert t["idle_s"] == pytest.approx(5.0)


def test_nothing_to_read_gives_none(reader):
    table = kernels_table()
    assert reader.table(planes(with_stages=False), table) is None  # parent
    assert reader.table([planes()[1]], table) is None  # no device plane
    assert reader.table([], table) is None


def write_trace(work, cell, stamp, data):
    d = os.path.join(work, cell, "trace", "plugins", "profile", stamp)
    os.makedirs(d)
    path = os.path.join(d, "vm.xplane.pb")
    with open(path, "wb") as f:
        f.write(data)
    return path


def test_read_takes_the_running_cells_trace(reader, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    assert reader.read({"slice": {"xplane_bytes": 1}}, {}) is None  # none
    old = write_trace(harness.WORK_DIR, "ecvol.encode", "2026_01_01", b"x")
    os.utime(old, (1, 1))
    new = write_trace(harness.WORK_DIR, "ecvol.rebuild_1lost", "2026_01_02",
                      b"four")
    assert reader.newest_trace() == ("ecvol.rebuild_1lost", new)
    monkeypatch.setattr(trace_reduce, "load_planes", lambda path: planes())
    assert reader.read({"slice": None}, {}) is None
    # another run's trace (the size run.py saw differs): not read
    assert reader.read({"slice": {"xplane_bytes": 5}}, {}) is None
    value = reader.read({"slice": {"xplane_bytes": 4}}, {})
    assert value == pytest.approx(43.75)
    with open(tmp_path / "out" / "ecvol.rebuild_1lost" /
              "stage_gaps.json") as f:
        left = json.load(f)
    assert left["unattributed_share"] == pytest.approx(43.75)
    assert left["trace"].endswith("vm.xplane.pb")
