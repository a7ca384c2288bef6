"""Readers `call_gaps` and `unit_timeline` on hand-made planes: two jobs
with a gap between them, one device (then two), the program's stage and
seam annotations on three host threads."""

import json

import pytest

import harness
import run
import trace_reduce
from test_stage_gaps import write_trace

OP = "%_gf_apply.1 = u8[4,8]{1,0} custom-call(u8[10,8]{1,0} %data.1)"
MODULES = "^XLA Modules$"
kernels_table = harness.kernel_table


@pytest.fixture
def gaps():
    return run.load_module("readers", "call_gaps")


@pytest.fixture
def units():
    return run.load_module("readers", "unit_timeline")


def unit(t: float, device_at: float) -> dict:
    """One unit's annotations from `t`: put 0.2 s, enqueue 0.1 s; its
    program runs 0.5 s from `device_at`; the drain waits from t + 0.3 until
    the program ends and copies back for 0.2 s."""
    return {"h2d": (t, t + 0.2), "dispatch": (t + 0.2, t + 0.3),
            "program": (device_at, device_at + 0.5),
            "wait": (t + 0.3, device_at + 0.5),
            "copy": (device_at + 0.5, device_at + 0.7)}


# job A 1-5 s, job B 7-10 s (two seconds between them), in a 12 s slice
A = [unit(1.5, 2.0), unit(2.0, 3.0)]
B = [unit(7.5, 8.0), unit(8.0, 8.5)]


def planes(with_jobs=True, drop_program=False, skew=(0.0, 0.0)):
    """`skew`: how far the device planes' clock is behind the host
    plane's, in job A's programs and in job B's."""
    caller, drain, dev = [], [], []
    for span, j0, j1, us in (("ec.rebuild", 1.0, 5.0, A),
                             ("ec.encode", 7.0, 10.0, B)):
        if with_jobs:
            caller.append(("job." + span, j0, j1))
        caller.append((span + ".open", j0, us[0]["h2d"][0]))
        for u in us:
            caller += [("codec.h2d", *u["h2d"]),
                       ("codec.dispatch", *u["dispatch"])]
            drain += [("codec.device_wait", *u["wait"]),
                      ("codec.d2h_copy", *u["copy"])]
            late = skew[0] if span == "ec.rebuild" else skew[1]
            dev.append((u["program"][0] - late, u["program"][1] - late))
    if drop_program:
        dev = dev[:-1]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_unit(1)", s, e) for s, e in dev]},
            {"name": "XLA Ops", "events": [(OP, s, e) for s, e in dev]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "worker", "events": caller + [("XlaLinearize", 0., 12.)]},
            {"name": "ec-drain", "events": drain}]}]


def test_idle_inside_the_calls_on_hand_made_planes(gaps):
    t = gaps.table(planes(), kernels_table())
    assert t["window_s"] == pytest.approx(12.0)
    assert t["calls"] == [(1.0, 5.0), (7.0, 10.0)]
    assert t["in_call_s"] == pytest.approx(7.0)
    assert t["jobs"] == {"job.ec.encode": 1, "job.ec.rebuild": 1}
    (dev,) = t["devices"]
    # four programs of 0.5 s inside 7 s of calls; the slice's other 5 s
    # (before, between and after the calls) are idle and outside
    assert dev["idle_in_call_s"] == pytest.approx(5.0)
    assert dev["idle_outside_calls_s"] == pytest.approx(5.0)
    assert t["idle_share"] == pytest.approx(100 * 5.0 / 7.0)
    # job A: open 1-1.5, h2d/dispatch 1.5-1.8 and 2.0-2.3, wait 1.8-2.5 and
    # 2.3-3.5, copy 2.5-2.7 and 3.5-3.7 leave 3.7-5 bare (1.3 s); job B:
    # open 7-7.5, stages to 9.2 leave 9.2-10 bare (0.8 s)
    assert dev["unstaged_s"] == pytest.approx(2.1)
    assert t["unstaged_share"] == pytest.approx(100 * 2.1 / 5.0)
    assert dev["by_stage_s"]["ec.rebuild.open"] == pytest.approx(0.5)
    assert dev["by_stage_s"]["ec.encode.open"] == pytest.approx(0.5)
    # device_wait is idle until its program starts: 1.8-2, 2.5-3 (unit 1
    # waits from 2.3, the device is busy 2-2.5), 7.8-8 and none for the last
    assert dev["by_stage_s"]["codec.device_wait"] == pytest.approx(
        0.2 + 0.5 + 0.2 + 0.0)
    # the slice's own idle seconds, as stage_gaps and the reduction see them
    r = trace_reduce.reduce(planes(), kernels_table())
    assert dev["idle_in_call_s"] + dev["idle_outside_calls_s"] == \
        pytest.approx(r["window_s"] - r["busy_s"])


def test_a_trace_with_no_job_gives_none(gaps, units):
    table = kernels_table()
    assert gaps.table(planes(with_jobs=False), table) is None  # the parent
    assert units.timeline(planes(with_jobs=False), table, MODULES) is None
    assert gaps.table([planes()[1]], table) is None  # no device plane
    assert gaps.table([], table) is None


def test_units_paired_with_their_programs(units):
    t = units.timeline(planes(), kernels_table(), MODULES)
    assert [(u["job"], u["unit"]) for u in t["units"]] == \
        [(0, 0), (0, 1), (1, 0), (1, 1)]
    first = t["units"][0]
    assert first["device"] == "/device:TPU:0"
    assert (first["h2d_start"], first["h2d_end"], first["dispatch_start"],
            first["dispatch_end"], first["program_start"],
            first["program_end"], first["wait_start"], first["copy_start"],
            first["copy_end"]) == \
        pytest.approx((1.5, 1.7, 1.7, 1.8, 2.0, 2.5, 1.8, 2.5, 2.7))
    # upload: 0.5, 1.0, 0.5, 0.5 s; back: 0.2 s each; gaps inside a job
    # only: 2.5 -> 3.0 and 8.5 -> 8.5, never across the two jobs
    assert t["medians"]["upload_ms"] == pytest.approx(500.0)
    assert t["medians"]["return_ms"] == pytest.approx(200.0)
    assert t["medians"]["device_gap_ms"] == pytest.approx(250.0)
    assert t["unpaired"] == []


def test_a_count_mismatch_gives_nothing(units):
    t = units.timeline(planes(drop_program=True), kernels_table(), MODULES)
    assert t["medians"] is None and t["units"] == []
    (bad,) = t["unpaired"]
    assert bad["job"] == "job.ec.encode"
    assert bad["codec.dispatch"] == 2
    assert bad["programs"] == {"/device:TPU:0": 1}


def test_device_planes_behind_the_host_plane_are_shifted(units):
    """The four-chip host's trace of PR 36: every program 'ran' before it
    was enqueued.  The least shift that restores that order is applied and
    stated; one that would end a program after its wait gives nothing."""
    t = units.timeline(planes(skew=(0.4, 0.4)), kernels_table(), MODULES)
    # a program may start when its dispatch does: 0.3 s before it did
    assert t["clock_shift_s"] == pytest.approx(0.1)
    assert t["clock_shift_room_s"] == pytest.approx(0.3)
    assert t["units"][0]["program_start"] == pytest.approx(1.7)
    assert t["medians"]["upload_ms"] == pytest.approx(200.0)
    assert t["medians"]["return_ms"] == pytest.approx(500.0)
    assert t["medians"]["device_gap_ms"] == pytest.approx(250.0)
    agree = units.timeline(planes(), kernels_table(), MODULES)
    assert agree["clock_shift_s"] == 0.0
    # job A's planes behind, job B's ahead: no one shift serves both
    t = units.timeline(planes(skew=(0.4, -0.2)), kernels_table(), MODULES)
    assert t["medians"] is None and t["units"] == []
    assert t["clock_shift_s"] == pytest.approx([0.1, -0.2])


def test_four_chips_share_a_batchs_annotations(units):
    """One `h2d` / `dispatch` / `device_wait` a batch, one program a chip,
    one `d2h_copy` a slot in the order of the chips' planes."""
    two = planes()
    two.insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_unit(1)", u["program"][0] + 0.1, u["program"][1] + 0.1)
            for u in A + B]}]})
    drain = two[2]["lines"][1]["events"]
    for u in A + B:  # the second slot's copy follows the first's
        drain.append(("codec.d2h_copy", u["copy"][1], u["copy"][1] + 0.1))
    t = units.timeline(two, kernels_table(), MODULES)
    assert len(t["units"]) == 8
    chip1 = [u for u in t["units"] if u["device"] == "/device:TPU:1"]
    assert [(u["job"], u["unit"]) for u in chip1] == \
        [(0, 0), (0, 1), (1, 0), (1, 1)]
    assert chip1[0]["h2d_start"] == pytest.approx(1.5)
    assert chip1[0]["program_start"] == pytest.approx(2.1)
    assert (chip1[0]["copy_start"], chip1[0]["copy_end"]) == \
        pytest.approx((2.7, 2.8))


def test_read_leaves_both_tables_and_loads_the_trace_once(
        gaps, units, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    write_trace(harness.WORK_DIR, "ecvol.rebuild_1lost", "2026_01_02",
                b"four")
    loads = []
    monkeypatch.setattr(trace_reduce, "load_planes",
                        lambda path: loads.append(path) or planes())
    ev = {"slice": {"xplane_bytes": 4}}
    assert gaps.read({"slice": {"xplane_bytes": 5}},
                     {"value": "idle_share"}) is None  # another run's trace
    assert gaps.read(ev, {"value": "idle_share"}) == \
        pytest.approx(100 * 5.0 / 7.0)
    assert gaps.read(ev, {"value": "unstaged_share"}) == pytest.approx(42.0)
    for value, want in (("upload_ms", 500.0), ("return_ms", 200.0),
                        ("device_gap_ms", 250.0)):
        assert units.read(ev, {"value": value, "modules_line": MODULES}) \
            == pytest.approx(want)
    assert len(loads) == 1
    out = tmp_path / "out" / "ecvol.rebuild_1lost"
    with open(out / "call_gaps.json") as f:
        assert json.load(f)["idle_outside_calls_s"] == pytest.approx(5.0)
    with open(out / "unit_timeline.json") as f:
        left = json.load(f)
    assert len(left["units"]) == 4 and left["trace"].endswith("vm.xplane.pb")
    # the parent's trace: both leave the metric out, neither raises
    monkeypatch.setattr(trace_reduce, "load_planes",
                        lambda path: planes(with_jobs=False))
    old = {"slice": {"xplane_bytes": 4}}
    assert gaps.read(old, {"value": "idle_share"}) is None
    assert units.read(old, {"value": "upload_ms",
                            "modules_line": MODULES}) is None
