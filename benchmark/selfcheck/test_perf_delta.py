"""Reader `perf_delta` on two hand-written /perf bodies: the window's two
edges."""

import pytest

import run


def perf(reconstruct, gather, compiles, calls):
    """A /perf body as pipeline.local_snapshot() shapes it, cut to what
    the reader reads."""
    body = {
        "id": "x", "enabled": True, "codecs": [],
        "jobs": [
            {"kind": "ec_rebuild", "state": "done", "stages": {
                "reconstruct": {"busy_s": 99.0, "items": 6.0}}},
            {"kind": "ec_read", "state": "flow", "stages": {
                "reconstruct": {"busy_s": reconstruct[0],
                                "items": reconstruct[1]},
                "gather_survivors": {"busy_s": gather[0],
                                     "items": gather[1]}}}],
        "roofline": {"rows": [
            {"kernel": "reconstruct", "backend": "device",
             "resource": "h2d", "calls": calls, "gbytes": 1.0},
            {"kernel": "reconstruct", "backend": "device",
             "resource": "device", "calls": calls, "gbytes": 1.0},
            {"kernel": "reconstruct", "backend": "host",
             "resource": "host", "calls": 10_000, "gbytes": 1.0},
            {"kernel": "encode_parity", "backend": "device",
             "resource": "h2d", "calls": 777, "gbytes": 1.0}]}}
    if compiles is not None:
        body["compiles"] = compiles
    return body


@pytest.fixture
def reader():
    return run.load_module("readers", "perf_delta").read


STAGE = {"what": "stage_ms", "flow": "ec_read", "stage": "reconstruct"}
PER_CALL = {"what": "compiles_per_call", "entry": "reconstruct",
            "kernel": "reconstruct"}
SHARE = {"what": "compile_share", "entry": "reconstruct", "flow": "ec_read",
         "stage": "reconstruct"}


def test_deltas_between_the_windows_edges(reader):
    ev = {"window": {
        "perf0": perf((10.0, 20.0), (1.0, 20.0),
                      {"reconstruct": {"count": 100, "seconds": 9.0},
                       "other": {"count": 50, "seconds": 5.0}}, 20),
        "perf1": perf((230.0, 220.0), (1.5, 220.0),
                      {"reconstruct": {"count": 1300, "seconds": 207.0},
                       "other": {"count": 51, "seconds": 5.5}}, 220)}}
    assert reader(ev, STAGE) == pytest.approx(1100.0)  # 220 s over 200
    assert reader(ev, dict(STAGE, stage="gather_survivors")) == \
        pytest.approx(2.5)
    assert reader(ev, PER_CALL) == pytest.approx(6.0)  # 1200 over 200
    assert reader(ev, SHARE) == pytest.approx(90.0)  # 198 s of 220 s


def test_an_entry_point_first_seen_inside_the_window(reader):
    ev = {"window": {
        "perf0": perf((0.0, 0.0), (0.0, 0.0), {}, 0),
        "perf1": perf((4.0, 4.0), (0.25, 4.0),
                      {"reconstruct": {"count": 24, "seconds": 3.0}}, 4)}}
    assert reader(ev, PER_CALL) == pytest.approx(6.0)
    assert reader(ev, SHARE) == pytest.approx(75.0)
    ev["window"]["perf0"]["jobs"] = []  # the flow itself is new
    assert reader(ev, STAGE) == pytest.approx(1000.0)


def test_a_program_without_the_counter_reports_nothing(reader):
    """The parent of the PR that added the compile counter: /perf has no
    `compiles`, and its stage seconds are still read."""
    ev = {"window": {"perf0": perf((1.0, 1.0), (0.1, 1.0), None, 1),
                     "perf1": perf((3.0, 3.0), (0.3, 3.0), None, 3)}}
    assert reader(ev, PER_CALL) is None and reader(ev, SHARE) is None
    assert reader(ev, STAGE) == pytest.approx(1000.0)
    assert reader(ev, dict(STAGE, stage="remote_fetch")) is None
    assert reader(ev, dict(STAGE, flow="no_such_flow")) is None


def test_a_window_in_which_nothing_moved_reports_nothing(reader):
    edge = perf((1.0, 1.0), (0.1, 1.0),
                {"reconstruct": {"count": 6, "seconds": 1.0}}, 1)
    ev = {"window": {"perf0": edge, "perf1": edge}}
    for params in (STAGE, PER_CALL, SHARE):
        assert reader(ev, params) is None
    assert reader({"window": {}}, STAGE) is None
    with pytest.raises(ValueError):
        reader(ev, {"what": "nonsense"})
