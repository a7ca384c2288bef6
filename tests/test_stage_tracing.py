"""The EC plane's one stage primitive (stats/pipeline.Stage), driven
through the four engines on the `jax` codec: what it leaves on the
profiler's trace, in the engines' stats dicts, in the compile counter and
on /perf; plus the two ways to open a profiler session on a server
(`--jax-profile`, `/debug/jax_profile`) and the SIGTERM that flushes."""

import asyncio
import glob
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
import types

import jax
import numpy as np
import pytest

from seaweedfs_tpu.ops import codec_base, dispatch, fleet_convert
from seaweedfs_tpu.stats import pipeline, profile, trace
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import grace
from tests.test_observability import _mock_req

LARGE, SMALL, BATCH = 10000, 100, 1000
KINDS = ["encode", "rebuild", "fleet", "degraded_read"]
# and the fleet stream over the unit-sharded mesh, whose units go up as
# spans of the `.dat` maps (in-process only: it needs the virtual devices)
MESH_KINDS = KINDS + ["fleet_spans"]
SEAM = {"codec.h2d", "codec.dispatch", "codec.device_wait",
        "codec.d2h_copy"}
# the engine's own stages that a tiny run on a device codec must show
# (`stall` is there too whenever a pooled buffer was waited for); since
# PR 36 the call's head, tail and waits are stages too
ENGINE = {"encode": {"ec.encode.read", "ec.encode.write_data",
                     "ec.encode.write_parity", "ec.encode.open",
                     "ec.encode.map",
                     "ec.encode.ship_data", "ec.encode.await_unit",
                     "ec.encode.await_parity", "ec.encode.join_drain",
                     "ec.encode.join_writers", "ec.encode.commit"},
          "rebuild": {"ec.rebuild.stage", "ec.rebuild.unstage",
                      "ec.rebuild.write", "ec.rebuild.open",
                      "ec.rebuild.map",
                      "ec.rebuild.await_batch", "ec.rebuild.join_drain",
                      "ec.rebuild.join_writers", "ec.rebuild.commit"},
          "fleet": {"ec.fleet.read", "ec.fleet.write_data",
                    "ec.fleet.write_parity", "ec.fleet.open",
                    "ec.fleet.ship_data", "ec.fleet.await_unit",
                    "ec.fleet.await_parity", "ec.fleet.join_drain",
                    "ec.fleet.join_writers", "ec.fleet.commit"},
          "degraded_read": {"ec.read.local_pread",
                            "ec.read.gather_survivors",
                            "ec.read.reconstruct"}}
ENGINE["fleet_spans"] = ENGINE["fleet"]
# the bulk engines: kind -> (job kind, the job's span)
BULK = {"encode": ("ec_encode", "ec.encode"),
        "rebuild": ("ec_rebuild", "ec.rebuild"),
        "fleet": ("fleet_convert", "ec.fleet"),
        "fleet_spans": ("fleet_convert", "ec.fleet")}
# stages whose annotation carries no `unit`: a writer's batch spans
# several, and the call's own stages belong to no unit
NO_UNIT = (".write", ".open", ".map", ".await_", ".join_", ".commit")
# every key /admin/ec/progress `stages` carried before the seam's cut
# (the parent commit's stats dicts of the same tiny runs)
OLD_KEYS = {
    "encode": {"backend", "bytes", "d2h_s", "encode_s", "mode",
               "overlap_frac", "read_s", "stall_s", "wall_s",
               "write_data_s", "write_data_workers", "write_parity_s",
               "write_parity_workers"},
    "rebuild": {"bytes", "codec", "mode", "overlap_frac",
                "reconstruct_s", "stall_s", "wall_s", "write_s",
                "write_workers"},
    "fleet": {"backend", "bytes", "committed_bases", "d2h_s",
              "devices", "encode_s", "mode", "overlap_frac", "read_s",
              "stall_s", "unit_batch", "units", "volumes", "wall_s",
              "write_data_s", "write_data_workers", "write_parity_s",
              "write_parity_workers"},
    "degraded_read": {"gather_survivors", "local_pread", "reconstruct"},
}
# PR 33: the fleet job says how many stripe rows it copied on the host
OLD_KEYS["fleet"].add("rows_staged")
OLD_KEYS["fleet_spans"] = OLD_KEYS["fleet"]
# PR 35: the rebuild job says how many batches were out at once
OLD_KEYS["rebuild"].add("inflight_max")
# PR 36: the call's clock, its head, tail and waits, and the gauge
for _kind, _more in (("encode", {"ship_data_s", "await_unit_s",
                                 "await_parity_s"}),
                     ("rebuild", {"await_batch_s"}),
                     ("fleet", {"ship_data_s", "await_unit_s",
                                "await_parity_s"})):
    OLD_KEYS[_kind] |= _more | {
        "call_s", "open_s", "join_drain_s", "join_writers_s", "commit_s",
        "inflight_max", "inflight_avg", "inflight_ge2_frac"}
# PR 37: the single-volume engines map their sources in a stage of its
# own and count the spans they select there
for _kind in ("encode", "rebuild"):
    OLD_KEYS[_kind] |= {"map_s", "spans_mapped"}
# the fleet stream maps its volumes the same way
OLD_KEYS["fleet"] |= {"map_s", "spans_mapped"}


@pytest.fixture(autouse=True)
def _jax_codec(monkeypatch):
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    monkeypatch.delenv("WEEDTPU_CONVERT_CODEC", raising=False)
    pipeline.reset()
    yield
    pipeline.reset()


def _make_ec(tmp_path, n=30):
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(5)
    blobs = {}
    for i in range(1, n + 1):
        data = rng.integers(0, 256, int(rng.integers(1, 4000)),
                            dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=BATCH)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, blobs


def prepare(kind: str, tmp_path):
    """Untimed set-up of one tiny run -> op(); op() runs it and returns
    (what carries the stage seconds, the id its annotations carry)."""
    tmp_path.mkdir(exist_ok=True)
    rng = np.random.default_rng(11)
    if kind == "encode":
        base = str(tmp_path / "1")
        rng.integers(0, 256, 230_000, dtype=np.uint8).tofile(base + ".dat")

        def op():
            stats: dict = {}
            ec_files.write_ec_files(base, large_block=LARGE,
                                    small_block=SMALL, batch_size=BATCH,
                                    stats=stats)
            return stats, ("job", _last_job("ec_encode")["id"])
    elif kind == "rebuild":
        base, _ = _make_ec(tmp_path)
        os.remove(base + layout.to_ext(3))

        def op():
            stats: dict = {}
            assert ec_files.rebuild_ec_files(base, batch_size=BATCH,
                                             stats=stats) == [3]
            return stats, ("job", _last_job("ec_rebuild")["id"])
    elif kind in ("fleet", "fleet_spans"):
        bases = []
        for i, size in enumerate((150_000, 99_777)):
            bases.append(str(tmp_path / f"f{i}"))
            rng.integers(0, 256, size, dtype=np.uint8).tofile(
                bases[-1] + ".dat")
        codec = None  # the one-device XLA shell: a dispatch a unit
        if kind == "fleet_spans":
            from seaweedfs_tpu.models import rs
            from seaweedfs_tpu.parallel import mesh as pmesh
            codec = pmesh.FleetUnitEncoder(
                rs.get_code(10, 4), pmesh.make_mesh(8, ("unit",)))

        def op():
            stats: dict = {}
            fleet_convert.convert_volumes(
                bases, large_block=LARGE, small_block=SMALL,
                batch_size=BATCH, stats=stats, codec=codec)
            assert stats["backend"] == (
                "JaxRSCodec" if codec is None else "FleetUnitEncoder")
            # the one volume's last, short row: units go up from the
            # maps under either codec
            assert stats["rows_staged"] == 1
            return stats, ("job", _last_job("fleet_convert")["id"])
    else:
        base, blobs = _make_ec(tmp_path)
        for sid in (0, 1):
            os.remove(base + layout.to_ext(sid))

        def op():
            root = trace.new_root()
            token = trace._current.set(root)
            ev = ec_volume.EcVolume(base)
            try:
                for nid, data in blobs.items():
                    assert ev.read_needle(nid).data == data
            finally:
                ev.close()
                trace._current.reset(token)
            return _last_job("ec_read")["stages"], ("trace", root.trace_id)
    return op


def _last_job(kind: str) -> dict:
    return next(j for j in pipeline.jobs_snapshot() if j["kind"] == kind)


def _annotations(trace_dir: str) -> dict[str, list[dict]]:
    """{event name: [its stats]} of the program's annotations in the
    newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        assert not plane.name.startswith("/device:") or not any(
            e.name.startswith(("ec.", "codec.", "job."))
            for ln in plane.lines for e in ln.events)
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(("ec.", "codec.", "job.")):
                    found.setdefault(e.name, []).append(dict(
                        {k: v for k, v in e.stats}, t0=e.start_ns,
                        t1=e.start_ns + e.duration_ns, thread=ln.name))
    return found


# -- (a) the profiler session is the switch ---------------------------------

@pytest.mark.parametrize("kind", MESH_KINDS)
def test_stages_annotate_the_profilers_trace(kind, tmp_path):
    op = prepare(kind, tmp_path / "data")
    assert pipeline._profiler_annotation() is None  # no session: nothing
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        assert pipeline._profiler_annotation() is jax.profiler.TraceAnnotation
        _, (id_key, id_value) = op()
    finally:
        jax.profiler.stop_trace()
    assert pipeline._profiler_annotation() is None
    found = _annotations(str(tmp_path / "trace"))
    assert SEAM | ENGINE[kind] <= set(found), sorted(found)
    for name in SEAM | ENGINE[kind]:
        mine = [s for s in found[name] if str(s.get(id_key)) ==
                str(id_value)]
        assert mine, (name, id_key, id_value, found[name][:3])
        if kind != "degraded_read" and not any(x in name for x in NO_UNIT):
            # bulk stages say which unit
            assert {int(s["unit"]) for s in mine} >= {0}


@pytest.mark.parametrize("kind", sorted(BULK))
def test_the_job_is_on_the_trace_round_every_stage_of_the_call(
        kind, tmp_path):
    """`job.<span>` carries the job id and holds every annotation the job
    left, on whichever thread, from `open` to the renames in `commit`:
    `finish()` comes after the commit in all three engines."""
    op = prepare(kind, tmp_path / "data")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _, (_, job_id) = op()
    finally:
        jax.profiler.stop_trace()
    found = _annotations(str(tmp_path / "trace"))
    span = BULK[kind][1]
    mine = [s for s in found["job." + span] if int(s["job"]) == job_id]
    assert len(mine) == 1, found["job." + span]
    job, = mine
    stages = [(name, s) for name, evs in found.items()
              if not name.startswith("job.") for s in evs
              if str(s.get("job")) == str(job_id)]
    assert {name for name, _ in stages} >= SEAM | ENGINE[kind]
    for name, s in stages:
        assert job["t0"] <= s["t0"] and s["t1"] <= job["t1"], (name, s, job)
    # the commit is the call's last stage, on the thread the job is on
    last = max((s for name, s in stages
                if name == span + ".commit" and s["thread"] == job["thread"]),
               key=lambda s: s["t1"])
    assert last["t1"] == max(s["t1"] for _, s in stages
                             if s["thread"] == job["thread"])
    # no stage reader may take the job for a stage
    assert not [n for n in found if n.startswith("job.")
                and n.startswith(("ec.", "codec."))]


def test_no_session_builds_no_annotation_object(tmp_path, monkeypatch):
    """The off path: with no profiler session open neither a stage nor a
    job constructs an annotation."""
    built = []

    class Closed:
        def __init__(self, *a, **kw):
            built.append(a)

        @staticmethod
        def is_enabled():
            return False

    ec_files._get_codec("jax")  # jax is loaded: the gate has a class to ask
    monkeypatch.setattr(pipeline, "_trace_annotation", Closed)
    for kind in sorted(BULK):
        prepare(kind, tmp_path / kind)()
    assert built == []
    job = pipeline.track("t", span="ec.t")
    with job.stage("s"), job.blocked("w"):
        pass
    job.finish()
    assert built == [] and job._ann is None


def test_a_session_opened_after_the_run_holds_no_annotation(tmp_path):
    prepare("encode", tmp_path / "data")()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    jax.profiler.stop_trace()
    assert _annotations(str(tmp_path / "trace")) == {}


def test_host_codec_process_stages_initialise_no_jax_backend(tmp_path):
    """All four engines on the native codec in a fresh process: the stage
    primitive looks for a profiler session (jax is loaded:
    ops.native_codec imports it) and must bring up no backend doing so."""
    from seaweedfs_tpu import native
    if not native.available():
        pytest.skip("no native codec here")
    code = (
        "import os, pathlib, sys\n"
        "os.environ['WEEDTPU_EC_CODEC'] = 'cpp'\n"
        "os.environ.pop('WEEDTPU_CONVERT_CODEC', None)\n"
        "from tests import test_stage_tracing as t\n"
        "from seaweedfs_tpu.stats import pipeline\n"
        "for kind in t.KINDS:\n"
        "    if kind != 'fleet':  # its op asserts the jax codec: below\n"
        "        t.prepare(kind, pathlib.Path(sys.argv[1]) / kind)()\n"
        "import numpy as np\n"
        "from seaweedfs_tpu.ops import fleet_convert\n"
        "b = str(pathlib.Path(sys.argv[1]) / 'v')\n"
        "np.zeros(50_000, np.uint8).tofile(b + '.dat')\n"
        "fleet_convert.convert_volumes([b], large_block=10000,\n"
        "                              small_block=100, batch_size=1000)\n"
        "assert pipeline._profiler_annotation() is None\n"
        "assert pipeline.local_snapshot()['compiles'] == {}\n"
        "from jax._src import xla_bridge\n"
        "assert 'jax' in sys.modules\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    env = {k: v for k, v in os.environ.items() if k != "WEEDTPU_EC_CODEC"}
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       capture_output=True, text=True, timeout=180,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]


# -- (b) the lumps are the sums of their parts ------------------------------

def _sum(stats, *keys):
    return sum(stats[k] for k in keys)


@pytest.mark.parametrize("kind", MESH_KINDS)
def test_sum_identities_and_every_old_key(kind, tmp_path):
    stats, _ = prepare(kind, tmp_path)()
    assert OLD_KEYS[kind] <= set(stats), OLD_KEYS[kind] - set(stats)
    if kind in ("encode", "fleet", "fleet_spans"):
        assert stats["encode_s"] == pytest.approx(
            _sum(stats, "h2d_s", "dispatch_s"), rel=1e-9)
        assert stats["d2h_s"] == pytest.approx(
            _sum(stats, "device_wait_s", "d2h_copy_s"), rel=1e-9)
    elif kind == "rebuild":
        assert stats["reconstruct_s"] == pytest.approx(
            _sum(stats, "stage_s", "h2d_s", "dispatch_s", "device_wait_s",
                 "d2h_copy_s", "unstage_s"), rel=1e-9)
        assert stats["overlap_frac"] == ec_files.overlap_fraction(stats)
    else:
        # the read engine nests: `reconstruct` holds the seam's four
        parts = sum(stats[s]["busy_s"] for s in
                    ("h2d", "dispatch", "device_wait", "d2h_copy"))
        assert 0 < parts <= stats["reconstruct"]["busy_s"] + 1e-4
        assert stats["reconstruct"]["items"] == stats["dispatch"]["items"]
    if kind != "degraded_read":
        # a part is never counted beside its lump, and neither a clock
        # nor a blocked stage counts as work
        stage_sum = sum(v for k, v in stats.items() if k.endswith("_s")
                        and k not in ec_files._NOT_WORK_KEYS
                        and k not in ec_files._PART_KEYS)
        assert stats["overlap_frac"] == round(
            max(0.0, 1.0 - stats["wall_s"] / stage_sum), 3)
        assert {"open_s", "commit_s"} <= set(stats)
        assert stats["call_s"] >= stats["wall_s"] > 0
        # blocked stages book as blocked, never busy
        stages = _last_job(BULK[kind][0])["stages"]
        for name in ("await_unit", "await_parity", "await_batch",
                     "join_drain", "join_writers"):
            if name in stages:
                assert stages[name]["busy_s"] == 0, (name, stages[name])
                assert stages[name]["blocked_s"] > 0, (name, stages[name])
        assert _last_job(BULK[kind][0])["bottleneck"]["stage"] not in (
            "await_unit", "await_parity", "await_batch", "join_drain",
            "join_writers", "stall")


@pytest.mark.parametrize("kind", sorted(BULK))
def test_the_callers_stages_add_up_to_call_s(kind, tmp_path, monkeypatch):
    """On the thread that makes the call its stages follow one another
    from the job's first line to its last.  A slowed map and a slowed
    rename (the head and the tail) make the call long beside what lies
    between two stages; the program itself sleeps nowhere.  Every engine
    maps its sources in a stage of its own, `map` (`_map_lazy`), after
    `open` on the calling thread."""
    real_lazy, real_replace = ec_files._map_lazy, os.replace

    def slow_lazy(fd):
        time.sleep(0.05)
        return real_lazy(fd)

    def slow_replace(src, dst):
        time.sleep(0.005)
        return real_replace(src, dst)

    op = prepare(kind, tmp_path)
    monkeypatch.setattr(ec_files, "_map_lazy", slow_lazy)
    monkeypatch.setattr(fleet_convert, "_map_lazy", slow_lazy)
    monkeypatch.setattr(os, "replace", slow_replace)
    booked = []
    real = pipeline.PipelineJob._book

    def book(self, name, secs, nbytes, items, blocked):
        if self.kind == BULK[kind][0]:
            booked.append((name, secs, threading.current_thread().name))
        return real(self, name, secs, nbytes, items, blocked)

    monkeypatch.setattr(pipeline.PipelineJob, "_book", book)
    stats, _ = op()
    me = threading.current_thread().name
    mine = [(name, secs) for name, secs, thread in booked if thread == me]
    assert {"open", "h2d", "dispatch", "join_drain", "commit"} <= \
        {name for name, _ in mine}
    total = sum(secs for _, secs in mine)
    assert total == pytest.approx(stats["call_s"], rel=0.05), (
        stats["call_s"], sorted(mine))
    # the slowed map, in `map` on this thread and so inside the sum above
    assert stats["map_s"] >= 0.05 and stats["commit_s"] >= 0.005
    assert "map" in {name for name, _ in mine}


def test_occupancy_states_max_mean_and_share_at_two_or_more(monkeypatch):
    """The one gauge on a scripted clock: a count weighted by the time it
    stood, over the engine's `wall_s`."""
    ticks = iter([0.0,             # the job is made
                  1.0, 2.0, 4.0,   # +1, +1, +1
                  5.0, 7.0, 8.0,   # -1, -1, -1
                  10.0, 10.0])     # finish; the second job is made
    monkeypatch.setattr(pipeline, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ticks), time=time.time,
        monotonic=time.monotonic))
    stats = {"wall_s": 10.0}
    job = pipeline.PipelineJob("t", stats, register=False)
    for delta in (+1, +1, +1, -1, -1, -1):
        job.occupancy("inflight", delta)
    job.finish()
    # levels 0, 1, 2, 3, 2, 1, 0 stood 1, 1, 2, 1, 2, 1, 2 seconds
    assert stats["inflight_max"] == 3
    assert stats["inflight_avg"] == pytest.approx(
        (1 * 1 + 2 * 2 + 3 * 1 + 2 * 2 + 1 * 1) / 10.0)
    assert stats["inflight_ge2_frac"] == pytest.approx((2 + 1 + 2) / 10.0)
    assert stats["call_s"] == 10.0
    # a job whose gauge never moved states none of the three
    assert "inflight_max" not in pipeline.PipelineJob(
        "u", register=False).stats


def test_occupancy_loses_no_move_under_many_threads():
    """The gauge is moved from the dispatcher and the drain at once: more
    threads than cores, a short switch interval, and every +1 has its -1."""
    job = pipeline.PipelineJob("t", register=False)
    workers, moves = 32, 400

    def churn():
        for _ in range(moves):
            job.occupancy("inflight", +1)
            job.occupancy("inflight", -1)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    level, _, seconds, _, peak = job._gauges["inflight"]
    assert level == 0 and 1 <= peak <= workers and seconds >= 0
    job.finish()
    assert job.stats["inflight_max"] == peak
    assert 0 <= job.stats["inflight_ge2_frac"] <= 1


def test_a_failed_rebuild_still_finishes_its_job(tmp_path, monkeypatch):
    """A survivor that vanishes between the present-list and its open
    fails the call inside `open`: the job is sealed `failed` with the
    error, the tmp output is rolled back, and `call_s` is stated."""
    base, _ = _make_ec(tmp_path)
    os.remove(base + layout.to_ext(3))
    real = ec_files._survivor_basis

    def vanish(codec, present, wanted):
        use = real(codec, present, wanted)
        os.remove(base + layout.to_ext(use[-1]))
        return use

    monkeypatch.setattr(ec_files, "_survivor_basis", vanish)
    stats: dict = {}
    with pytest.raises(FileNotFoundError):
        ec_files.rebuild_ec_files(base, batch_size=BATCH, stats=stats)
    job = _last_job("ec_rebuild")
    assert job["state"] == "failed" and "No such file" in job["error"]
    assert not [j for j in pipeline.jobs_snapshot()
                if j["state"] == "running"]
    assert stats["call_s"] > 0 and "open_s" in stats and \
        "commit_s" in stats
    assert not os.path.exists(base + layout.to_ext(3) + ".tmp")
    assert not os.path.exists(base + layout.to_ext(3))


@pytest.mark.parametrize("kind", ["ec_regen", "ec_scrub"])
def test_regen_and_the_scrubber_book_the_seam_to_a_flow(kind, tmp_path):
    """Both call the seam outside any job: their four `codec.*` stages
    land on a flow account of their own on /perf, as a read's do."""
    codec = ec_files._get_codec("jax")
    if kind == "ec_scrub":
        from seaweedfs_tpu.maintenance import scrub
        base, _ = _make_ec(tmp_path)
        ev = ec_volume.EcVolume(base)
        try:
            assert scrub.syndrome_scan(ev, window=SMALL * 2) == []
        finally:
            ev.close()
    else:
        from seaweedfs_tpu.models import rs
        from seaweedfs_tpu.ops import regen
        from tests import test_regen as tr
        shards = rs.get_code(10, 4).encode_numpy(
            np.random.default_rng(1).integers(0, 256, (10, tr.L),
                                              dtype=np.uint8))
        out = np.zeros(tr.L, dtype=np.uint8)

        def sink(off, row):
            out[off:off + len(row)] = row

        regen.repair_shard(
            tr.CODE, codec, 2, tr._groups({2}), tr.L,
            lambda sid, off, n: shards[sid][off:off + n].tobytes(),
            tr._fetcher(shards, {}), sink, batch_size=4096, align=1024)
        assert np.array_equal(out, shards[2])
    flow = _last_job(kind)
    assert flow["state"] == "flow"
    for name in ("h2d", "dispatch", "device_wait", "d2h_copy"):
        assert flow["stages"][name]["busy_s"] > 0, (name, flow["stages"])
        assert flow["stages"][name]["items"] >= 1


def test_rebuild_books_its_six_stages_to_one_job_from_two_threads(
        tmp_path, monkeypatch):
    """Batches are in flight (ec_files._rebuild_pipelined): the calling
    thread selects and enqueues, the drain thread waits, copies back and
    unstages, every stage once a batch to the one `ec_rebuild` job."""
    booked = []
    real = pipeline.PipelineJob._book

    def book(self, name, secs, nbytes, items, blocked):
        if self.kind == "ec_rebuild" and items:
            booked.append((name, self.job_id,
                           threading.current_thread().name))
        return real(self, name, secs, nbytes, items, blocked)

    monkeypatch.setattr(pipeline.PipelineJob, "_book", book)
    op = prepare("rebuild", tmp_path)
    booked.clear()
    stats, (_, job_id) = op()
    me = threading.current_thread().name
    by_stage = {name: {(job, thread) for n, job, thread in booked
                       if n == name} for name, _, _ in booked}
    for name in ("stage", "h2d", "dispatch"):
        assert by_stage[name] == {(job_id, me)}, name
    for name in ("device_wait", "d2h_copy", "unstage"):
        assert by_stage[name] == {(job_id, "ec-rebuild-drain")}, name
    stages = _last_job("ec_rebuild")["stages"]
    batches = stages["stage"]["items"]
    assert batches > ec_files.PIPELINE_DEPTH
    assert {stages[s]["items"] for s in ec_files.REBUILD_SUMS[
        "reconstruct"]} == {batches}
    # the call's own stages are the caller's, once or twice a call
    for name in ("open", "commit"):
        assert by_stage[name] == {(job_id, me)}, name
    assert 1 <= stats["inflight_max"] <= ec_files.PIPELINE_DEPTH


def test_encode_stages_are_entered_once_a_unit(tmp_path):
    """A device codec's encode unit is a span of the .dat's map
    (ec_files._iter_spans): `read` (the selection, and the copy of a last
    short row), `stall` (the wait for one of PIPELINE_DEPTH slots) and the
    seam's four stages are entered once a unit, not once a row, and
    `rows_staged` is on the job's stats in every call."""
    stats, _ = prepare("encode", tmp_path)()
    # 230,000 bytes: two large rows of 10 x 10,000, each cut in ten
    # columns of BATCH bytes, then thirty whole small rows, ten to a unit
    units = 2 * (LARGE // BATCH) + 30 // (BATCH // SMALL)
    stages = _last_job("ec_encode")["stages"]
    for name in ("read", "h2d", "dispatch", "device_wait", "d2h_copy"):
        assert stages[name]["items"] == units, (name, stages[name])
    assert stats["rows_staged"] == 0
    assert {"read_s", "stall_s"} <= set(stats)
    # one short row after them: one more unit, and the one staged row
    base = str(tmp_path / "1")
    with open(base + ".dat", "ab") as f:
        f.write(b"x" * 37)
    stats = {}
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=BATCH, stats=stats)
    assert stats["rows_staged"] == 1
    assert _last_job("ec_encode")["stages"]["read"]["items"] == units + 1


def test_reconstruct_books_device_seconds():
    """PERF.md's verdict table (PR 23): `reconstruct` had no device row."""
    codec = ec_files._get_codec("jax")
    before = profile.KERNELS.snapshot().get("reconstruct[device]", {})
    rng = np.random.default_rng(2)
    rows = rng.integers(0, 256, (10, 70_000), dtype=np.uint8)
    out = dispatch.reconstruct_batch(codec, rows, range(1, 11), [0])
    assert out[0].shape == (70_000,)
    after = profile.KERNELS.snapshot()["reconstruct[device]"]
    assert after["device_s"] > before.get("device_s", 0.0)
    # what crosses is the bucket's width, what was asked for the needle's
    width = codec_base.bucket(70_000, codec.tile)
    assert after["d2h_bytes"] - before.get("d2h_bytes", 0.0) == width
    assert after["h2d_bytes"] - before.get("h2d_bytes", 0.0) == 10 * width
    assert after["bytes"] - before.get("bytes", 0.0) == 700_000


# -- (c) compilations, counted inside the program ---------------------------

def _compiles(entry: str) -> int:
    return profile.compiles_snapshot().get(entry, {}).get("count", 0)


def test_compile_counter_by_entry_point_agrees_with_the_log(caplog):
    codec = ec_files._get_codec("jax")  # notes the codec: counting is on
    rng = np.random.default_rng(3)

    def degraded_read(length: int) -> None:
        rows = rng.integers(0, 256, (10, length), dtype=np.uint8)
        dispatch.reconstruct_batch(codec, rows, range(1, 11), [0])

    def total() -> int:
        return sum(r["count"] for r in profile.compiles_snapshot().values())

    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING, logger="jax"):
            t0 = total()
            r0, e0 = _compiles("reconstruct"), _compiles("encode_parity")
            degraded_read(131_337)  # a width bucket not seen before
            r1 = _compiles("reconstruct")
            assert r1 > r0 and _compiles("encode_parity") == e0
            degraded_read(131_339)  # another length of the bucket: nothing
            assert _compiles("reconstruct") == r1
            degraded_read(331_339)  # the next bucket
            assert _compiles("reconstruct") > r1
            dispatch.materialize(dispatch.dispatch_parity(
                codec, rng.integers(0, 256, (10, 7_777), dtype=np.uint8)))
            assert _compiles("encode_parity") == e0 + 1
            jax.jit(lambda x: x * 3 + 1)(np.arange(5))  # outside the seam
            logged = [r for r in caplog.records
                      if "Finished XLA compilation of" in r.getMessage()]
            assert total() - t0 == len(logged) > 3
    finally:
        jax.config.update("jax_log_compiles", False)
    snap = profile.compiles_snapshot()
    assert snap["other"]["count"] >= 1
    assert all(r["seconds"] > 0 for r in snap.values())


# -- (d) /perf --------------------------------------------------------------

def test_perf_keeps_every_old_key_and_carries_compiles(tmp_path):
    prepare("degraded_read", tmp_path)()
    resp = asyncio.run(pipeline.handle_perf(_mock_req("/perf", "10.0.0.9")))
    body = json.loads(resp.text)
    assert {"id", "enabled", "jobs", "roofline", "codecs",
            "compiles"} <= set(body)
    assert body["compiles"]["reconstruct"]["count"] >= 1
    assert set(body["compiles"]["reconstruct"]) == {"count", "seconds"}
    for row in body["roofline"]["rows"]:
        assert {"kernel", "backend", "resource", "calls", "gbytes",
                "busy_s", "achieved_gbps"} <= set(row)
    flow = next(j for j in body["jobs"] if j["kind"] == "ec_read")
    assert flow["state"] == "flow"
    assert {"busy_s", "blocked_s", "bytes", "items", "busy_frac"} <= \
        set(flow["stages"]["reconstruct"])
    assert any(b["codec"] == "JaxRSCodec" for b in body["codecs"])


# -- the cost of a stage with the profiler closed ---------------------------

def test_a_closed_profiler_stage_costs_microseconds():
    job = pipeline.PipelineJob("t", register=False)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with job.stage("s", unit=i):
            pass
    per_stage = (time.perf_counter() - t0) / n
    # a bulk call opens about a thousand: 1 ms of a 0.44 s call at 1 us
    assert per_stage < 50e-6, per_stage
    assert job.stats["s_s"] > 0 and job.snapshot()["stages"]["s"]["items"] == n


# -- opening a session on a server: --jax-profile, SIGTERM, the route --------

def test_sigterm_closes_the_jax_profile(tmp_path):
    """What `--jax-profile DIR` does (grace.setup_jax_profile), in a
    child on the CPU: SIGTERM must leave the .xplane.pb, with the stage
    annotations of the encode that ran inside the session."""
    code = (
        "import os, sys, time\n"
        "import numpy as np\n"
        "from seaweedfs_tpu.utils import grace\n"
        "from seaweedfs_tpu.storage.ec import ec_files\n"
        "base = os.path.join(sys.argv[1], '1')\n"
        "np.arange(200_000, dtype=np.uint8).tofile(base + '.dat')\n"
        "grace.setup_jax_profile(os.path.join(sys.argv[1], 'trace'))\n"
        "ec_files.write_ec_files(base, large_block=10000, small_block=100,\n"
        "                        batch_size=1000)\n"
        "print('ready', flush=True)\n"
        "time.sleep(120)\n")
    env = dict(os.environ, WEEDTPU_EC_CODEC="jax", JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", code, str(tmp_path)],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    try:
        assert p.stdout.readline().strip() == "ready", p.stderr.read()[-2000:]
        p.send_signal(signal.SIGTERM)
        assert p.wait(60) == 128 + signal.SIGTERM
    finally:
        if p.poll() is None:
            p.kill()
    found = _annotations(str(tmp_path / "trace"))
    assert "ec.encode.read" in found and SEAM <= set(found), sorted(found)


def test_debug_jax_profile_opens_one_window_at_a_time(tmp_path):
    handler = profile.handle_debug_jax_profile
    ec_files._get_codec("jax")  # a codec of this process runs on JAX
    op = prepare("encode", tmp_path / "data")

    async def window_with_an_encode_in_it():
        first = asyncio.ensure_future(handler(_mock_req(
            f"/debug/jax_profile?seconds=3&dir={tmp_path}/w",
            "127.0.0.1")))
        await asyncio.sleep(0.3)
        second = await handler(_mock_req(
            "/debug/jax_profile?seconds=0.1", "127.0.0.1"))
        await asyncio.to_thread(op)
        return await first, second

    first, second = asyncio.run(window_with_an_encode_in_it())
    assert second.status == 400 and "open already" in second.text
    assert first.status == 200, first.text
    body = json.loads(first.text)
    assert body["dir"] == f"{tmp_path}/w" and len(body["xplane"]) == 1
    assert "ec.encode.read" in _annotations(body["dir"])
    assert grace.stop_jax_profile() is None  # the window closed itself
    # mounted beside /debug/pprof, behind the same loopback guard
    routes = {r.path: r.handler for r in trace.debug_routes()}
    resp = asyncio.run(routes["/debug/jax_profile"](
        _mock_req("/debug/jax_profile?seconds=0.1", "10.0.0.9")))
    assert resp.status == 403


def test_debug_jax_profile_refuses_a_process_with_no_jax_backend(
        monkeypatch):
    monkeypatch.setattr(profile, "_codecs_noted", {})
    resp = asyncio.run(profile.handle_debug_jax_profile(
        _mock_req("/debug/jax_profile?seconds=0.1", "127.0.0.1")))
    assert resp.status == 400 and "initialise" in resp.text
