"""Performance-observatory tests (stats/pipeline.py + the roofline
plane): stage-accounting math invariants (busy/blocked separation,
stats-dict merge, queue-depth bounds), bottleneck attribution with
ceiling fractions, fleet aggregation with tracker dedupe, and a cluster
integration — an e2e fleet conversion whose /cluster/perf bottleneck
verdict must match the max-busy-fraction stage."""

import io
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.stats import metrics, pipeline, profile
from tests.test_cluster import Cluster
from tests.test_maintenance import _get, _post


@pytest.fixture(autouse=True)
def _fresh_observatory(monkeypatch):
    """Every test starts with an empty job registry and the enabled()
    cache invalidated (its 0.5s TTL would otherwise leak one test's
    WEEDTPU_PERF_OBS into the next)."""
    monkeypatch.setattr(pipeline, "_enabled_cache", (0.0, True))
    pipeline.reset()
    yield
    pipeline.reset()
    pipeline._enabled_cache = (0.0, True)


# ---- stage accounting math ---------------------------------------------

def test_stage_accounting_busy_blocked_invariants():
    stats: dict = {}
    with pipeline.track("t", stats, total_bytes=100) as job:
        with job.stage("read", nbytes=50, items=2):
            time.sleep(0.01)
        with job.stage("read", nbytes=50, items=2):
            time.sleep(0.01)
        with job.blocked("read"):
            time.sleep(0.02)
    snap = job.snapshot()
    row = snap["stages"]["read"]
    # busy and blocked accumulate separately; blocked never counts busy
    assert 0.015 <= row["busy_s"] <= snap["wall_s"]
    assert row["blocked_s"] >= 0.015
    assert row["bytes"] == 100 and row["items"] == 4
    # busy_frac is busy/wall, bounded by 1 for a single-threaded stage
    assert 0 < row["busy_frac"] <= 1.0
    assert abs(row["busy_frac"] - row["busy_s"] / snap["wall_s"]) < 0.01
    assert snap["state"] == "done" and snap["bytes"] == 100


def test_stage_seconds_write_through_and_stall_maps_to_blocked():
    # a Stage books its seconds to the job AND to the `<stage>_s` key of
    # the wrapped stats dict (what /admin/ec/progress reads); seconds
    # folded into the dict alone still make a stage; stall_s is idle,
    # never a stage
    stats = {"write_parity_s": 1.0}
    job = pipeline.PipelineJob("t", stats)
    with job.stage("encode", nbytes=10):
        time.sleep(0.002)
    with job.blocked("stall"):
        time.sleep(0.002)
    job.add_bytes("encode", 90)
    job.add_bytes("write_parity", 5)
    job.finish()
    snap = job.snapshot()
    assert stats["encode_s"] >= 0.002
    assert snap["stages"]["encode"]["busy_s"] == \
        pytest.approx(stats["encode_s"], abs=1e-6)
    assert snap["stages"]["encode"]["bytes"] == 100
    assert snap["stages"]["write_parity"]["busy_s"] == 1.0
    assert snap["stages"]["write_parity"]["bytes"] == 5
    assert "stall" not in snap["stages"]
    assert snap["blocked_s"] == pytest.approx(stats["stall_s"], abs=1e-4)


def test_lump_is_the_sum_of_its_parts_at_every_moment():
    stats: dict = {}
    job = pipeline.PipelineJob(
        "t", stats, sums={"d2h": ("device_wait", "d2h_copy")})
    for part in ("device_wait", "d2h_copy", "device_wait"):
        with job.stage(part):
            time.sleep(0.001)
        assert stats["d2h_s"] == pytest.approx(
            stats.get("device_wait_s", 0.0) + stats.get("d2h_copy_s", 0.0),
            rel=1e-9)
    job.finish()
    stages = job.snapshot()["stages"]
    assert stages["d2h"]["busy_s"] == pytest.approx(
        stages["device_wait"]["busy_s"] + stages["d2h_copy"]["busy_s"],
        abs=2e-6)


def test_queue_depth_bounds_and_averages():
    job = pipeline.PipelineJob("t")
    for depth in (1, 3, 2):
        job.queue("q", depth, bound=4)
    job.finish()
    q = job.snapshot()["queues"]["q"]
    assert q["last"] == 2 and q["max"] == 3 and q["bound"] == 4
    assert q["avg"] == pytest.approx(2.0)
    assert q["max"] <= q["bound"]


def test_finish_exports_stage_counters_and_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("WEEDTPU_PERF_OBS_JOBS", "4")
    pipeline.reset()  # picks up the tightened ring bound
    before = metrics.PIPELINE_STAGE_SECONDS.labels("ring", "s").value
    for i in range(9):
        job = pipeline.track("ring")
        with job.stage("s", nbytes=1):
            # an empty stage can round to 0 busy seconds on a fast host
            # and export nothing (seen 1 run in 3)
            time.sleep(0.001)
        job.finish()
    after = metrics.PIPELINE_STAGE_SECONDS.labels("ring", "s").value
    assert after > before  # finish() exported busy seconds
    snaps = [s for s in pipeline.jobs_snapshot() if s["kind"] == "ring"]
    assert len(snaps) == 4  # WEEDTPU_PERF_OBS_JOBS bounds retention


def test_finish_normalizes_exported_seconds_by_workers():
    """An N-worker pool's summed busy seconds export divided by N, so
    the counter RATE tops out at 1/s for a saturated stage — the
    '1.0 = saturated' contract the dashboard panel and README state."""
    pipeline.reset()
    stats = {"write_s": 8.0, "write_workers": 4}
    before = metrics.PIPELINE_STAGE_SECONDS.labels("norm", "write").value
    job = pipeline.track("norm", stats)
    job.finish()
    after = metrics.PIPELINE_STAGE_SECONDS.labels("norm", "write").value
    assert after - before == pytest.approx(2.0)  # 8 busy-s / 4 workers


def test_writer_pool_worker_counts_accumulate_across_pools(tmp_path):
    """fleet_convert folds N per-volume writer pools into ONE shared
    stats dict: the published <stage>_workers must sum the concurrent
    pools' capacity, not keep the first-closed pool's count — summed
    busy seconds divided by one pool's workers reads >100% saturated."""
    from seaweedfs_tpu.storage.ec import ec_files
    stats: dict = {}
    fds, pools = [], []
    for p in range(2):
        fs = [os.open(str(tmp_path / f"f{p}_{i}"),
                      os.O_RDWR | os.O_CREAT, 0o644) for i in range(3)]
        fds += fs
        pool = ec_files._ShardWriterPool(
            fs, None, pipeline.PipelineJob("t", stats, register=False))
        for i in range(3):
            pool.put(i, np.ones(1024, dtype=np.uint8), 0)
        pools.append(pool)
    # a single-stage pool's whole thread set backs its one stage
    # (capacity splits across stages by busy share when pools are
    # multi-stage); across pools the counts sum
    expected = sum(pool._nworkers for pool in pools)
    for pool in pools:
        pool.close()
    for fd in fds:
        os.close(fd)
    assert stats["write_workers"] == pytest.approx(expected)
    assert stats["write_workers"] > pools[0]._nworkers  # summed


def test_perf_endpoint_is_cluster_internal_but_objects_stay_data():
    """/perf rides the /heat posture: the endpoint itself is internal
    (open to the master's /cluster/perf fan-out, out of data-plane SLO
    denominators), while an s3 bucket literally named "perf" keeps its
    OBJECT traffic on the data plane."""
    from seaweedfs_tpu.stats import netflow
    assert netflow.is_internal("/perf")
    assert netflow.classify("/perf") == "internal"
    assert netflow.classify("/perf/obj") == "data"


def test_flow_account_exports_incrementally_and_disabled_is_noop(
        monkeypatch):
    flow = pipeline.flow("t_flow")
    c = metrics.PIPELINE_STAGE_SECONDS.labels("t_flow", "fetch")
    b = metrics.PIPELINE_STAGE_BYTES.labels("t_flow", "fetch")
    v0, b0 = c.value, b.value
    with flow.stage("fetch", nbytes=128):
        time.sleep(0.002)
    assert c.value > v0 and b.value == b0 + 128
    # same flow instance is returned per kind
    assert pipeline.flow("t_flow") is flow
    # disabled: stage() is a nullcontext, nothing books
    monkeypatch.setenv("WEEDTPU_PERF_OBS", "0")
    monkeypatch.setattr(pipeline, "_enabled_cache", (0.0, False))
    v1 = c.value
    with flow.stage("fetch", nbytes=128):
        pass
    assert c.value == v1


def test_disabled_observatory_registers_nothing(monkeypatch):
    monkeypatch.setenv("WEEDTPU_PERF_OBS", "0")
    monkeypatch.setattr(pipeline, "_enabled_cache", (0.0, False))
    job = pipeline.track("off")
    with job.stage("s"):
        pass
    job.finish()
    assert not [s for s in pipeline.jobs_snapshot()
                if s["kind"] == "off"]


# ---- bottleneck attribution --------------------------------------------

def test_bottleneck_is_max_busy_stage():
    stats = {"read_s": 0.5, "encode_s": 2.0, "write_parity_s": 1.0,
             "wall_s": 2.2}
    job = pipeline.PipelineJob("t", stats, total_bytes=10**9)
    job.add_bytes("encode", 2 * 10**9)  # 1 GB/s achieved over 2s busy
    job.finish()
    bn = job.snapshot()["bottleneck"]
    assert bn["stage"] == "encode"
    assert bn["busy_frac"] == pytest.approx(2.0 / 2.2, abs=0.02)
    assert bn["achieved_gbps"] == pytest.approx(1.0, abs=0.01)
    # the verdict names a stage and its rate, and holds it to no ceiling
    assert set(bn) == {"stage", "busy_frac", "achieved_gbps"}


def test_multiworker_stage_occupancy_does_not_outrank_saturated_stage():
    """Stage seconds summed across N parallel workers (the shard writer
    pools publish `<stage>_workers`) are occupancy of N-worker capacity:
    a 4-worker pool 30% busy must not outrank a saturated single-thread
    encode stage just because its summed seconds exceed the wall."""
    stats = {"encode_s": 0.9, "write_parity_s": 1.2,
             "write_parity_workers": 4, "wall_s": 1.0}
    job = pipeline.PipelineJob("t", stats, total_bytes=10**9)
    job.add_bytes("write_parity", 4 * 10**9)
    job.finish()
    snap = job.snapshot()
    assert snap["stages"]["write_parity"]["busy_frac"] == \
        pytest.approx(0.3)
    assert snap["stages"]["write_parity"]["workers"] == 4
    assert snap["stages"]["encode"]["busy_frac"] == pytest.approx(0.9)
    bn = snap["bottleneck"]
    assert bn["stage"] == "encode", bn
    # and the aggregate rate of a multi-worker stage divides its summed
    # seconds by the worker count: 4 GB over 1.2s/4 of active time
    stats2 = {"write_parity_s": 1.2, "write_parity_workers": 4,
              "wall_s": 1.0}
    job2 = pipeline.PipelineJob("t2", stats2)
    job2.add_bytes("write_parity", 4 * 10**9)
    job2.finish()
    bn2 = job2.snapshot()["bottleneck"]
    assert bn2["achieved_gbps"] == pytest.approx(4 / 0.3, rel=0.01)


@pytest.mark.parametrize("form", ["array", "spans"])
def test_dispatch_parity_batch_books_h2d_exactly_once(unit_mesh, form):
    """The mesh place() seam books its own H2D; dispatch_parity_batch
    must not book it again when IT calls place() (the default
    fleet-convert path) — double-booking inflated the fleet_encode h2d
    roofline row 2x.  A batch of spans (a unit a device, each piece put
    1-D to its own device) books the occupied slots' bytes, once, and
    brings back no parity of an empty slot."""
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.parallel import mesh as pmesh
    enc = pmesh.FleetUnitEncoder(rs.get_code(10, 4), unit_mesh)
    units = np.random.default_rng(3).integers(
        0, 256, (8, 10, 256), dtype=np.uint8)
    before = profile.KERNELS.snapshot().get("fleet_encode[device]", {})
    if form == "array":
        parity = dispatch.dispatch_parity_batch(enc, units)
        up, back = units.nbytes, 8 * 4 * 256
    else:  # five units of one stripe row, three empty slots
        parity = dispatch.dispatch_parity_batch(
            enc, [[u.reshape(-1)] for u in units[:5]] + [None] * 3,
            stripes=1)
        up, back = units[:5].nbytes, 5 * 4 * 256
    blocks = list(dispatch.unit_parity_shards(parity))
    after = profile.KERNELS.snapshot()["fleet_encode[device]"]
    h2d = after["h2d_bytes"] - before.get("h2d_bytes", 0.0)
    d2h = after["d2h_bytes"] - before.get("d2h_bytes", 0.0)
    assert after["calls"] - before.get("calls", 0) == 1
    assert h2d == up  # once, not twice
    assert d2h == sum(np.asarray(b).nbytes for _, _, b in blocks) == back


def test_roofline_snapshot_rows_by_resource():
    profile.KERNELS.reset()
    profile.KERNELS.record("encode_parity", "device", wall_s=1.0,
                           device_s=1.0, nbytes=10**9,
                           d2h_s=0.5, d2h_bytes=10**9,
                           h2d_s=0.25, h2d_bytes=10**9)
    profile.KERNELS.record("shard_write", "host", wall_s=2.0,
                           nbytes=4 * 10**9)
    try:
        snap = profile.roofline_snapshot()
        assert set(snap) == {"rows"}
        rows = {(r["resource"], r["kernel"]): r for r in snap["rows"]}
        assert rows[("device", "encode_parity")]["achieved_gbps"] == \
            pytest.approx(1.0, abs=0.01)
        assert rows[("d2h", "encode_parity")]["achieved_gbps"] == \
            pytest.approx(2.0, abs=0.01)
        assert rows[("h2d", "encode_parity")]["achieved_gbps"] == \
            pytest.approx(4.0, abs=0.01)
        assert rows[("disk", "shard_write")]["achieved_gbps"] == \
            pytest.approx(2.0, abs=0.01)
        # busiest first, and no row is held to a ceiling nobody measured
        assert snap["rows"][0]["kernel"] == "shard_write"
        assert not [k for r in snap["rows"] for k in r if "ceiling" in k]
    finally:
        profile.KERNELS.reset()


def test_aggregate_fleet_dedupes_trackers_and_picks_worst_verdict():
    job = {"kind": "fleet_convert", "state": "done",
           "stages": {"encode": {"busy_s": 2.0, "bytes": 1e9,
                                 "busy_frac": 0.9}},
           "bottleneck": {"stage": "encode", "busy_frac": 0.9}}
    weak = {"kind": "fleet_convert", "state": "done",
            "stages": {"write_parity": {"busy_s": 1.0, "bytes": 5e8,
                                        "busy_frac": 0.4}},
            "bottleneck": {"stage": "write_parity", "busy_frac": 0.4}}
    shared = {"id": "AA", "jobs": [job]}
    out = pipeline.aggregate_fleet([
        ("vs1", shared), ("vs2", shared),  # co-hosted: same tracker id
        ("vs3", {"id": "BB", "jobs": [weak]})])
    # the co-hosted duplicate merged once, not twice
    assert out["occupancy"]["fleet_convert"]["encode"]["busy_s"] == 2.0
    assert out["occupancy"]["fleet_convert"]["encode"]["jobs"] == 1
    assert sorted(out["nodes"]) == ["vs1", "vs3"]
    # worst (max busy_frac) bottleneck wins the per-kind verdict
    assert out["bottlenecks"]["fleet_convert"]["stage"] == "encode"
    assert out["bottlenecks"]["fleet_convert"]["node"] == "vs1"


# ---- one tile a platform ------------------------------------------------

@pytest.fixture
def tile_noise(tmp_path, monkeypatch):
    """Everything that used to move the tile: the override, a pin where
    the variable says and one under $HOME, both naming another tile."""
    pin = {"tile": 65536, "gbps": 300.0, "fingerprint": "cpu:cpu:8"}
    for path in (tmp_path / "pin.json",
                 tmp_path / ".weedtpu_tile_pin.json"):
        path.write_text(json.dumps(pin))
    monkeypatch.setenv("WEEDTPU_EC_TILE", "65536")
    monkeypatch.setenv("WEEDTPU_TILE_PIN", str(tmp_path / "pin.json"))
    monkeypatch.setenv("HOME", str(tmp_path))


@pytest.mark.parametrize("backend,want", [("tpu", "TPU_TILE"),
                                          ("cpu", "DEFAULT_TILE")])
def test_resolved_tile_is_one_constant_a_platform(monkeypatch, tile_noise,
                                                  backend, want):
    import jax

    from seaweedfs_tpu.ops import pallas_gf
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_gf.resolved_tile() == getattr(pallas_gf, want)
    assert pallas_gf.resolved_tile(256) == 256  # an explicit tile is kept


def test_codec_resolve_touches_no_file_and_perf_reports_the_tile(
        monkeypatch, tile_noise):
    """`codecs.resolve` rides every degraded-read batch: no stat, no
    open, and the tile of the kernel's shell is the platform's constant.
    /perf says so under `codecs`, and has no `tile` block of its own."""
    import asyncio
    import builtins

    import jax

    from seaweedfs_tpu.ops import codecs, pallas_gf
    from tests.test_stage_tracing import _mock_req
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(profile, "_codecs_noted", {})
    codecs._build.cache_clear()
    try:
        first = codecs.resolve("rs_10_4", "tpu")  # imports, builds, notes
        touched: list = []

        def refuse(name):
            def hook(*a, **kw):
                touched.append((name, a[:1]))
                raise AssertionError(f"{name}{a[:1]} during resolve")
            return hook

        with monkeypatch.context() as m:
            for name in ("stat", "lstat", "open", "listdir", "access"):
                m.setattr(os, name, refuse(f"os.{name}"))
            m.setattr(builtins, "open", refuse("open"))
            again = codecs.resolve("rs_10_4", "tpu")
        assert not touched and again is first
        assert type(first).__name__ == "PallasRSCodec"
        assert first.tile == pallas_gf.TPU_TILE == 131072
        resp = asyncio.run(pipeline.handle_perf(
            _mock_req("/perf", "10.0.0.9")))
        body = json.loads(resp.text)
        assert "tile" not in body and "tiles" not in \
            pipeline.aggregate_fleet([("vs1", body)])
        noted = [c for c in body["codecs"] if c["asked"] == "tpu"]
        assert noted and noted[0]["codec"] == "PallasRSCodec"
        assert noted[0]["tile"] == 131072
    finally:
        codecs._build.cache_clear()  # no CPU test inherits a Pallas shell


def test_ec_read_flow_account_books_stage_occupancy(tmp_path, monkeypatch):
    """The continuous ec_read flow (the long-lived engine twin of a
    PipelineJob) books local-pread and reconstruct busy seconds + bytes,
    exported incrementally so the counter RATE is live occupancy."""
    from seaweedfs_tpu.storage.ec import ec_volume as ecv
    from seaweedfs_tpu.storage.ec import layout
    from tests.test_read_engine import LARGE, SMALL, _make_ec
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, blobs = _make_ec(tmp_path, n=20)
    os.remove(base + layout.to_ext(2))  # force reconstruction
    c_busy = metrics.PIPELINE_STAGE_SECONDS.labels("ec_read",
                                                   "local_pread")
    v0 = c_busy.value
    ev = ecv.EcVolume(base)
    try:
        for nid, data in blobs.items():
            assert ev.read_needle(nid).data == data
    finally:
        ev.close()
    flows = [s for s in pipeline.jobs_snapshot()
             if s["kind"] == "ec_read"]
    assert flows, pipeline.jobs_snapshot()
    st = flows[0]["stages"]
    assert st["local_pread"]["busy_s"] > 0
    assert st["local_pread"]["bytes"] > 0
    assert st["reconstruct"]["busy_s"] > 0
    assert c_busy.value > v0  # incremental export, not finish-time


# ---- cluster integration -----------------------------------------------

def _first_vs_vids(c):
    vs = c.volume_servers[0]
    return vs, sorted({vid for loc in vs.store.locations
                       for vid in loc.volumes})


def test_fleet_convert_bottleneck_matches_max_busy_stage_on_cluster_perf(
        tmp_path, monkeypatch):
    """e2e: a real fleet conversion through the master scheduler, then
    /cluster/perf's fleet_convert verdict must name exactly the stage
    with the max busy fraction in the job's own /debug/pipeline
    timeline — and the per-device drain must have booked its D2H (and
    place() its H2D) bytes into the fleet_encode kernel row."""
    import asyncio

    from seaweedfs_tpu.client import WeedClient
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "0")
    monkeypatch.setenv("WEEDTPU_REPAIR_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_AGG_INTERVAL", "0")
    kern0 = profile.KERNELS.snapshot().get("fleet_encode[device]", {})
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        client = WeedClient(c.master.url)
        rng = np.random.default_rng(13)
        blobs = {}
        for i in range(10):
            data = rng.integers(0, 256, 30_000, dtype=np.uint8).tobytes()
            blobs[client.upload(data, name=f"p{i}.bin")] = data
        vs, vids = _first_vs_vids(c)
        assert vids
        for v in vids:
            vs.store.get_volume(v).nm.flush()

        async def convert():
            c.master.convert.enqueue(vids)
            return await c.master.convert.tick()
        actions = c.submit(asyncio.wait_for(convert(), 60))
        assert all(a["outcome"] == "ok" for a in actions), actions

        # the job's own timeline on the volume server's debug surface
        dbg = _get(vs.url, "/debug/pipeline")
        jobs = [j for j in dbg["jobs"] if j["kind"] == "fleet_convert"]
        assert jobs, dbg
        job = jobs[0]
        assert job["state"] == "done"
        expect = max(job["stages"],
                     key=lambda s: (job["stages"][s]["busy_frac"],
                                    job["stages"][s]["busy_s"]))
        assert job["bottleneck"]["stage"] == expect
        assert job["queues"]  # queue depths sampled at the dispatch site

        # the master's fleet verdict agrees
        perf = _get(c.master.url, "/cluster/perf")
        bn = perf["bottlenecks"]["fleet_convert"]
        assert bn["stage"] == expect, (bn, job["stages"])
        occ = perf["occupancy"]["fleet_convert"]
        assert occ[expect]["busy_s"] > 0
        assert occ[expect]["bytes"] > 0

        # satellite: the per-device drain booked D2H (and place() H2D)
        # bytes into the fleet_encode kernel profile
        kern = profile.KERNELS.snapshot().get("fleet_encode[device]")
        assert kern is not None
        assert kern["d2h_bytes"] > kern0.get("d2h_bytes", 0.0)
        assert kern["h2d_bytes"] > kern0.get("h2d_bytes", 0.0)

        # readback stays byte-identical through the converted sets
        for fid, data in blobs.items():
            assert client.download(fid) == data

        # the shell command renders the verdict
        from seaweedfs_tpu.shell.commands import CommandEnv, run_command
        out = io.StringIO()
        run_command(CommandEnv(c.master.url), "cluster.perf", out)
        text = out.getvalue()
        assert "fleet_convert" in text and "bottleneck" in text, text
    finally:
        c.stop()
