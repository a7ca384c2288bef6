"""Fleet conversion: the interleaved multi-volume device-resident encode
stream (ops/fleet_convert), its clean-abort contract, and the master-side
paced scheduler (maintenance/convert)."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.maintenance import faults
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import fleet_convert
from seaweedfs_tpu.stats import netflow
from seaweedfs_tpu.storage.ec import ec_files, layout


def _make_volumes(tmp_path, sizes, seed=7):
    rng = np.random.default_rng(seed)
    bases, payloads = [], []
    for i, sz in enumerate(sizes):
        base = str(tmp_path / f"{i + 1}")
        data = rng.integers(0, 256, sz, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            f.write(data)
        bases.append(base)
        payloads.append(data)
    return bases, payloads


def _shard_bytes(base):
    out = {}
    for i in range(layout.TOTAL_SHARDS):
        p = base + layout.to_ext(i)
        if os.path.exists(p):
            with open(p, "rb") as f:
                out[i] = f.read()
    return out


def test_convert_volumes_byte_identity(tmp_path, unit_mesh):
    """Interleaved fleet conversion over the unit-sharded CPU mesh is
    byte-identical to an independent numpy-codec write_ec_files run for
    every volume — ragged tails included — and commits .vif sidecars."""
    sizes = [200_000, 137_777, 95_001]
    bases, payloads = _make_volumes(tmp_path, sizes)
    from seaweedfs_tpu.parallel import mesh as pmesh
    codec = pmesh.FleetUnitEncoder(rs.get_code(10, 4), unit_mesh)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        codec=codec, stats=stats)
    assert rep["bytes"] == sum(sizes)
    assert stats["mode"] == "fleet" and stats["unit_batch"] % 8 == 0
    for base, data in zip(bases, payloads):
        ref = str(tmp_path / ("ref_" + os.path.basename(base)))
        with open(ref + ".dat", "wb") as f:
            f.write(data)
        os.environ["WEEDTPU_EC_CODEC"] = "numpy"
        try:
            ec_files.write_ec_files(ref, large_block=10_000,
                                    small_block=100)
        finally:
            del os.environ["WEEDTPU_EC_CODEC"]
        got, want = _shard_bytes(base), _shard_bytes(ref)
        assert sorted(got) == list(range(layout.TOTAL_SHARDS))
        for i in range(layout.TOTAL_SHARDS):
            assert got[i] == want[i], (base, i)
        assert ec_files.read_vif(base)["dat_file_size"] == len(data)


def _reference_shards(tmp_path, base, data):
    """The volume's shard files under the plain numpy RS code
    (models/rs), written by the single-volume engine."""
    ref = str(tmp_path / ("ref_" + os.path.basename(base)))
    with open(ref + ".dat", "wb") as f:
        f.write(data)
    os.environ["WEEDTPU_EC_CODEC"] = "numpy"
    try:
        ec_files.write_ec_files(ref, large_block=10_000, small_block=100)
    finally:
        del os.environ["WEEDTPU_EC_CODEC"]
    return _shard_bytes(ref)


# sizes of the volumes of one run over the 8-device mesh.  Rows are 10
# blocks of 100 bytes, a unit up to ten of them (batch_size 1000), and a
# volume over 100,000 bytes starts with a large-block row cut in columns
# of ten 1000-byte pieces: three unit shapes and the short last units.
SPAN_CASES = {
    "unequal_sizes_mixed_shapes": [200_000, 137_777, 95_001, 4_000, 23_456],
    "ends_on_a_row_boundary": [50_000],
    "ends_inside_a_row": [50_001],
    "shorter_than_one_stripe_row": [37],
    "an_empty_volume_among_others": [0, 12_345],
    "more_volumes_than_devices": [10_000 + 1000 * i for i in range(11)],
    "fewer_volumes_than_devices": [30_000, 30_500, 29_999],
}


@pytest.mark.parametrize("sizes", list(SPAN_CASES.values()),
                         ids=list(SPAN_CASES))
def test_convert_spans_byte_identity(tmp_path, unit_mesh, monkeypatch,
                                     sizes):
    """A codec that lays a unit out on the device (FleetUnitEncoder) gets
    every unit as spans of the `.dat` map, put 1-D a device: shard files
    byte for byte the plain RS code's, `rows_staged` one for each volume
    that ends inside a stripe row and nothing else, no [U, k, W] staging
    buffer, batches of one shape with empty slots where they close short,
    and a zero unit's parity never copied back."""
    from seaweedfs_tpu.parallel import mesh as pmesh
    bases, payloads = _make_volumes(tmp_path, sizes)
    codec = pmesh.FleetUnitEncoder(rs.get_code(10, 4), unit_mesh)
    batches, staging = [], []
    orig_dispatch, orig_empty = fleet_convert.dispatch_parity_batch, np.empty

    def dispatch(codec, units, **kw):
        batches.append((units, kw["stripes"]))
        return orig_dispatch(codec, units, **kw)

    def empty(shape, *a, **kw):
        if np.ndim(shape) and len(shape) == 3:
            staging.append(shape)
        return orig_empty(shape, *a, **kw)

    monkeypatch.setattr(fleet_convert, "dispatch_parity_batch", dispatch)
    monkeypatch.setattr(np, "empty", empty)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        codec=codec, stats=stats)
    monkeypatch.undo()
    assert rep["bytes"] == sum(sizes)
    for base, data in zip(bases, payloads):
        got, want = _shard_bytes(base), _reference_shards(tmp_path, base,
                                                           data)
        assert sorted(got) == list(range(layout.TOTAL_SHARDS))
        for i in range(layout.TOTAL_SHARDS):
            assert got[i] == want[i], (base, i)
        assert ec_files.read_vif(base)["dat_file_size"] == len(data)
    assert stats["rows_staged"] == sum(1 for n in sizes if n % 1000)
    assert not staging
    assert stats["unit_batch"] == 8
    occupied = 0
    for units, stripes in batches:
        assert isinstance(units, list) and len(units) == 8
        shapes = {tuple(map(len, u)) for u in units if u is not None}
        assert len(shapes) == 1  # one program a batch
        assert all(p.ndim == 1 for u in units if u is not None for p in u)
        # whole 100-byte rows, or one large-block row's 1000-byte columns
        assert sum(shapes.pop()) in (stripes * 1000, stripes * 10_000)
        occupied += sum(u is not None for u in units)
    assert occupied == rep["units"] == stats["units"]
    if any(sizes):
        assert rep["devices"] == min(8, max(
            sum(u is not None for u in units) for units, _ in batches))


def test_span_batch_parity_runs_and_empty_slots(unit_mesh):
    """A batch of spans through the seam: each occupied slot's parity is
    m contiguous 1-D runs on the slot's own device, an empty slot gives
    None and is never yielded."""
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.parallel import mesh as pmesh
    code = rs.get_code(10, 4)
    enc = pmesh.FleetUnitEncoder(code, unit_mesh)
    rng = np.random.default_rng(3)
    flat = [rng.integers(0, 256, 3 * 10 * 64, dtype=np.uint8)
            for _ in range(5)]
    parity = dispatch.dispatch_parity_batch(
        enc, [[f] for f in flat] + [None] * 3, stripes=3)
    assert [runs is None for runs in parity] == [False] * 5 + [True] * 3
    assert dispatch.parity_devices(parity) == 5
    blocks = list(dispatch.unit_parity_shards(parity))
    assert [(a, b) for a, b, _ in blocks] == [(s, s + 1) for s in range(5)]
    for (_, _, (runs,)), f in zip(blocks, flat):
        want = code.encode_numpy(
            f.reshape(3, 10, 64).transpose(1, 0, 2).reshape(10, -1))[10:]
        assert len(runs) == 4 and all(r.ndim == 1 for r in runs)
        assert np.array_equal(np.stack(runs), want)


def test_convert_books_class_convert(tmp_path):
    """The whole conversion runs under netflow class=convert, so any
    network hop made on its behalf books repair-adjacent bytes."""
    bases, _ = _make_volumes(tmp_path, [50_000])
    seen = []
    fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        progress=lambda n: seen.append(netflow.current_class()))
    assert seen and set(seen) == {"convert"}


@pytest.mark.parametrize("kind", ["fleet", "numpy"])
def test_convert_cancel_clean_abort(tmp_path, kind):
    """Cancel mid-stream: EncodeCancelled, NO partial .ecXX visible, no
    .tmp litter, and a previous valid shard set survives untouched —
    whether units go up as spans of the maps (the mesh encoder) or are
    staged into [U, k, W] batches (a host codec)."""
    bases, _ = _make_volumes(tmp_path, [300_000, 280_000], seed=9)
    # volume 0 already has a valid shard set from an earlier encode
    os.environ["WEEDTPU_EC_CODEC"] = "numpy"
    try:
        ec_files.write_ec_files(bases[0], large_block=10_000,
                                small_block=100)
    finally:
        del os.environ["WEEDTPU_EC_CODEC"]
    before = _shard_bytes(bases[0])
    calls = []

    def cancel():
        calls.append(1)
        return len(calls) > 2  # abort a couple of units in

    with pytest.raises(ec_files.EncodeCancelled):
        fleet_convert.convert_volumes(
            bases, large_block=10_000, small_block=100, batch_size=1000,
            cancel=cancel, codec=fleet_convert.fleet_codec(kind))
    # the old set is byte-identical, the fresh volume has nothing visible
    assert _shard_bytes(bases[0]) == before
    assert _shard_bytes(bases[1]) == {}
    for base in bases:
        assert not [p for p in os.listdir(tmp_path)
                    if p.endswith(".tmp")], os.listdir(tmp_path)


def test_convert_shard_write_fault_aborts(tmp_path):
    """An armed shard_write_error fault (the chaos disk-death shape)
    fails the conversion before any tmp shard exists."""
    bases, _ = _make_volumes(tmp_path, [40_000])
    faults.set_shard_write_error("EIO")
    try:
        with pytest.raises(OSError):
            fleet_convert.convert_volumes(
                bases, large_block=10_000, small_block=100,
                batch_size=1000)
    finally:
        faults.clear_net()
    assert _shard_bytes(bases[0]) == {}
    assert not [p for p in os.listdir(tmp_path) if ".ec" in p]


# -- master-side scheduler ------------------------------------------------

class _StubNode:
    def __init__(self, vids):
        self.volumes = {v: object() for v in vids}


class _StubTopo:
    def __init__(self, placement):
        import threading
        self._lock = threading.Lock()
        self.nodes = {url: _StubNode(vids)
                      for url, vids in placement.items()}


class _StubResp:
    def __init__(self, status=200, payload=None):
        self.status = status
        self._payload = payload or {}

    async def json(self):
        return self._payload

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return False


class _StubSession:
    """Records fleet_convert POSTs; `fail` raises like a dead node."""

    def __init__(self, fail=False):
        self.calls = []
        self.fail = fail

    def post(self, url, json=None, timeout=None):
        self.calls.append((url, json))
        if self.fail:
            raise OSError("connection refused")
        return _StubResp(payload={"converted": json["volumes"],
                                  "bytes": 1, "wall_s": 0.1})


class _StubAlerts:
    def __init__(self, firing=()):
        self._firing = firing

    def status(self):
        return {"rules": [{"name": n, "state": "firing"}
                          for n in self._firing]}


class _StubMaintenance:
    def __init__(self, active_nodes=None):
        self._active_nodes = dict(active_nodes or {})


class _StubMaster:
    def __init__(self, placement, firing=(), active_nodes=None,
                 fail=False):
        self.topo = _StubTopo(placement)
        self.alerts = _StubAlerts(firing)
        self.maintenance = _StubMaintenance(active_nodes)
        self._session = _StubSession(fail=fail)


def _tick(sched):
    return asyncio.run(sched.tick())


def test_scheduler_groups_paces_and_converts():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [1, 2, 3], "n2:80": [7]})
    sched = ConvertScheduler(master, rate=100.0, burst=100.0,
                             node_batch=2)
    assert sched.enqueue([1, 2, 3, 7, 7, "x"]) == [1, 2, 3, 7]
    actions = _tick(sched)
    # node_batch caps n1 at 2 volumes per call; 3 stays queued
    by_node = {a["node"]: a for a in actions}
    assert sorted(by_node) == ["n1:80", "n2:80"]
    assert by_node["n1:80"]["volumes"] == [1, 2]
    assert by_node["n1:80"]["outcome"] == "ok"
    assert sched.queued == [3] and sched.converted == 3
    assert _tick(sched)[0]["volumes"] == [3]
    assert not sched.queued and not sched.active


def test_scheduler_requeues_on_node_failure():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [5, 6]}, fail=True)
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([5, 6])
    actions = _tick(sched)
    assert actions and actions[0]["outcome"].startswith("error")
    # RE-QUEUED with backoff, never dropped
    assert sorted(sched.queued) == [5, 6]
    st = sched.status()
    assert st["backoffs"]["5"]["failures"] == 1
    # while backing off, nothing launches
    assert _tick(sched) == []
    # node recovers, backoff expires -> converted on the next tick
    master._session.fail = False
    sched._backoff = {v: (f, 0.0) for v, (f, _) in sched._backoff.items()}
    actions = _tick(sched)
    assert actions[0]["outcome"] == "ok"
    assert sched.converted == 2 and not sched.queued


def test_scheduler_pauses_on_interference_alert():
    # exact-name matching (ISSUE 14): the default pause list names the
    # actual default rules; a rule merely CONTAINING "interference"
    # must not pause (tests/test_interference.py covers that edge)
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [4]},
                         firing=("interference_high",))
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([4])
    assert _tick(sched) == []
    assert sched.status()["paused"] == "interference_high"
    assert sched.queued == [4]  # still queued, resumes when it clears
    master.alerts._firing = ()
    assert _tick(sched)[0]["outcome"] == "ok"
    assert sched.status()["paused"] is None


def test_scheduler_yields_to_active_repair_and_drops_unplaceable():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [8]}, active_nodes={"n1:80": 1})
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([8, 99])  # 99 lives nowhere (already EC / deleted)
    assert _tick(sched) == []
    assert sched.queued == [8]  # deferred behind the repair, not lost
    assert any(h.get("outcome") == "unplaceable" and h["vid"] == 99
               for h in sched.history)
    master.maintenance._active_nodes = {}
    assert _tick(sched)[0]["outcome"] == "ok"


def test_cluster_fleet_convert_end_to_end(tmp_path):
    """Full plane: blobs land in real volumes, the master scheduler
    paces a /admin/ec/fleet_convert batch to the owning node, shard sets
    commit (all 14 + .ecx + .vif, never a partial subset), convert bytes
    book on the netflow ledger, and readback stays byte-identical."""
    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import WeedClient
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        client = WeedClient(c.master.url)
        rng = np.random.default_rng(0xFEE7)
        blobs = {}
        for i in range(12):
            data = rng.integers(0, 256, int(rng.integers(5_000, 40_000)),
                                dtype=np.uint8).tobytes()
            blobs[client.upload(data, name=f"f{i}.bin")] = data
        vs = c.volume_servers[0]
        vids = sorted({vid for loc in vs.store.locations
                       for vid in loc.volumes})
        assert vids
        for v in vids:
            vs.store.get_volume(v).nm.flush()
        recv0 = netflow.class_total("recv", "convert")
        res = c.submit(asyncio.wait_for(_enqueue_and_tick(
            c.master, vids), 60))
        assert res["accepted"] == vids
        assert all(a["outcome"] == "ok" for a in res["actions"]), res
        st = c.master.convert.status()
        assert st["converted"] == len(vids) and not st["queued"]
        for v in vids:
            base = vs.store.get_volume(v)._base
            got = _shard_bytes(base)
            assert sorted(got) == list(range(layout.TOTAL_SHARDS)), v
            assert os.path.exists(base + ".ecx")
            assert ec_files.read_vif(base) is not None
        # the orchestration hop booked as class=convert on the ledger
        assert netflow.class_total("recv", "convert") > recv0
        for fid, data in blobs.items():
            assert client.download(fid) == data
    finally:
        c.stop()


async def _enqueue_and_tick(master, vids):
    accepted = master.convert.enqueue(vids)
    actions = await master.convert.tick()
    return {"accepted": accepted, "actions": actions}


def test_fleet_convert_partial_failure_settles_freeze(tmp_path,
                                                      monkeypatch):
    """A run that dies after SOME volumes committed keeps those frozen
    read-only with their .ecx (the EC set is their copy of record) and
    thaws only the rolled-back ones — a thawed-but-committed volume
    would take writes the shard set silently lacks."""
    import urllib.request
    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.ops import fleet_convert as fc
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        client = WeedClient(c.master.url)
        rng = np.random.default_rng(11)
        for i in range(10):
            client.upload(rng.integers(0, 256, 20_000,
                                       dtype=np.uint8).tobytes(),
                          name=f"x{i}.bin")
        vs = c.volume_servers[0]
        # a second volume via an assign in another collection, so the
        # batch spans a committed volume AND a rolled-back one
        with urllib.request.urlopen(
                f"http://{c.master.url}/dir/assign?collection=cx",
                timeout=10) as r:
            a = json.load(r)
        urllib.request.urlopen(urllib.request.Request(
            f"http://{a['url']}/{a['fid']}",
            data=rng.integers(0, 256, 30_000, dtype=np.uint8).tobytes(),
            method="PUT"), timeout=10).read()
        vids = sorted({vid for loc in vs.store.locations
                       for vid in loc.volumes})
        assert len(vids) >= 2
        for v in vids:
            vs.store.get_volume(v).nm.flush()

        real = fc.convert_volumes

        def first_commits_then_dies(bases, **kw):
            real(bases[:1], **kw)
            raise RuntimeError("disk died after the first commit")

        monkeypatch.setattr(fc, "convert_volumes",
                            first_commits_then_dies)
        req = urllib.request.Request(
            f"http://{vs.url}/admin/ec/fleet_convert",
            data=json.dumps({"volumes": vids}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        assert ei.value.code == 500
        committed, rest = vids[0], vids[1:]
        v0 = vs.store.get_volume(committed)
        assert v0.read_only  # stays frozen: shards are the copy of record
        assert sorted(_shard_bytes(v0._base)) == \
            list(range(layout.TOTAL_SHARDS))
        assert os.path.exists(v0._base + ".ecx")
        for vid in rest:
            v = vs.store.get_volume(vid)
            assert not v.read_only  # rolled back -> thawed, writable
            assert _shard_bytes(v._base) == {}
    finally:
        c.stop()


def test_scheduler_token_bucket_paces():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [1, 2, 3, 4]})
    sched = ConvertScheduler(master, rate=0.0001, burst=2.0, node_batch=4)
    sched.enqueue([1, 2, 3, 4])
    actions = _tick(sched)
    # burst grants exactly 2; the rest wait for tokens, still queued
    assert actions[0]["volumes"] == [1, 2]
    assert sorted(sched.queued) == [3, 4]
