"""Self-healing maintenance plane: scrubber syndrome checks, quarantine,
fault injection, and the master's automatic repair planner.

Unit layers test the syndrome math and planner throttling directly; the
cluster layers prove the heal loop end-to-end — faults injected through
/admin/faults, detection via scrub + heartbeat diff, repair via planner
ticks, with no manual shell command."""

import asyncio
import io
import json
import os
import time
import types as _types
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu.maintenance import faults, scrub
from seaweedfs_tpu.maintenance.repair import (RepairPlanner, TokenBucket,
                                              build_ledger)
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.topology.topology import Topology
from tests.test_cluster import Cluster

SMALL = 4096


def _flip(path: str, offset: int, mask: int = 0x10) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ mask]))


def _make_ec_volume(tmp_path, vid=7, n_needles=24, nsize=3000, seed=0):
    vol = Volume(str(tmp_path), "", vid)
    rng = np.random.default_rng(seed)
    payloads = {}
    for i in range(1, n_needles + 1):
        data = rng.integers(0, 256, nsize, dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x11, id=i, data=data))
        payloads[i] = data
    vol.close()
    base = os.path.join(str(tmp_path), str(vid))
    ec_files.write_ec_files(base, large_block=1 << 40, small_block=SMALL,
                            batch_size=SMALL * 10)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, payloads


def test_syndrome_catches_single_flipped_bit_in_any_shard(tmp_path,
                                                          monkeypatch):
    """A single flipped bit in ANY of the 14 shards trips the batched
    parity-syndrome check and is localized to the right shard; the
    dispatched syndrome is byte-identical to a python-backend recompute."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, _ = _make_ec_volume(tmp_path)
    ev = ec_volume.EcVolume(base)
    try:
        assert scrub.syndrome_scan(ev, window=SMALL * 2) == []

        # byte-identity: the dispatch-seam parity equals the slow python
        # reference recompute over the same stripes
        from seaweedfs_tpu.models import rs
        from seaweedfs_tpu.ops import dispatch
        n = ev.shard_size
        rows = {sid: np.frombuffer(ev._read_local(sid, 0, n), np.uint8)
                for sid in range(layout.TOTAL_SHARDS)}
        batch = np.stack([rows[i] for i in range(layout.DATA_SHARDS)])
        got = dispatch.materialize(
            dispatch.dispatch_parity(ec_files._get_codec(), batch))
        want = rs.get_code(10, 4).encode_numpy(batch)[10:]
        assert np.array_equal(got, want)

        for sid in range(layout.TOTAL_SHARDS):
            p = base + layout.to_ext(sid)
            off = 5000 % os.path.getsize(p)
            _flip(p, off)
            found = scrub.syndrome_scan(ev, window=SMALL * 2)
            assert len(found) == 1 and found[0]["shard"] == sid, (sid,
                                                                  found)
            _flip(p, off)  # restore
        assert scrub.syndrome_scan(ev, window=SMALL * 2) == []
    finally:
        ev.close()


def test_quarantined_range_served_via_reconstruction(tmp_path,
                                                     monkeypatch):
    """Corrupt bytes under a quarantined range are never served: reads
    reconstruct the range from the other shards and return the original
    payload byte-for-byte."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, payloads = _make_ec_volume(tmp_path)
    p = base + layout.to_ext(2)
    with open(p, "r+b") as f:
        f.seek(64)
        f.write(b"\xff" * 128)
    ev = ec_volume.EcVolume(base)
    try:
        found = scrub.syndrome_scan(ev, window=SMALL)
        assert found and found[0]["shard"] == 2
        for c in found:
            ev.quarantine_range(c["shard"], c["offset"], c["size"])
        assert ev.quarantine_snapshot().get("2")
        for nid, data in payloads.items():
            assert ev.read_needle(nid).data == data, nid
        assert ev.read_stats_snapshot()["reconstruct_batches"] > 0
    finally:
        ev.close()


def _degraded_topology(n_vols: int, missing: int = 2) -> Topology:
    topo = Topology()
    beat = {"max_volume_count": 50, "volumes": [],
            "ec_shards": [{"id": vid, "collection": "",
                           "shard_ids": list(range(layout.TOTAL_SHARDS
                                                   - missing))}
                          for vid in range(1, n_vols + 1)]}
    topo.register_heartbeat(node_id="127.0.0.1:1", url="127.0.0.1:1",
                            public_url="", dc="", rack="", beat=beat)
    return topo


def test_token_bucket_oversized_request_admits_at_full():
    """A request larger than the whole burst (one production-sized
    shard can exceed the cross-rack budget) must not starve forever: a
    FULL bucket admits it, driving tokens negative so the debt pays off
    at `rate` and the long-run byte rate stays bounded."""
    b = TokenBucket(rate=0.0, burst=1024.0)
    assert b.try_acquire(4096.0)      # full bucket admits the oversized
    assert b.tokens == -3072.0        # ... at the price of debt
    assert not b.try_acquire(1.0)     # which throttles what follows
    b.tokens = 1023.0                 # near-full is NOT full enough
    assert not b.try_acquire(4096.0)


def test_token_bucket_caps_concurrent_rebuilds():
    """The planner launches at most `burst` repairs per tick when the
    refill rate is zero — re-protection traffic is throttled."""
    bucket = TokenBucket(rate=0.0, burst=2.0)
    assert bucket.try_acquire() and bucket.try_acquire()
    assert not bucket.try_acquire()

    master = _types.SimpleNamespace(topo=_degraded_topology(6),
                                    _session=None)
    planner = RepairPlanner(master, rate=0.0, burst=2.0,
                            node_concurrency=100)
    calls: list[tuple] = []

    async def fake_post(url, path, body):
        calls.append((url, path, body))
        return {}

    planner._post = fake_post

    async def drive():
        actions = await planner.tick()
        await planner.wait_idle()
        return actions

    actions = asyncio.run(drive())
    assert len(actions) == 2, actions  # bucket-capped, 6 candidates
    assert {c[1] for c in calls} >= {"/admin/ec/rebuild", "/admin/ec/mount"}
    # a later tick with refilled tokens picks up the remaining volumes
    planner.bucket.burst = planner.bucket.tokens = 10.0
    assert len(asyncio.run(drive())) == 6


def test_ledger_urgency_orders_by_shards_lost():
    """3-lost volumes preempt 1-lost ones (shards-lost ordering)."""
    topo = Topology()
    beat = {"max_volume_count": 50, "volumes": [], "ec_shards": [
        {"id": 1, "collection": "", "shard_ids": list(range(13))},
        {"id": 2, "collection": "", "shard_ids": list(range(11))},
    ]}
    topo.register_heartbeat(node_id="n1", url="n1", public_url="",
                            dc="", rack="", beat=beat)
    led = build_ledger(topo, {})
    assert led[1]["state"] == led[2]["state"] == "degraded"
    assert led[2]["urgency"] > led[1]["urgency"]
    # below k survivors: critical, not repairable
    topo2 = _degraded_topology(1, missing=6)
    assert build_ledger(topo2, {})[1]["state"] == "critical"


def _rack_topology(nodes: list[tuple], vids: dict[int, dict[str, list[int]]],
                   shard_size: int = 4096) -> Topology:
    """nodes: (url, dc, rack); vids: vid -> {url: shard_ids}."""
    topo = Topology()
    for url, dc, rack in nodes:
        beat = {"max_volume_count": 50, "volumes": [],
                "ec_shards": [{"id": vid, "collection": "",
                               "shard_ids": per[url],
                               "shard_size": shard_size}
                              for vid, per in vids.items()
                              if per.get(url)]}
        topo.register_heartbeat(node_id=url, url=url, public_url="",
                                dc=dc, rack=rack, beat=beat)
    return topo


def test_plan_survivors_prefers_same_rack_minimal_groups():
    """Survivor selection: the rebuilder is the node with most shards,
    helpers come same-rack-first, and the group set is MINIMAL — a
    same-rack node that covers k alone keeps cross-rack estimates at
    zero even though a cross-rack node also holds survivors."""
    topo = _rack_topology(
        [("a", "dc1", "r0"), ("b", "dc1", "r0"), ("c", "dc1", "r1")],
        {1: {"a": list(range(0, 6)), "b": list(range(6, 10)),
             "c": [10, 11]}})
    led = build_ledger(topo, {})
    info = led[1]
    assert info["shards_missing"] == [12, 13]
    assert info["shard_size"] == 4096
    planner = RepairPlanner(
        _types.SimpleNamespace(topo=topo, _session=None))
    plan = planner._plan_survivors(info)
    assert plan["rebuilder"] == "a"
    assert [g["node"] for g in plan["groups"]] == ["b"]  # same rack only
    assert plan["groups"][0]["locality"] == 1
    assert plan["est_xrack_bytes"] == 0
    # 2 lost shards x 1 remote helper node x shard_size
    assert plan["est_remote_bytes"] == 2 * 4096
    # the naive baseline would copy every off-rebuilder survivor
    assert plan["naive_remote_bytes"] == 6 * 4096


def test_xrack_budget_defers_lower_urgency_repairs():
    """Cross-rack budget enforcement: with a burst that covers only the
    most urgent volume's estimate, the lower-urgency repair WAITS (shows
    in status.xrack.waiting) instead of launching, and launches once the
    bucket refills."""
    size = 4096
    topo = _rack_topology(
        [("a", "dc1", "r0"), ("c", "dc1", "r1")],
        {1: {"a": list(range(0, 6)), "c": list(range(6, 12))},    # -2
         2: {"a": list(range(0, 6)), "c": list(range(6, 13))}},   # -1
        shard_size=size)
    master = _types.SimpleNamespace(topo=topo, _session=None)
    # burst covers vid1's 2-lost cross-rack estimate plus half of vid2's
    planner = RepairPlanner(master, rate=0.0, burst=10.0,
                            node_concurrency=100,
                            xrack_rate=0.0, xrack_burst=2.5 * size)
    launched: list[int] = []

    async def fake_run_one(info, node):
        launched.append(info["vid"])
        planner._active_vids.discard(info["vid"])

    planner._run_one = fake_run_one
    planner.bucket.tokens = 10.0

    actions = asyncio.run(planner.tick())
    assert [a["vid"] for a in actions] == [1]  # most at-risk first
    assert planner.waiting_xrack == [2]
    assert planner.status()["xrack"]["waiting"] == [2]

    # refilled bucket: the deferred repair launches on the next tick
    # (vid1 relaunches too — the fake executor never healed it)
    planner.xrack_bucket.tokens = planner.xrack_bucket.burst = 10 * size
    actions = asyncio.run(planner.tick())
    assert 2 in {a["vid"] for a in actions}
    assert planner.waiting_xrack == []


def test_naive_fallback_debits_xrack_shortfall():
    """When the reduced rebuild fails and the planner degrades to
    survivor copies, the (much larger) naive cross-rack cost is forced
    into the budget as debt — a cluster-wide fallback storm must not
    spend naive-level bytes against a reduced-level debit."""
    size = 4096
    topo = _rack_topology(
        [("a", "dc1", "r0"), ("c", "dc1", "r1")],
        {1: {"a": list(range(0, 6)), "c": list(range(6, 12))}},
        shard_size=size)
    planner = RepairPlanner(
        _types.SimpleNamespace(topo=topo, _session=None),
        rate=0.0, burst=10.0, node_concurrency=100,
        xrack_rate=0.0, xrack_burst=100.0 * size)
    info = build_ledger(topo, {})[1]
    plan = planner._plan_survivors(info)
    assert plan["est_xrack_bytes"] < plan["naive_xrack_bytes"]

    async def fake_post(url, path, body):
        if path == "/admin/ec/rebuild" and "reduced" in body:
            raise RuntimeError("helpers exhausted")
        return {}

    planner._post = fake_post
    before = planner.xrack_bucket.tokens
    asyncio.run(planner._repair_ec(1, info))
    assert before - planner.xrack_bucket.tokens == \
        plan["naive_xrack_bytes"] - plan["est_xrack_bytes"]


def test_locality_class_ranking():
    from seaweedfs_tpu.topology.topology import locality_class
    assert locality_class("dc1", "r0", "dc1", "r0", same_node=True) == 0
    assert locality_class("dc1", "r0", "dc1", "r0") == 1
    assert locality_class("dc1", "r0", "dc1", "r1") == 2
    assert locality_class("dc1", "r0", "dc2", "r0") == 3
    # label-less deployments compare as one rack
    assert locality_class("", "", "", "") == 1
    assert locality_class("", "DefaultRack", "", "") == 1


def _post(url, path, body, timeout=120):
    req = urllib.request.Request(
        f"http://{url}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, path, timeout=30):
    with urllib.request.urlopen(f"http://{url}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def _encode_first_volume(cluster, payloads):
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    vid = int(next(iter(payloads)).split(",")[0])
    time.sleep(0.5)
    env = CommandEnv(cluster.master.url)
    out = io.StringIO()
    run_command(env, "lock", out)
    run_command(env, f"ec.encode -volumeId {vid}", out)
    run_command(env, "unlock", out)
    time.sleep(0.5)
    return vid


@pytest.fixture()
def heal_cluster(tmp_path, monkeypatch):
    """Single-node cluster (all 14 shards co-located so the syndrome scan
    can assemble full stripes locally), deterministic maintenance: the
    background loops are parked and tests drive /admin/scrub +
    /maintenance/tick explicitly."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    monkeypatch.setenv("WEEDTPU_SCRUB_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_REPAIR_INTERVAL", "3600")
    c = Cluster(tmp_path, n_volume_servers=1).start()
    c.wait_heartbeats()
    yield c
    c.stop()


def _upload_payloads(cluster, n=20, size=15000, seed=3):
    from seaweedfs_tpu.client import WeedClient
    client = WeedClient(cluster.master.url)
    rng = np.random.default_rng(seed)
    payloads = {}
    for i in range(n):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        payloads[client.upload(data, name=f"m{i}.bin")] = data
    return client, payloads


def test_shard_loss_detected_by_heartbeat_diff_and_auto_rebuilt(
        heal_cluster):
    """Fault-injected shard loss surfaces in the master ledger through
    the heartbeat diff and is rebuilt within ONE planner tick."""
    c = heal_cluster
    client, payloads = _upload_payloads(c)
    vid = _encode_first_volume(c, payloads)
    vs = c.volume_servers[0]

    _post(vs.url, "/admin/faults", {"faults": [
        {"action": "delete_shard", "volume": vid, "shard": 4},
        {"action": "delete_shard", "volume": vid, "shard": 12}]})
    deadline = time.time() + 10
    while time.time() < deadline:
        v = _get(c.master.url, "/maintenance/status")["volumes"][str(vid)]
        if v["shards_missing"] == [4, 12]:
            break
        time.sleep(0.1)
    assert v["state"] == "degraded" and v["shards_missing"] == [4, 12], v

    r = _post(c.master.url, "/maintenance/tick", {"wait": True})
    assert any(a["vid"] == vid for a in r["actions"]), r
    deadline = time.time() + 10
    while time.time() < deadline:
        v = _get(c.master.url, "/maintenance/status")["volumes"][str(vid)]
        if v["state"] == "healthy":
            break
        time.sleep(0.1)
    assert v["state"] == "healthy" and len(v["shards_present"]) == 14, v
    client._vid_cache.clear()
    for fid, data in payloads.items():
        assert client.download(fid) == data, fid


@pytest.mark.parametrize("codec_env", ["numpy", None])
def test_end_to_end_heal_delete_two_flip_one(tmp_path, monkeypatch,
                                             codec_env):
    """The acceptance scenario: faults delete 2 shards and flip a bit in
    a third; the cluster detects (scrub syndrome + heartbeat diff),
    quarantines, and rebuilds to fully-protected state with no manual
    shell command — under both the python codec and the default
    backend (same ops/dispatch selection as encode)."""
    if codec_env is not None:
        monkeypatch.setenv("WEEDTPU_EC_CODEC", codec_env)
    else:
        monkeypatch.delenv("WEEDTPU_EC_CODEC", raising=False)
    monkeypatch.setenv("WEEDTPU_SCRUB_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_REPAIR_INTERVAL", "3600")
    c = Cluster(tmp_path, n_volume_servers=1).start()
    c.wait_heartbeats()
    try:
        client, payloads = _upload_payloads(c)
        vid = _encode_first_volume(c, payloads)
        vs = c.volume_servers[0]

        # silent corruption first (shard 0 carries real needle bytes)...
        _post(vs.url, "/admin/faults", {"faults": [
            {"action": "flip_bit", "volume": vid, "shard": 0,
             "offset": 1234}]})
        sc = _post(vs.url, "/admin/scrub", {})
        cor = sc["volumes"][str(vid)]["corrupt"]
        assert cor and cor[0]["shard"] == 0, cor
        assert sc["volumes"][str(vid)]["quarantined"].get("0"), sc
        # quarantined range is served via reconstruction, never bad bytes
        client._vid_cache.clear()
        for fid, data in payloads.items():
            assert client.download(fid) == data, f"quarantined {fid}"

        # ...then hard loss of two more shards
        _post(vs.url, "/admin/faults", {"faults": [
            {"action": "delete_shard", "volume": vid, "shard": 3},
            {"action": "delete_shard", "volume": vid, "shard": 11}]})
        deadline = time.time() + 10
        while time.time() < deadline:
            v = _get(c.master.url,
                     "/maintenance/status")["volumes"][str(vid)]
            if v["shards_missing"] == [3, 11]:
                break
            time.sleep(0.1)
        assert v["state"] == "corrupt", v

        _post(c.master.url, "/maintenance/tick", {"wait": True})
        deadline = time.time() + 15
        while time.time() < deadline:
            v = _get(c.master.url,
                     "/maintenance/status")["volumes"][str(vid)]
            if v["state"] == "healthy" and len(v["shards_present"]) == 14:
                break
            time.sleep(0.1)
        assert v["state"] == "healthy" and len(v["shards_present"]) == 14, v

        # fully re-protected: fresh syndrome pass is clean, bytes intact
        sc = _post(vs.url, "/admin/scrub", {})
        assert sc["volumes"][str(vid)]["corrupt"] == [], sc
        client._vid_cache.clear()
        for fid, data in payloads.items():
            assert client.download(fid) == data, fid
    finally:
        c.stop()


def test_blob_read_crc_fallback_to_replica(tmp_path, monkeypatch):
    """A store-volume read that fails CRC verification is counted, and
    served from a replica instead of 500ing with bad bytes."""
    monkeypatch.setenv("WEEDTPU_SCRUB_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_REPAIR_INTERVAL", "3600")
    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.stats import metrics
    c = Cluster(tmp_path, n_volume_servers=2, replication="001").start()
    c.wait_heartbeats()
    try:
        client = WeedClient(c.master.url)
        data = os.urandom(5000)
        fid = client.upload(data, replication="001")
        vid = int(fid.split(",")[0])
        time.sleep(0.7)
        locs = client.lookup(vid)
        assert len(locs) == 2
        victim = next(v for v in c.volume_servers if v.url == locs[0])
        vol = victim.store.get_volume(vid)
        key = t.FileId.parse(fid).key
        off_units, _size = vol.nm.get(key)
        # flip a data byte inside the record (header 16 + DataSize 4)
        _flip(vol.dat_path, t.from_offset_units(off_units) + 20 + 100)
        before = metrics.NEEDLE_CRC_MISMATCH.labels().value
        with urllib.request.urlopen(f"http://{victim.url}/{fid}",
                                    timeout=30) as r:
            assert r.read() == data  # replica bytes, not the corrupt copy
        assert metrics.NEEDLE_CRC_MISMATCH.labels().value > before
    finally:
        c.stop()


def test_needle_map_integrity_drops_counted():
    from seaweedfs_tpu.stats import metrics
    from seaweedfs_tpu.storage.needle_map import NeedleMap
    nm = NeedleMap()
    nm.put(1, 0, 100)
    before = metrics.NEEDLE_MAP_DROPS.labels("integrity_repair").value
    nm.drop(1)
    nm.drop(1)  # absent: not counted twice
    after = metrics.NEEDLE_MAP_DROPS.labels("integrity_repair").value
    assert after == before + 1


def test_faults_env_parse():
    plan = faults.parse_env(
        "delete_shard:1:3;flip_bit:2:7:4096:5;delay_shard_read:50;bogus:1")
    assert plan == [
        {"action": "delete_shard", "volume": 1, "shard": 3},
        {"action": "flip_bit", "volume": 2, "shard": 7, "offset": 4096,
         "bit": 5},
        {"action": "delay_shard_read", "ms": 50.0},
    ]


@pytest.mark.slow
def test_scrubber_respects_rate_limit(tmp_path, monkeypatch):
    """A pass over ~2MB at 2MB/s must take about a second; the same pass
    unthrottled is far faster."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    from seaweedfs_tpu.storage.store import Store
    vol = Volume(str(tmp_path), "", 9)
    blob = os.urandom(32 * 1024)
    for i in range(1, 65):  # ~2MB of needle data
        vol.append_needle(ndl.Needle(cookie=1, id=i, data=blob))
    vol.close()
    store = Store([str(tmp_path)])
    try:
        fast = scrub.Scrubber(store, mbps=10_000, interval=1e9)
        t0 = time.perf_counter()
        s1 = fast.scrub_once()
        fast_s = time.perf_counter() - t0
        assert s1["bytes"] > 1_900_000

        slow = scrub.Scrubber(store, mbps=2.0, interval=1e9)
        t0 = time.perf_counter()
        slow.scrub_once()
        slow_s = time.perf_counter() - t0
        # 2MB at 2MB/s minus the 0.25s burst allowance
        assert slow_s >= 0.6, slow_s
        assert slow_s > fast_s * 2
    finally:
        store.close()
