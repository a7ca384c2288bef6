"""Protobuf wire layer: schema round-trips and the dual-framing heartbeat
(reference: weed/pb/master.proto; JSON stays the fallback framing)."""

import numpy as np
import pytest

from seaweedfs_tpu import pb


pytestmark = pytest.mark.skipif(not pb.available(),
                                reason="protoc/protobuf unavailable")


def test_heartbeat_roundtrip_preserves_fields():
    beat = {
        "id": "127.0.0.1:8080", "url": "127.0.0.1:8080",
        "public_url": "example:8080", "data_center": "dc1", "rack": "r2",
        "max_volume_count": 48, "max_file_key": 12345,
        "volumes": [
            {"id": 3, "size": 1 << 30, "collection": "hot",
             "file_count": 42, "delete_count": 2, "deleted_bytes": 999,
             "read_only": True, "replica_placement": "010", "ttl": "3d",
             "modified_at": 1700000000, "version": 3},
        ],
        "ec_shards": [
            {"id": 7, "collection": "", "shard_ids": [0, 3, 13]},
            # the shard report is where a volume's codec tag travels
            {"id": 9, "collection": "warm", "shard_ids": [3, 12, 15],
             "shard_size": 80 << 20, "codec": "lrc_12_2_2"},
        ],
    }
    back = pb.heartbeat_from_bytes(pb.heartbeat_to_bytes(beat))
    assert back == beat


def test_heartbeat_binary_is_compact():
    rng = np.random.default_rng(0)
    beat = {"id": "x", "url": "x", "public_url": "", "data_center": "",
            "rack": "",
            "max_volume_count": 100, "max_file_key": 1,
            "volumes": [
                {"id": int(i), "size": int(rng.integers(1 << 30)),
                 "collection": "c", "file_count": 10, "delete_count": 0,
                 "deleted_bytes": 0, "read_only": False,
                 "replica_placement": "000", "ttl": "",
                 "modified_at": 1700000000}
                for i in range(200)],
            "ec_shards": []}
    import json
    raw = pb.heartbeat_to_bytes(beat)
    assert len(raw) < len(json.dumps(beat).encode()) / 2


def test_cluster_heartbeats_ride_protobuf(tmp_path):
    """Default wire is protobuf when built: a registered node's topology
    data must round-trip the binary framing end-to-end."""
    from tests.test_cluster import Cluster
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        assert c.volume_servers[0]._wire_pb is True
        topo = c.master.topo.to_dict()
        assert topo["nodes"], "no node registered over pb heartbeats"
    finally:
        c.stop()


def test_json_fallback_when_forced(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_WIRE", "json")
    from tests.test_cluster import Cluster
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        assert c.volume_servers[0]._wire_pb is False
        assert c.master.topo.to_dict()["nodes"]
    finally:
        c.stop()


def test_generated_module_from_another_tree_is_regenerated():
    """The chip tool copies the tree as it stands, generated module
    included: one whose first line does not carry this schema's and this
    protobuf runtime's key is regenerated, never imported."""
    key = pb._gen_key()
    with open(pb._GEN) as f:
        assert f.readline() == key
        body = f.read()
    try:
        with open(pb._GEN, "w") as f:
            f.write("# weedtpu-key: 0000000000000000\nraise ImportError\n")
        pb._generate()
        with open(pb._GEN) as f:
            assert f.readline() == key
            assert f.read() == body
    finally:
        with open(pb._GEN, "w") as f:
            f.write(key + body)
