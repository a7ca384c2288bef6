"""Protobuf wire layer: binary control-plane framing for the hot RPCs.

Reference: weed/pb/*.proto + generated code.  The schema (weedtpu.proto)
is compiled with protoc on first use (same build-on-demand discipline as
native/, and the same rule for a generated file that came with a copied
tree: its first line carries a key over the schema and the installed
protobuf runtime, and a module with another key is regenerated, not
imported).  `available()` is False when the module is stale or absent
and protoc is missing — every endpoint keeps its JSON framing, so
protobuf is an upgrade, not a dependency.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_PROTO = os.path.join(_HERE, "weedtpu.proto")
_GEN = os.path.join(_HERE, "weedtpu_pb2.py")

_lock = threading.Lock()
_mod = None
_err: str | None = None

CONTENT_TYPE = "application/x-protobuf"


def _gen_key() -> str:
    import google.protobuf
    with open(_PROTO, "rb") as f:
        h = hashlib.sha256(f.read())
    h.update(google.protobuf.__version__.encode())
    return f"# weedtpu-key: {h.hexdigest()[:16]}\n"


def _generate() -> None:
    """(Re)generate weedtpu_pb2.py unless the one on disk carries this
    schema's and this runtime's key."""
    key = _gen_key()
    try:
        with open(_GEN) as f:
            if f.readline() == key:
                return
    except OSError:
        pass
    # protoc writes into a scratch directory and the keyed module is
    # renamed into place, so no other process imports a half-written file
    with tempfile.TemporaryDirectory(dir=_HERE) as tmp:
        subprocess.run(
            ["protoc", f"--python_out={tmp}",
             f"--proto_path={_HERE}", "weedtpu.proto"],
            check=True, capture_output=True)
        out = os.path.join(tmp, os.path.basename(_GEN))
        with open(out) as f:
            body = f.read()
        with open(out, "w") as f:
            f.write(key + body)
        os.replace(out, _GEN)


def _load():
    global _mod, _err
    with _lock:
        if _mod is not None or _err is not None:
            return _mod
        try:
            _generate()
            from seaweedfs_tpu.pb import weedtpu_pb2  # noqa: PLC0415
            _mod = weedtpu_pb2
        except (OSError, subprocess.CalledProcessError, ImportError) as e:
            _err = str(e)
            return None
        return _mod


def available() -> bool:
    return _load() is not None


def messages():
    """The generated module (weedtpu_pb2); raises if unavailable."""
    mod = _load()
    if mod is None:
        raise RuntimeError(f"protobuf wire layer unavailable: {_err}")
    return mod


# -- Heartbeat dict <-> message bridging (the JSON shapes stay the
# source of truth; protobuf is an alternate framing of the same data) --

def heartbeat_to_bytes(beat: dict) -> bytes:
    m = messages()
    hb = m.Heartbeat(
        id=beat.get("id", ""), url=beat.get("url", ""),
        public_url=beat.get("public_url", ""),
        data_center=beat.get("data_center", ""),
        rack=beat.get("rack", ""),
        max_volume_count=int(beat.get("max_volume_count", 0)),
        max_file_key=int(beat.get("max_file_key", 0)))
    for v in beat.get("volumes", []):
        hb.volumes.add(
            id=int(v.get("id", 0)), size=int(v.get("size", 0)),
            collection=v.get("collection", "") or "",
            file_count=int(v.get("file_count", 0)),
            delete_count=int(v.get("delete_count", 0)),
            deleted_byte_count=int(v.get("deleted_bytes", 0)),
            read_only=bool(v.get("read_only", False)),
            replica_placement=str(v.get("replica_placement", "000")),
            ttl=str(v.get("ttl", "") or ""),
            modified_at_second=int(v.get("modified_at", 0)),
            version=int(v.get("version", 0)))
    for e in beat.get("ec_shards", []):
        hb.ec_shards.add(id=int(e.get("id", 0)),
                         collection=e.get("collection", "") or "",
                         shards=[int(s) for s in e.get("shard_ids", [])],
                         shard_size=int(e.get("shard_size", 0)),
                         codec=e.get("codec", "") or "")
    return hb.SerializeToString()


def heartbeat_from_bytes(raw: bytes) -> dict:
    m = messages()
    hb = m.Heartbeat()
    hb.ParseFromString(raw)
    return {
        "id": hb.id, "url": hb.url, "public_url": hb.public_url,
        "data_center": hb.data_center, "rack": hb.rack,
        "max_volume_count": hb.max_volume_count,
        "max_file_key": hb.max_file_key,
        "volumes": [{
            # proto3 zero-default: a 0 version means "unset" — omit it so
            # the consumer's CURRENT_VERSION default applies, matching a
            # JSON beat that never carried the key
            **({"version": v.version} if v.version else {}),
            "id": v.id, "size": v.size, "collection": v.collection,
            "file_count": v.file_count, "delete_count": v.delete_count,
            "deleted_bytes": v.deleted_byte_count,
            "read_only": v.read_only,
            "replica_placement": v.replica_placement,
            "ttl": v.ttl, "modified_at": v.modified_at_second,
        } for v in hb.volumes],
        "ec_shards": [{
            # empty codec = a pre-codec-family node: consumers default rs
            **({"codec": e.codec} if e.codec else {}),
            # proto3 zero-default, as `version` above: a JSON beat that
            # never carried a shard size round-trips without the key
            **({"shard_size": e.shard_size} if e.shard_size else {}),
            "id": e.id, "collection": e.collection,
            "shard_ids": list(e.shards),
        } for e in hb.ec_shards],
    }
