"""The benchmark's own checks: `python -m pytest benchmark/selfcheck -q`.
Not part of tier-1; they run on the CPU and never start the program's
server (test_broken_run.py starts a stand-in of its own, on a thread)."""

import hashlib
import os
import sys
import types

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

NEEDLE_HEADER = 16  # [cookie 4][id 8][size 4], then the body


def hand_made_volume(directory, raw: bytes, placed: list[tuple]):
    """A sealed volume `c_7` made by hand: `raw` as its `.dat`, an `.idx`
    of `placed` (id, offset, body size), and what the write path would
    have acknowledged for each needle: the body is what lies behind the
    record's header.  -> (srv with .base(), base, loaded, {fid: body})"""
    import reference
    srv = types.SimpleNamespace(
        base=lambda collection, vid: str(directory / f"{collection}_{vid}"))
    base = srv.base("c", 7)
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    with open(base + ".idx", "wb") as f:
        for nid, offset, size in placed:
            f.write(reference.IDX_ENTRY.pack(nid, offset // 8, size))
    bodies = {f"7,{nid:x}{0xabcd0000 + nid:08x}":
              raw[offset + NEEDLE_HEADER:offset + NEEDLE_HEADER + size]
              for nid, offset, size in placed}
    needles = [[fid, hashlib.sha256(body).hexdigest(), len(body)]
               for fid, body in bodies.items()]
    return srv, base, {"collection": "c", "vid": 7, "needles": needles}, bodies
