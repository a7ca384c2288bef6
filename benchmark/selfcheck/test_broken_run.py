"""A run with the timed path broken underneath comes back not `correct`.

The cells' own traffic files (`rebuild_1lost.json`, `read_2lost.json`) are
played by their drivers against a stand-in volume server that holds a
hand-made RS(10,4) volume at 4 KiB blocks and answers as the program
does.  Sound, every run is `correct`; with an answer altered where it is
produced (a rebuilt shard file with one byte flipped, a rebuilt file
never written, a call that answers for another shard, a needle's body
with one byte flipped) `run.judge` says false and names the count."""

import contextlib
import http.server
import json
import os
import threading
import types

import numpy as np
import pytest

import harness
import reference
import run
from conftest import BENCH, NEEDLE_HEADER, hand_made_volume

CODEC = {"reference": "reference", "tag": "rs_10_4", "family": "rs",
         "data_shards": 10, "parity_shards": 4,
         "large_block_bytes": 1 << 30, "small_block_bytes": 4096}
BLOCK = 4096


def shard_files(raw: bytes) -> list[bytes]:
    rows = -(-len(raw) // (10 * BLOCK))
    data = np.zeros(rows * 10 * BLOCK, dtype=np.uint8)
    data[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    data = data.reshape(rows, 10, BLOCK).transpose(1, 0, 2).reshape(10, -1)
    parity = reference.gf_matmul(reference.parity_matrix(10, 4), data)
    return [row.tobytes() for row in (*data, *parity)]


class StandIn(http.server.ThreadingHTTPServer):
    """`/admin/faults` removes shard files, `/admin/ec/rebuild` writes
    the missing ones back, `GET /<fid>` answers a needle; `fault` breaks
    one of them from the `after`-th call on."""

    def __init__(self, base: str, raw: bytes, bodies: dict):
        super().__init__(("127.0.0.1", 0), Handler)
        self.base, self.bodies = base, bodies
        self.shards = shard_files(raw)
        self.fault, self.after, self.calls = None, 0, 0
        self.lock = threading.Lock()
        for i, data in enumerate(self.shards):
            self.write(i, data)

    def write(self, i: int, data: bytes) -> None:
        with open(f"{self.base}.ec{i:02d}", "wb") as f:
            f.write(data)

    def broken(self, fault: str) -> bool:
        return self.fault == fault and self.calls > self.after


def flipped(data: bytes) -> bytes:
    return data[:100] + bytes([data[100] ^ 1]) + data[101:]


class Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def answer(self, body: bytes, status: int = 200) -> None:
        self.send_response(status)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        s = self.server
        if self.path.startswith("/admin/ec/progress"):
            return self.answer(json.dumps(
                {"kind": "ec_rebuild", "stages": {}}).encode())
        with s.lock:
            s.calls += 1
            body = s.bodies[self.path[1:]]
            if s.broken("read_altered"):
                body = flipped(body)
        self.answer(body)

    def do_POST(self):
        s = self.server
        req = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if self.path == "/admin/faults":
            for f in req["faults"]:
                with contextlib.suppress(FileNotFoundError):
                    os.remove(f"{s.base}.ec{f['shard']:02d}")
            return self.answer(json.dumps(
                {"applied": [{"ok": True} for _ in req["faults"]]}).encode())
        assert self.path == "/admin/ec/rebuild", self.path
        s.calls += 1
        missing = [i for i in range(len(s.shards))
                   if not os.path.exists(f"{s.base}.ec{i:02d}")]
        for i in missing:
            if s.broken("rebuilt_file_altered"):
                s.write(i, flipped(s.shards[i]))
            elif not s.broken("rebuilt_file_not_written"):
                s.write(i, s.shards[i])
        if s.broken("answers_for_another_shard"):
            missing = [i + 1 for i in missing]
        self.answer(json.dumps({"rebuilt": missing}).encode())


@pytest.fixture
def stand_in(tmp_path):
    rng = np.random.default_rng(1)
    raw = rng.bytes(200_000)
    # 60 needles of 1,000-byte bodies, 3,200 bytes apart: some inside a
    # block of shard 0 or 1 (degraded with those two lost), most not
    placed = [(i + 1, 8 + 3200 * i, 1000) for i in range(60)]
    assert placed[-1][1] + NEEDLE_HEADER + 1000 <= len(raw)
    srv, base, loaded, bodies = hand_made_volume(tmp_path, raw, placed)
    volume = harness.describe_volume(srv, loaded, reference, CODEC)
    server = StandIn(base, raw, bodies)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    srv.volume = f"127.0.0.1:{server.server_address[1]}"
    yield server, srv, volume
    server.shutdown()
    thread.join(10)
    server.server_close()
    assert not thread.is_alive()


def cell_of(traffic: str, srv, volume, seconds: float):
    """What a driver is handed, without the server child, the device
    check and the profiler."""
    return types.SimpleNamespace(
        traffic=harness.load_json(os.path.join(BENCH, "traffic",
                                               traffic + ".json")),
        srv=srv, volumes=[volume], codec=CODEC, ref=reference, seed=1,
        seconds=seconds, device={"platform": "cpu", "kind": "cpu", "count": 1},
        tracer=types.SimpleNamespace(enabled=False, stop=lambda: None),
        post_steps=lambda steps, vids: harness.post_steps(
            srv, steps, vids, codec=CODEC["tag"]),
        window_begins=lambda: None, window_ended=lambda: None)


@pytest.mark.parametrize("fault, count", [
    (None, None),
    ("rebuilt_file_altered", "shard_files_wrong"),
    ("rebuilt_file_not_written", "shard_files_wrong"),
    ("answers_for_another_shard", "calls_answered_wrong"),
])
def test_rebuild_1lost(stand_in, fault, count):
    server, srv, volume = stand_in
    server.fault, server.after = fault, 2  # the warm-up and one call are sound
    cell = cell_of("rebuild_1lost", srv, volume, 0.5)
    result = run.load_module("drivers", "bulk_calls").run(cell)
    correct, compared = run.judge(result)
    assert result["attempted"] >= 3
    if fault is None:
        assert correct and result["failed"] == 0
        assert all(c["value"] == 0 and c["of"] > 0 for c in compared.values())
        assert compared["shard_files_wrong"]["of"] == \
            result["attempted"] + 1 + 14  # one a call, and the whole set last
    else:
        assert not correct and result["failed"] >= 1
        assert compared[count]["value"] >= 1
        assert result["failures"]


@pytest.mark.parametrize("fault", [None, "read_altered"])
def test_read_2lost(stand_in, fault):
    server, srv, volume = stand_in
    server.fault, server.after = fault, 40  # past the 32 warm-up reads
    cell = cell_of("read_2lost", srv, volume, 0.3)
    result = run.load_module("drivers", "closed_loop_reads").run(cell)
    correct, compared = run.judge(result)
    assert result["attempted"] > 10
    assert {o["klass"] for o in result["ops"]} == {"healthy", "degraded"}
    if fault is None:
        assert correct and compared == {"reads_wrong": {
            "value": 0, "limit": 0, "of": result["attempted"]}}
    else:
        assert not correct and compared["reads_wrong"]["value"] >= 1
        assert result["failed"] == compared["reads_wrong"]["value"]
