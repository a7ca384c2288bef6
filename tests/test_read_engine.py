"""Read-path engine tests: the EC batched degraded-read engine (byte
identity across codecs, pread thread-safety, interval coalescing into one
reconstruction dispatch), the filer streaming pipeline (singleflight
collapse, readahead byte order over sparse gaps), and the chunk-cache
satellites (tmp cleanup on error, .tmp exclusion from eviction totals,
stats export)."""

import asyncio
import os
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils.chunk_cache import ChunkCache, DiskTier

LARGE, SMALL = 10000, 100  # test block sizes (reference ec_test.go:16-19)


def _make_ec(tmp_path, n=60, seed=5, max_size=4000):
    """A small EC-encoded volume; returns (base, {needle_id: bytes})."""
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(seed)
    blobs = {}
    for i in range(1, n + 1):
        size = int(rng.integers(1, max_size))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=SMALL * 10)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, blobs


# ---- EC batched degraded reads ----------------------------------------

@pytest.mark.parametrize("codec,lost", [
    ("numpy", (2, 5, 11)), ("jax", (2, 5, 11)), ("cpp", (2, 5, 11)),
    ("numpy", (0, 7))])  # 2 data + 1 parity lost; the first shard lost
def test_degraded_read_byte_identity_across_codecs(tmp_path, monkeypatch,
                                                   codec, lost):
    """Degraded read_needle through the batched engine must return the
    bytes that were written, for host (numpy/cpp) and device-seam (jax)
    codecs alike."""
    if codec == "cpp" and not native.available():
        pytest.skip("native codec unavailable")
    base, blobs = _make_ec(tmp_path, n=50)
    for sid in lost:
        os.remove(base + layout.to_ext(sid))
    monkeypatch.setenv("WEEDTPU_EC_CODEC", codec)
    ev = ec_volume.EcVolume(base)
    try:
        for nid, data in blobs.items():
            assert ev.read_needle(nid).data == data, nid
        stats = ev.read_stats_snapshot()
        assert stats["reconstruct_batches"] >= 1
        assert stats["reconstruct_intervals"] >= stats["reconstruct_batches"]
    finally:
        ev.close()


def test_concurrent_degraded_reads_one_volume(tmp_path, monkeypatch):
    """Many threads hammering one EcVolume: the pread-based shard reads
    must not race a shared file position (the old seek+read did)."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, blobs = _make_ec(tmp_path, n=40)
    for sid in (1, 8):
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    errors: list = []

    def worker(seed: int) -> None:
        rng = np.random.default_rng(seed)
        ids = list(blobs)
        rng.shuffle(ids)
        try:
            for nid in ids:
                if ev.read_needle(nid).data != blobs[nid]:
                    raise AssertionError(f"bytes mismatch for {nid}")
        except Exception as e:  # surfaced after join
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    ev.close()
    assert not errors, errors


def test_coalesced_intervals_one_dispatch(tmp_path, monkeypatch):
    """A needle spanning many blocks of a missing shard must reconstruct
    in ONE codec dispatch (the old engine paid one matmul per interval),
    with adjacent same-shard ranges coalesced into single reads."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    # one 6KB needle -> ~60 small-block intervals across all 10 shards
    base, blobs = _make_ec(tmp_path, n=1, seed=8, max_size=2)
    vol_dir = tmp_path
    vol = Volume(str(vol_dir), "", 4)
    big = np.random.default_rng(9).integers(
        0, 256, 6000, dtype=np.uint8).tobytes()
    vol.append_needle(ndl.Needle(cookie=0x9, id=1, data=big))
    vol.close()
    base = str(vol_dir / "4")
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=SMALL * 10)
    ec_files.write_sorted_ecx(base + ".idx")
    os.remove(base + layout.to_ext(3))

    calls = []
    real = ec_files._reconstruct_batch

    def counting(codec, rows, ids, wanted, **kw):
        calls.append(list(wanted))
        return real(codec, rows, ids, wanted, **kw)

    monkeypatch.setattr(ec_files, "_reconstruct_batch", counting)
    ev = ec_volume.EcVolume(base)
    try:
        assert ev.read_needle(1).data == big
        assert len(calls) == 1, calls  # one dispatch for the whole needle
        stats = ev.read_stats_snapshot()
        assert stats["intervals_coalesced"] > 0
        # hot-needle repeat: served from the reconstruction LRU
        assert ev.read_needle(1).data == big
        assert len(calls) == 1, calls
        assert ev.read_stats_snapshot()["reconstruct_cache_hits"] > 0
    finally:
        ev.close()


# ---- filer streaming: singleflight + readahead -------------------------

def _mk_filer():
    from seaweedfs_tpu.server.filer_server import FilerServer
    return FilerServer("127.0.0.1:0")


def test_singleflight_collapses_concurrent_fetches():
    fs = _mk_filer()
    calls = []

    async def fake_once(v, cache):
        calls.append(v.fid)
        await asyncio.sleep(0.02)
        return b"x" * 64

    fs._load_chunk_once = fake_once
    view = SimpleNamespace(fid="1,ab", cipher_key=b"", is_compressed=False)

    async def main():
        res = await asyncio.gather(
            *[fs._load_chunk_view(view, True) for _ in range(8)])
        assert all(r == b"x" * 64 for r in res)

    asyncio.run(main())
    assert len(calls) == 1, calls  # 8 concurrent readers, ONE fetch
    assert not fs._chunk_flight  # table empties once the flight lands


def test_singleflight_does_not_cache_failures():
    fs = _mk_filer()
    state = {"n": 0}

    async def flaky_once(v, cache):
        state["n"] += 1
        if state["n"] == 1:
            raise IOError("upstream died")
        return b"ok"

    fs._load_chunk_once = flaky_once
    view = SimpleNamespace(fid="1,cd", cipher_key=b"", is_compressed=False)

    async def main():
        with pytest.raises(IOError):
            await fs._load_chunk_view(view, True)
        assert await fs._load_chunk_view(view, True) == b"ok"

    asyncio.run(main())
    assert state["n"] == 2


class _Sink:
    def __init__(self):
        self.buf = bytearray()

    async def write(self, b: bytes) -> None:
        self.buf += b


@pytest.mark.parametrize("depth", ["0", "3"])
def test_readahead_preserves_order_with_sparse_gaps(monkeypatch, depth):
    """Ranged reads over a sparse chunk list must produce identical bytes
    through the serial loop and the readahead pipeline: in-order writes,
    zero-filled gaps, zero-filled tail."""
    from seaweedfs_tpu.filer.entry import FileChunk
    fs = _mk_filer()
    data = {f"1,{i:02x}": bytes([65 + i]) * 1000 for i in range(5)}
    # layout: [0,1000) [1000,2000) gap [3000,4000) [4500,5500) gap tail
    chunks = [
        FileChunk(fid="1,00", offset=0, size=1000, mtime=1),
        FileChunk(fid="1,01", offset=1000, size=1000, mtime=1),
        FileChunk(fid="1,02", offset=3000, size=1000, mtime=1),
        FileChunk(fid="1,03", offset=4500, size=1000, mtime=1),
    ]

    async def fake_fetch(fid, cache=True):
        # jitter completion order: later chunks land first
        await asyncio.sleep(0.001 * ((hash(fid) % 3) + 1))
        return data[fid]

    fs._fetch_chunk = fake_fetch
    monkeypatch.setenv("WEEDTPU_READAHEAD", depth)
    offset, length = 500, 5500  # mid-chunk start, past-EOF tail
    expected = (data["1,00"][500:] + data["1,01"]
                + b"\x00" * 1000 + data["1,02"]
                + b"\x00" * 500 + data["1,03"]
                + b"\x00" * 500)
    sink = _Sink()
    asyncio.run(fs._stream_range(sink, chunks, offset, length))
    assert bytes(sink.buf) == expected


# ---- chunk cache satellites -------------------------------------------

def test_disk_tier_unlinks_tmp_on_error(tmp_path, monkeypatch):
    tier = DiskTier(str(tmp_path / "t"), 1 << 20)

    def boom(src, dst):
        raise OSError("no rename for you")

    monkeypatch.setattr(os, "replace", boom)
    tier.put("k", b"abc")
    leftovers = [n for n in os.listdir(tier.dir) if n.endswith(".tmp")]
    assert leftovers == []


def test_disk_tier_evict_skips_tmp(tmp_path):
    tier = DiskTier(str(tmp_path / "t"), 3000)
    stale = os.path.join(tier.dir, "deadbeef.tmp")
    with open(stale, "wb") as f:
        f.write(b"z" * 10000)  # stale tmp bigger than the whole tier
    for i in range(4):
        tier.put(f"k{i}", b"y" * 1000)
    # the stale tmp neither counts toward the total nor gets evicted,
    # and live entries survive because the tmp no longer inflates totals
    assert os.path.exists(stale)
    live = [n for n in os.listdir(tier.dir) if not n.endswith(".tmp")]
    assert len(live) >= 3


def test_chunk_cache_stats(tmp_path):
    cc = ChunkCache(mem_limit=1 << 20, disk_dir=str(tmp_path / "cc"),
                    disk_limit=3 << 20)
    cc.put("a", b"x" * 10)
    assert cc.get("a") == b"x" * 10
    assert cc.get("missing") is None
    st = cc.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["mem_bytes"] == 10
    assert any(k.startswith("tier") for k in st)


def test_ec_read_stats_reach_metrics_registry(tmp_path, monkeypatch):
    """The volume server mirrors EcVolume counters into /metrics."""
    from seaweedfs_tpu.stats import metrics
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, blobs = _make_ec(tmp_path, n=10)
    os.remove(base + layout.to_ext(0))
    ev = ec_volume.EcVolume(base)
    try:
        for nid in blobs:
            ev.read_needle(nid)
        for stat, v in ev.read_stats_snapshot().items():
            metrics.EC_DEGRADED_READ.labels(stat).set(v)
        rendered = metrics.REGISTRY.render()
        assert 'weedtpu_ec_degraded_read{stat="reconstruct_batches"}' \
            in rendered
    finally:
        ev.close()
