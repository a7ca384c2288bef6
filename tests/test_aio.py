"""Host async-I/O engine (storage/aio.py): io_uring/O_DIRECT shard
writeback and its degradation ladder.

Covers the engine's own contracts (alignment splitting, unaligned-tail
deferral, registered buffers, probe-driven mode resolution) and the
three consumers riding it — encode, rebuild, fleet conversion — for
byte-identity across every WEEDTPU_AIO mode, including ragged tails and
shard sizes that are NOT a multiple of the O_DIRECT alignment.  Also
the failure ladder: a host whose io_uring probe fails must degrade to
pwritev batching without changing a single output byte, and the
tmp+rename crash-safety of fleet conversion must hold under the ring.
"""

import errno
import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops import fleet_convert
from seaweedfs_tpu.storage import aio
from seaweedfs_tpu.storage.ec import ec_files, layout

MODES = aio.MODES
# (WEEDTPU_AIO, WEEDTPU_AIO_DIRECT) columns: O_DIRECT is opt-in, so the
# aligned-split + deferred-tail machinery gets its own column next to
# the three plain modes
CONFIGS = [(m, "0") for m in MODES] + [("uring", "1")]


@pytest.fixture(autouse=True)
def _fresh_probe():
    """The uring probe caches process-wide; tests that monkeypatch the
    syscall or force modes must not leak the verdict."""
    aio._reset_probe_cache()
    yield
    aio._reset_probe_cache()


def _set_mode(monkeypatch, mode, direct="0"):
    monkeypatch.setenv("WEEDTPU_AIO", mode)
    monkeypatch.setenv("WEEDTPU_AIO_DIRECT", direct)
    aio._reset_probe_cache()


# ---- engine unit contracts ---------------------------------------------

def test_aligned_empty_is_aligned():
    buf = aio.aligned_empty((4, 8192))
    assert aio._buf_addr(buf) % aio.ALIGN == 0
    # rows stay aligned when the stride is a multiple of ALIGN
    assert aio._buf_addr(buf[2]) % aio.ALIGN == 0


@pytest.mark.parametrize("mode,direct", CONFIGS)
def test_writev_modes_byte_identical_with_ragged_tail(tmp_path,
                                                      monkeypatch, mode,
                                                      direct):
    """One aligned run plus a 777-byte unaligned tail, then a write at
    an odd (unaligned) offset: every mode must produce the same file."""
    _set_mode(monkeypatch, mode, direct)
    rng = np.random.default_rng(5)
    body = aio.aligned_empty((1, 1024 * 1024))[0]
    body[:] = rng.integers(0, 256, body.shape, dtype=np.uint8)
    tail = rng.integers(0, 256, 777, dtype=np.uint8)
    odd = rng.integers(0, 256, 300, dtype=np.uint8)
    p = str(tmp_path / f"f_{mode}_{direct}")
    fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        eng = aio.WriteEngine(reg=[body])
        assert eng.mode == aio.engine_mode()
        eng.writev(fd, [body, tail], 0)
        eng.writev(fd, [odd], body.nbytes + tail.nbytes + 13)
        eng.drain()
        eng.close()
    finally:
        os.close(fd)
    with open(p, "rb") as f:
        got = f.read()
    want = body.tobytes() + tail.tobytes() + b"\0" * 13 + odd.tobytes()
    assert got == want


def test_auto_resolves_ring_only_with_direct(monkeypatch):
    """``auto`` picks the ring only when O_DIRECT gives its completions
    device latency to hide; page-cache writeback rides pwritev (punting
    buffered writes to io-wq workers is a measured loss on filesystems
    without NOWAIT support).  Explicit ``uring`` always engages."""
    if not aio.probe_uring():
        pytest.skip("io_uring unavailable on this host")
    monkeypatch.delenv("WEEDTPU_AIO", raising=False)
    monkeypatch.setenv("WEEDTPU_AIO_DIRECT", "0")
    assert aio.engine_mode() == "pwritev"
    assert aio.engine_label() == "pwritev"
    monkeypatch.setenv("WEEDTPU_AIO_DIRECT", "1")
    assert aio.engine_mode() == "uring"
    assert aio.engine_label() == "uring+direct"
    monkeypatch.setenv("WEEDTPU_AIO", "uring")
    monkeypatch.setenv("WEEDTPU_AIO_DIRECT", "0")
    assert aio.engine_mode() == "uring"  # explicit request engages
    assert aio.engine_label() == "uring"


def test_uring_probe_failure_degrades_to_pwritev(monkeypatch, capsys):
    """auto/uring on a host whose io_uring probe fails must resolve to
    the pwritev ladder rung, warning only when uring was explicit."""
    monkeypatch.setattr(aio, "probe_uring", lambda: False)
    monkeypatch.setenv("WEEDTPU_AIO", "uring")
    aio._reset_probe_cache()
    assert aio.engine_mode() == "pwritev"
    assert "io_uring" in capsys.readouterr().err
    monkeypatch.delenv("WEEDTPU_AIO")
    assert aio.engine_mode() == "pwritev"  # auto degrades silently
    info = aio.engine_info()
    assert info["mode"] == "pwritev" and not info["uring_available"]


def test_engine_writes_identical_after_forced_fallback(tmp_path,
                                                       monkeypatch):
    """The degraded engine is not a different writer, just a slower
    one: forced-fallback output matches real-uring output bytewise."""
    data = np.random.default_rng(9).integers(
        0, 256, 256 * 1024 + 999, dtype=np.uint8)

    def write(mode_forced):
        p = str(tmp_path / f"g_{mode_forced}")
        fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            eng = aio.WriteEngine()
            eng.writev(fd, [data], 0)
            eng.drain()
            eng.close()
        finally:
            os.close(fd)
        with open(p, "rb") as f:
            return f.read()

    monkeypatch.setenv("WEEDTPU_AIO", "uring")
    aio._reset_probe_cache()
    ref = write("uring")
    monkeypatch.setattr(aio, "probe_uring", lambda: False)
    aio._reset_probe_cache()
    assert write("fallback") == ref == data.tobytes()


def test_odirect_is_opt_in_and_engages_on_aligned_runs(tmp_path,
                                                       monkeypatch):
    """By default aligned runs ride the page cache (direct_bytes stays
    0); WEEDTPU_AIO_DIRECT=1 routes them around it."""
    if not aio.probe_uring():
        pytest.skip("io_uring unavailable on this host")
    body = aio.aligned_empty((1, 256 * 1024))[0]
    body[:] = 3

    def run(direct):
        _set_mode(monkeypatch, "uring", direct)
        p = str(tmp_path / f"d{direct}")
        fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            eng = aio.WriteEngine(reg=[body])
            eng.writev(fd, [body], 0)
            eng.drain()
            n = eng.direct_bytes
            eng.close()
        finally:
            os.close(fd)
        with open(p, "rb") as f:
            assert f.read() == body.tobytes()
        return n

    assert run("0") == 0
    got = run("1")
    if got == 0:
        pytest.skip("filesystem refused O_DIRECT (EINVAL latch took it)")
    assert got == body.nbytes


def test_uring_engages_ring_without_direct(tmp_path, monkeypatch):
    """Default config (uring mode, O_DIRECT off) must still drive the
    ring: every run goes out as SQEs — the engine is not a deferred
    synchronous writer wearing an async label.  Regression test for the
    bug where direct-off routed everything to the tail path and drain()
    wrote it all with pwritev."""
    if not aio.probe_uring():
        pytest.skip("io_uring unavailable on this host")
    _set_mode(monkeypatch, "uring", "0")
    body = aio.aligned_empty((1, 256 * 1024))[0]
    body[:] = 7
    tail = np.full(777, 9, dtype=np.uint8)
    p = str(tmp_path / "ring")
    fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        eng = aio.WriteEngine(reg=[body])
        assert eng.mode == "uring" and eng._ring is not None
        eng.writev(fd, [body], 0)
        # the run is queued ON THE RING, not parked in the deferred
        # synchronous tail list
        assert not eng._tails
        assert eng._ring.inflight == 1 and len(eng._pending) == 1
        # without O_DIRECT there is no alignment rule: the unaligned
        # buffer rides the ring too
        eng.writev(fd, [tail], body.nbytes)
        assert not eng._tails
        assert eng._ring.inflight == 2
        eng.drain()
        assert eng.wbytes == body.nbytes + tail.nbytes
        assert eng.fixed_bytes == body.nbytes  # registered -> WRITE_FIXED
        assert eng.direct_bytes == 0  # page cache, as opted
        eng.close()
    finally:
        os.close(fd)
    with open(p, "rb") as f:
        assert f.read() == body.tobytes() + tail.tobytes()


def test_odirect_einval_latch_rescues_all_inflight_runs(tmp_path):
    """EVERY in-flight direct run completing with -EINVAL must rewrite
    buffered, not just the first: the first failing CQE un-latches the
    fd, and later completions used to miss the 'fd in _direct_fds'
    guard and hard-fail the encode on filesystems without O_DIRECT."""
    a = aio.aligned_empty(aio.ALIGN)
    a[:] = 1
    b = aio.aligned_empty(aio.ALIGN)
    b[:] = 2
    p = str(tmp_path / "latch")
    fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        eng = aio.WriteEngine(mode="buffered")  # _complete needs no ring
        # two direct runs in flight at once, as the writer pool submits
        # them; both CQEs come back EINVAL (tmpfs-style refusal)
        eng._direct_fds.add(fd)
        eng._pending[1] = (aio._OP_WRITEV, fd, [a], 0, a.nbytes,
                           None, 0, True)
        eng._pending[2] = (aio._OP_WRITEV, fd, [b], aio.ALIGN, b.nbytes,
                           None, 0, True)
        eng._complete(1, -errno.EINVAL)  # latches the fd buffered
        eng._complete(2, -errno.EINVAL)  # must rewrite too, not raise
        assert fd in eng._no_direct_fds
        assert eng.wbytes == a.nbytes + b.nbytes
        eng.close()
    finally:
        os.close(fd)
    with open(p, "rb") as f:
        assert f.read() == a.tobytes() + b.tobytes()


def test_ensure_buffered_flushes_deferred_tails(tmp_path, monkeypatch):
    """The non-engine-I/O barrier must also write out deferred tails
    for the fd — a copy_file_range issued after it must land over
    fully-ordered prior writes, not jump ahead of a queued tail."""
    if not aio.probe_uring():
        pytest.skip("io_uring unavailable on this host")
    _set_mode(monkeypatch, "uring", "1")
    tail = np.full(777, 5, dtype=np.uint8)  # unaligned -> deferred
    p = str(tmp_path / "barrier")
    fd = os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        eng = aio.WriteEngine()
        if eng.mode != "uring":
            pytest.skip("ring setup failed on this host")
        eng.writev(fd, [tail], 0)
        assert eng._tails  # parked for the post-direct buffered pwrite
        eng.ensure_buffered(fd)
        assert not eng._tails
        assert os.pread(fd, 777, 0) == tail.tobytes()  # already on disk
        eng.drain()
        eng.close()
    finally:
        os.close(fd)


# ---- consumer byte-identity across modes --------------------------------

def _shard_digest(base):
    h = hashlib.sha256()
    for i in range(layout.TOTAL_SHARDS):
        with open(base + layout.to_ext(i), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


# 100_001: ragged tail; shard size not a multiple of 4096 — the
# O_DIRECT-ineligible remainder must land via the buffered tail path
@pytest.mark.parametrize("size", [100_001, 3 * 4096 * 10])
def test_encode_rebuild_byte_identity_across_modes(tmp_path, size):
    rng = np.random.default_rng(size)
    digests = set()
    for mode, direct in CONFIGS:
        os.environ["WEEDTPU_AIO"] = mode
        os.environ["WEEDTPU_AIO_DIRECT"] = direct
        try:
            base = str(tmp_path / f"v_{mode}{direct}_{size}")
            rng2 = np.random.default_rng(42)
            rng2.integers(0, 256, size, dtype=np.uint8).tofile(
                base + ".dat")
            stats: dict = {}
            ec_files.write_ec_files(base, large_block=16384,
                                    small_block=1024,
                                    batch_size=8192, stats=stats)
            assert stats.get("aio_mode") == aio.engine_mode()
            enc = _shard_digest(base)
            digests.add(enc)
            os.remove(base + layout.to_ext(3))
            os.remove(base + layout.to_ext(12))
            ec_files.rebuild_ec_files(base, batch_size=8192)
            assert _shard_digest(base) == enc  # rebuild byte-identical
        finally:
            os.environ.pop("WEEDTPU_AIO", None)
            os.environ.pop("WEEDTPU_AIO_DIRECT", None)
        aio._reset_probe_cache()
    assert len(digests) == 1, digests
    del rng


def test_fleet_convert_byte_identity_across_modes(tmp_path):
    digests = set()
    for mode, direct in CONFIGS:
        os.environ["WEEDTPU_AIO"] = mode
        os.environ["WEEDTPU_AIO_DIRECT"] = direct
        try:
            bases = []
            for v, size in enumerate((150_000, 77_777)):
                b = str(tmp_path / f"{mode}{direct}_{v}")
                np.random.default_rng(v).integers(
                    0, 256, size, dtype=np.uint8).tofile(b + ".dat")
                bases.append(b)
            fleet_convert.convert_volumes(
                bases, large_block=10_000, small_block=100,
                batch_size=1000)
            h = hashlib.sha256()
            for b in bases:
                h.update(_shard_digest(b).encode())
            digests.add(h.hexdigest())
        finally:
            os.environ.pop("WEEDTPU_AIO", None)
            os.environ.pop("WEEDTPU_AIO_DIRECT", None)
        aio._reset_probe_cache()
    assert len(digests) == 1, digests


def test_fleet_convert_crash_safety_tmp_rename_under_uring(tmp_path,
                                                           monkeypatch):
    """A mid-stream failure must leave NO partial shard set visible —
    the .tmp staging + abort cleanup holds under the async engine."""
    _set_mode(monkeypatch, "uring")
    bases = []
    for v in range(2):
        b = str(tmp_path / f"c{v}")
        np.random.default_rng(v).integers(
            0, 256, 120_000, dtype=np.uint8).tofile(b + ".dat")
        bases.append(b)
    boom = RuntimeError("injected mid-convert failure")
    orig = fleet_convert.dispatch_parity_batch
    calls = {"n": 0}

    def failing(codec, units, **kw):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise boom
        return orig(codec, units, **kw)

    monkeypatch.setattr(fleet_convert, "dispatch_parity_batch", failing)
    with pytest.raises(RuntimeError, match="injected"):
        fleet_convert.convert_volumes(bases, large_block=10_000,
                                      small_block=100, batch_size=1000)
    for b in bases:
        for i in range(layout.TOTAL_SHARDS):
            assert not os.path.exists(b + layout.to_ext(i))
            assert not os.path.exists(b + layout.to_ext(i) + ".tmp")
        assert os.path.exists(b + ".dat")  # source untouched


# ---- streaming drain: write_parity overlaps d2h -------------------------

class _FakeShard:
    def __init__(self, start, stop, data, log, idx):
        self.index = (slice(start, stop),)
        self._data = data
        self._log = log
        self._idx = idx

    @property
    def data(self):
        self._log.append(("d2h", self._idx))
        return self._data


class _FakeParity:
    """Device-array stand-in: two addressable blocks whose .data access
    is logged, so the test can see writes interleave with transfers."""

    def __init__(self, parity, log):
        self.nbytes = parity.nbytes
        half = parity.shape[0] // 2
        self._shards = [
            _FakeShard(0, half, parity[:half], log, 0),
            _FakeShard(half, parity.shape[0], parity[half:], log, 1),
        ]

    def block_until_ready(self):
        return self

    @property
    def addressable_shards(self):
        return self._shards


def test_drain_streams_parity_writes_per_d2h_block(tmp_path, monkeypatch):
    """The fleet drain must fan out and SUBMIT each block's parity the
    moment that block's d2h lands — a parity flush interleaved between
    the two fake-shard transfers proves write_parity overlaps d2h
    instead of serializing behind a full gather."""
    from seaweedfs_tpu.models import rs
    code = rs.get_code(10, 4)
    log: list = []

    class StreamCodec:
        k, m = 10, 4

        def place(self, units):
            return units

        def encode_parity_batch(self, units):
            par = np.stack([code.encode_numpy(units[u])[code.k:]
                            for u in range(units.shape[0])])
            return _FakeParity(par, log)

    orig_flush = ec_files._ShardFlusher.flush

    def logged_flush(self):
        if any(self._jobs):
            log.append(("flush",))
        return orig_flush(self)

    monkeypatch.setattr(ec_files._ShardFlusher, "flush", logged_flush)
    bases = []
    for v in range(2):
        b = str(tmp_path / f"s{v}")
        np.random.default_rng(v).integers(
            0, 256, 60_000, dtype=np.uint8).tofile(b + ".dat")
        bases.append(b)
    stats: dict = {}
    fleet_convert.convert_volumes(bases, large_block=10_000,
                                  small_block=100, batch_size=1000,
                                  codec=StreamCodec(), stats=stats)
    d2h = [i for i, e in enumerate(log) if e[0] == "d2h"]
    flushes = [i for i, e in enumerate(log) if e[0] == "flush"]
    assert len(d2h) >= 4  # two blocks per dispatched batch
    # at least one parity flush lands BETWEEN two d2h events: the
    # writers were already busy while a later block was still in flight
    assert any(d2h[j] < f < d2h[j + 1]
               for f in flushes for j in range(len(d2h) - 1)), log
    assert stats["d2h_s"] > 0  # the streamed next() was timed
    # and the output is still correct
    for b in bases:
        ref = b + "_ref"
        os.replace(b + ".dat", ref + ".dat")
        ec_files.write_ec_files(ref, large_block=10_000, small_block=100,
                                batch_size=1000)
        for i in range(layout.TOTAL_SHARDS):
            with open(b + layout.to_ext(i), "rb") as f1, \
                    open(ref + layout.to_ext(i), "rb") as f2:
                assert f1.read() == f2.read(), (b, i)


# ---- stage accounting ---------------------------------------------------

def test_submit_complete_stage_accounting(tmp_path, monkeypatch):
    """A uring-mode encode publishes the engine's submit/complete split
    (as worker-normalized stage keys the observatory maps to the disk
    resource), and overlap_fraction does NOT double-count them — they
    are a finer cut of the same seconds the write stages carry."""
    if not aio.probe_uring():
        pytest.skip("io_uring unavailable on this host")
    _set_mode(monkeypatch, "uring")
    base = str(tmp_path / "v")
    np.random.default_rng(1).integers(
        0, 256, 300_000, dtype=np.uint8).tofile(base + ".dat")
    stats: dict = {}
    ec_files.write_ec_files(base, large_block=16384, small_block=1024,
                            batch_size=8192, stats=stats)
    assert stats["aio_mode"] == "uring"
    assert stats["submit_s"] >= 0 and stats["complete_s"] >= 0
    assert stats["submit_workers"] == stats["complete_workers"] > 0
    from seaweedfs_tpu.stats.pipeline import STAGE_RESOURCE
    assert STAGE_RESOURCE["submit"] == "disk"
    assert STAGE_RESOURCE["complete"] == "disk"
    # overlap_fraction excludes the sub-stages: inflating them must not
    # change the reported overlap
    frac = ec_files.overlap_fraction(stats)
    inflated = dict(stats, submit_s=99.0, complete_s=99.0)
    assert ec_files.overlap_fraction(inflated) == frac
