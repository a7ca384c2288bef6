"""The shard writers every EC job writes through (storage/ec/ec_files.py):
the two write primitives and their short-write loops, `_ShardWriterPool`
(per-shard order, release-after-write, error draining, run merging,
high-water marks, the copy_file_range fallback), `_ShardFlusher`,
`_make_sink`, `_finalize_shards`, the key set a job's `stats` carry, and
the three consumers on that one path (encode, rebuild, fleet conversion):
byte identity with ragged tails, crash safety of the tmp+rename commit,
and the fleet drain streaming parity per d2h block."""

import hashlib
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import fleet_convert
from seaweedfs_tpu.storage.ec import ec_files, layout


@pytest.fixture
def shard_fds(tmp_path):
    """open(n) -> n fresh shard fds (closed at teardown) and their paths."""
    opened: list[int] = []

    def open_n(n: int):
        paths = [str(tmp_path / f"s{len(opened) + i}") for i in range(n)]
        fds = [os.open(p, os.O_RDWR | os.O_CREAT, 0o644) for p in paths]
        opened.extend(fds)
        return fds, paths

    yield open_n
    for fd in opened:
        try:
            os.close(fd)
        except OSError:
            pass  # a test closed it on purpose


def _rand(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


# ---- the write primitives ----------------------------------------------

def test_pwritev_all_ragged_tail_and_odd_offset(shard_fds):
    """One 1 MiB run plus a 777-byte tail in one call, then a write at an
    odd offset past a gap: the file is the buffers where they were put."""
    (fd,), (path,) = shard_fds(1)
    body, tail, odd = _rand(1 << 20, 5), _rand(777, 6), _rand(300, 7)
    ec_files._pwritev_all(fd, [body, tail], 0)
    ec_files._pwritev_all(fd, [odd], body.nbytes + tail.nbytes + 13)
    with open(path, "rb") as f:
        assert f.read() == (body.tobytes() + tail.tobytes() + b"\0" * 13
                            + odd.tobytes())


def test_pwritev_all_resumes_a_short_write_mid_buffer(shard_fds,
                                                      monkeypatch):
    (fd,), (path,) = shard_fds(1)
    bufs = [_rand(1000, 1), _rand(500, 2), _rand(70, 3)]
    real, calls = os.pwritev, []

    def short(fd_, mvs, off):
        # at most 600 bytes a call: ends inside the first buffer, then
        # across the boundary of the first and the second
        calls.append((sum(len(m) for m in mvs), off))
        left, out = 600, []
        for m in mvs:
            out.append(m[:left])
            left -= len(out[-1])
            if not left:
                break
        return real(fd_, out, off)

    monkeypatch.setattr(os, "pwritev", short)
    ec_files._pwritev_all(fd, bufs, 40)
    assert calls == [(1570, 40), (970, 640), (370, 1240)]
    with open(path, "rb") as f:
        assert f.read() == b"\0" * 40 + b"".join(b.tobytes() for b in bufs)


def test_pwrite_all_resumes_a_short_write(shard_fds, monkeypatch):
    (fd,), (path,) = shard_fds(1)
    data = _rand(1000, 4)
    real, offs = os.pwrite, []

    def short(fd_, mv, off):
        offs.append(off)
        return real(fd_, mv[:333], off)

    monkeypatch.setattr(os, "pwrite", short)
    ec_files._pwrite_all(fd, data, 7)
    assert offs == [7, 340, 673, 1006]
    with open(path, "rb") as f:
        assert f.read() == b"\0" * 7 + data.tobytes()


@pytest.mark.parametrize("prim,call", [
    ("pwritev", lambda fd, d: ec_files._pwritev_all(fd, [d], 0)),
    ("pwrite", lambda fd, d: ec_files._pwrite_all(fd, d, 0))])
def test_write_primitives_raise_on_a_zero_return(shard_fds, monkeypatch,
                                                 prim, call):
    """A write that makes no progress is an error, not a loop: a shard
    must not commit with a gap, nor the writer spin on a full disk."""
    (fd,), _ = shard_fds(1)
    monkeypatch.setattr(os, prim, lambda *a: 0)
    with pytest.raises(OSError, match=f"{prim} returned 0"):
        call(fd, _rand(64))


# ---- the pool ----------------------------------------------------------

def test_writes_to_one_shard_land_in_submission_order(shard_fds):
    """Overlapping offsets on one shard: the last one submitted wins,
    whatever the other shards' workers are doing meanwhile."""
    fds, paths = shard_fds(4)
    pool = ec_files._ShardWriterPool(fds, workers=2)
    for gen in range(50):
        for shard in range(4):
            pool.put(shard, np.full(4096, gen, dtype=np.uint8),
                     (gen % 3) * 1000)
    pool.close()
    assert not pool.errors
    want = np.zeros(2000 + 4096, dtype=np.uint8)
    for gen in range(50):
        want[(gen % 3) * 1000:(gen % 3) * 1000 + 4096] = gen
    for p in paths:
        with open(p, "rb") as f:
            assert f.read() == want.tobytes()


def test_release_fires_once_a_buffer_after_its_bytes_are_readable(
        shard_fds):
    (fd,), (path,) = shard_fds(1)
    pool = ec_files._ShardWriterPool([fd])
    seen: list[tuple[int, bool]] = []
    bufs = [_rand(5000, i) for i in range(6)]

    def released(i):
        # runs on the writer thread: the batch's bytes are in the file
        with open(path, "rb") as f:
            f.seek(i * 5000)
            seen.append((i, f.read(5000) == bufs[i].tobytes()))

    pool.put_many(0, [(bufs[i], None, i * 5000, lambda i=i: released(i))
                      for i in range(3)])
    for i in range(3, 6):
        pool.put(0, bufs[i], i * 5000, release=lambda i=i: released(i))
    pool.close()
    assert not pool.errors
    assert sorted(seen) == [(i, True) for i in range(6)]  # once each


def test_release_still_fires_for_every_item_after_the_first_error(
        shard_fds):
    """A pooled buffer must come back even when the run is lost, or the
    producer waiting for the ring never learns the run failed."""
    fds, _ = shard_fds(2)
    os.close(fds[1])  # the second shard's disk is gone
    pool = ec_files._ShardWriterPool(fds, workers=1)
    fired: list[int] = []
    for i in range(8):
        pool.put(i % 2, _rand(100, i), i * 100,
                 release=lambda i=i: fired.append(i))
    pool.close()
    assert isinstance(pool.errors[0], OSError)
    assert sorted(fired) == list(range(8))


def test_closed_fd_surfaces_oserror_and_never_blocks_the_producer(
        shard_fds):
    """errors[0] is the OSError, close() does not raise, and a producer
    at depth=1 is never left waiting on a dead worker's full queue."""
    fds, _ = shard_fds(1)
    os.close(fds[0])
    pool = ec_files._ShardWriterPool(fds, depth=1, workers=1)
    done = threading.Event()

    def produce():
        for i in range(64):
            pool.put(0, _rand(10), i * 10)
        done.set()

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    assert done.wait(20), "producer blocked on a failed pool"
    pool.close()  # does not raise
    pool.close()  # idempotent
    assert pool.failed and isinstance(pool.errors[0], OSError)


def _count_pwritev(monkeypatch):
    real, calls = os.pwritev, []

    def counted(fd, mvs, off):
        calls.append((len(mvs), off))
        return real(fd, mvs, off)

    monkeypatch.setattr(os, "pwritev", counted)
    return calls


@pytest.mark.parametrize("case", ["contiguous", "gap", "past_iov_run"])
def test_put_many_merges_contiguous_runs_into_one_pwritev(
        shard_fds, monkeypatch, case):
    (fd,), (path,) = shard_fds(1)
    calls = _count_pwritev(monkeypatch)
    run = ec_files._ShardWriterPool._IOV_RUN
    if case == "contiguous":
        offs, want_calls = [0, 64, 128, 192], [(4, 0)]
    elif case == "gap":  # one byte short of contiguous: two runs
        offs, want_calls = [0, 64, 129, 193], [(2, 0), (2, 129)]
    else:  # a run longer than _IOV_RUN is cut there, not at IOV_MAX
        offs = [i * 64 for i in range(run + 3)]
        want_calls = [(run, 0), (3, run * 64)]
    bufs = [np.full(64, i % 251, dtype=np.uint8) for i in range(len(offs))]
    pool = ec_files._ShardWriterPool([fd])
    pool.put_many(0, [(b, None, o, None) for b, o in zip(bufs, offs)])
    pool.close()
    assert not pool.errors
    assert calls == want_calls
    want = np.zeros(offs[-1] + 64, dtype=np.uint8)
    for b, o in zip(bufs, offs):
        want[o:o + 64] = b
    with open(path, "rb") as f:
        assert f.read() == want.tobytes()


@pytest.mark.parametrize("via", ["put", "copy"])
def test_highwater_is_the_furthest_byte_written(shard_fds, tmp_path, via):
    fds, _ = shard_fds(3)
    src = tmp_path / "src.dat"
    data = _rand(10_000, 9)
    data.tofile(src)
    hw = [0, 0, 0]
    pool = ec_files._ShardWriterPool(fds, hw)
    with open(src, "rb") as f:
        # out of order on purpose: the mark is the furthest end, not the last
        for shard, off, n in ((0, 5000, 1234), (0, 0, 100),
                              (2, 777, 4000), (2, 100, 10)):
            if via == "put":
                pool.put(shard, data[:n], off)
            else:
                pool.copy(shard, f.fileno(), 0, off, n, src_view=data)
        pool.close()
    assert not pool.errors
    assert hw == [6234, 0, 4777]
    assert [os.fstat(fd).st_size for fd in fds] == hw


def test_copy_falls_back_to_pwrite_where_it_stopped_and_latches(
        shard_fds, tmp_path, monkeypatch):
    """copy_file_range dying half way (cross-fs, an old kernel): the rest
    comes from the mmap view by pwrite, from where the copy stopped, and
    the next copy does not try copy_file_range again."""
    (fd,), (path,) = shard_fds(1)
    src = tmp_path / "src.dat"
    data = _rand(9000, 3)
    data.tofile(src)
    real, tries = os.copy_file_range, []

    def dying(sfd, dfd, count, so, do):
        tries.append(count)
        if len(tries) > 1:
            raise OSError(18, "EXDEV")
        return real(sfd, dfd, min(count, 2500), so, do)

    monkeypatch.setattr(os, "copy_file_range", dying)
    monkeypatch.setattr(ec_files, "_CFR_OK", True)
    with open(src, "rb") as f:
        ec_files._copy_range(f.fileno(), fd, 1000, 50, 6000, src_view=data)
        assert tries == [6000, 3500] and ec_files._CFR_OK is False
        ec_files._copy_range(f.fileno(), fd, 0, 6050, 500, src_view=data)
        assert len(tries) == 2  # latched: straight to pwrite
    with open(path, "rb") as f:
        assert f.read() == (b"\0" * 50 + data[1000:7000].tobytes()
                            + data[:500].tobytes())


# ---- the submission fronts ---------------------------------------------

class _RecordingPool:
    def __init__(self):
        self.batches: list[tuple[int, list]] = []

    def put_many(self, shard, jobs):
        self.batches.append((shard, jobs))


def test_flusher_ships_every_shard_at_flush_bytes_and_the_rest_on_flush():
    pool = _RecordingPool()
    fl = ec_files._ShardFlusher(pool, 3, flush_bytes=1000)
    fl.put(0, b"a", 0)
    fl.copy(2, 9, 0, 0, 10)
    fl.account(999)
    assert pool.batches == []  # below the target: nothing crosses
    fl.put(0, b"b", 1)
    fl.account(1)  # crosses: every shard with jobs ships, as one batch
    assert [(s, len(j)) for s, j in pool.batches] == [(0, 2), (2, 1)]
    assert pool.batches[1][1][0][1] == (9, 0, 10, None)  # the copy job
    fl.put(1, b"c", 0)
    fl.account(10)
    assert len(pool.batches) == 2  # the count started again from zero
    fl.flush()
    assert [(s, len(j)) for s, j in pool.batches[2:]] == [(1, 1)]
    fl.flush()
    assert len(pool.batches) == 3  # nothing pending: nothing shipped


@pytest.mark.parametrize("delta,direct", [(0, True), (-1, False)])
def test_make_sink_is_the_pool_at_direct_min_and_a_flusher_below(
        shard_fds, delta, direct):
    fds, _ = shard_fds(2)
    pool = ec_files._ShardWriterPool(fds)
    try:
        sink = ec_files._make_sink(pool, 2, ec_files.DIRECT_MIN + delta)
        assert (sink is pool) == direct
        assert isinstance(sink, ec_files._ShardFlusher) != direct
    finally:
        pool.close()


@pytest.mark.parametrize("recycled,written", [(300_000, 100_000),
                                              (10_000, 10_000)])
def test_finalize_shards_cuts_a_longer_tmp_and_extends_with_a_hole(
        shard_fds, recycled, written):
    """A recycled .tmp longer than the new shard loses its stale bytes;
    one written short of shard_size grows by a hole, not by zeros on
    disk."""
    (fd,), (path,) = shard_fds(1)
    shard_size = 1 << 20
    os.pwrite(fd, b"\xff" * recycled, 0)  # an earlier attempt's bytes
    os.pwrite(fd, b"\x01" * written, 0)
    ec_files._finalize_shards([fd], [written], shard_size)
    st = os.fstat(fd)
    assert st.st_size == shard_size
    with open(path, "rb") as f:
        got = f.read()
    assert got == b"\x01" * written + b"\0" * (shard_size - written)
    # the suffix is a hole: far fewer blocks than its length
    assert st.st_blocks * 512 < written + 64 * 1024


# ---- what a job's stats carry ------------------------------------------

_GONE = ("submit_s", "complete_s", "submit_workers", "complete_workers",
         "aio_mode", "aio_direct_bytes", "aio_degraded_engines")
# the call's own stages, in every engine and under every codec (PR 36)
_CALL_KEYS = {"open_s", "map_s", "join_writers_s", "commit_s", "call_s",
              "wall_s"}
_ENCODE_KEYS = {
    "jax": {"read_s", "encode_s", "h2d_s", "dispatch_s", "d2h_s",
            "device_wait_s", "d2h_copy_s", "write_data_s",
            "write_parity_s", "stall_s", "ship_data_s", "await_unit_s",
            "await_parity_s", "join_drain_s",
            "write_data_workers", "write_parity_workers"} | _CALL_KEYS}
_REBUILD_KEYS = {
    "jax": {"reconstruct_s", "stage_s", "h2d_s", "dispatch_s",
            "device_wait_s", "d2h_copy_s", "unstage_s", "write_s",
            "stall_s", "await_batch_s", "join_drain_s",
            "write_workers"} | _CALL_KEYS}
# the native host codec rides the same pipeline: its whole computation is
# the seam's `dispatch`, and nothing goes up or comes back
_DEVICE_ONLY = {"h2d_s", "device_wait_s", "d2h_copy_s"}
_ENCODE_KEYS["cpp"] = _ENCODE_KEYS["jax"] - _DEVICE_ONLY - {"d2h_s"}
_REBUILD_KEYS["cpp"] = _REBUILD_KEYS["jax"] - _DEVICE_ONLY


@pytest.mark.parametrize("codec", ["jax", "cpp"])
@pytest.mark.parametrize("job", ["encode", "rebuild"])
def test_job_stats_carry_exactly_the_documented_stage_keys(
        tmp_path, monkeypatch, job, codec):
    """The `_s` and `_workers` keys /admin/ec/progress `stages` shows and
    the benchmark's `ec_progress` reader divides: the stages of the one
    path, and nothing of an engine under the writers."""
    from seaweedfs_tpu import native
    if codec == "cpp" and not native.available():
        pytest.skip("native codec unavailable")
    monkeypatch.setenv("WEEDTPU_EC_CODEC", codec)
    base = str(tmp_path / "v")
    _rand(300_000, 1).tofile(base + ".dat")
    stats: dict = {}
    ec_files.write_ec_files(base, large_block=16384, small_block=1024,
                            batch_size=8192, stats=stats)
    want = _ENCODE_KEYS[codec]
    if job == "rebuild":
        os.remove(base + layout.to_ext(3))
        os.remove(base + layout.to_ext(12))
        stats = {}
        ec_files.rebuild_ec_files(base, batch_size=8192, stats=stats)
        want = _REBUILD_KEYS[codec]
    got = {k for k in stats if k.endswith(("_s", "_workers"))}
    # a ring that never ran dry books no stall
    assert got - {"stall_s"} == want - {"stall_s"}, sorted(got ^ want)
    assert not [k for k in _GONE if k in stats]
    if codec == "cpp":  # the host seam's `dispatch` is summed into the lump
        lump, parts = (("encode_s", ["dispatch_s"]) if job == "encode" else
                       ("reconstruct_s", ["stage_s", "dispatch_s",
                                          "unstage_s"]))
        assert stats["dispatch_s"] > 0
        assert stats[lump] == pytest.approx(sum(stats[p] for p in parts))


# ---- the three consumers on the one path -------------------------------

def _shard_digest(base):
    h = hashlib.sha256()
    for i in range(layout.TOTAL_SHARDS):
        with open(base + layout.to_ext(i), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


# 100_001: ragged tail, shard size not a multiple of the page size
@pytest.mark.parametrize("size", [100_001, 3 * 4096 * 10])
def test_encode_rebuild_byte_identity(tmp_path, size):
    """Encode, lose a data and a parity shard, rebuild: the same digest."""
    base = str(tmp_path / f"v_{size}")
    _rand(size, 42).tofile(base + ".dat")
    ec_files.write_ec_files(base, large_block=16384, small_block=1024,
                            batch_size=8192)
    enc = _shard_digest(base)
    os.remove(base + layout.to_ext(3))
    os.remove(base + layout.to_ext(12))
    assert sorted(ec_files.rebuild_ec_files(base, batch_size=8192)) == \
        [3, 12]
    assert _shard_digest(base) == enc


def test_fleet_convert_byte_identity_with_write_ec_files(tmp_path):
    """Fleet conversion cuts the shard files write_ec_files cuts."""
    bases = []
    for v, size in enumerate((150_000, 77_777)):
        b = str(tmp_path / f"f{v}")
        _rand(size, v).tofile(b + ".dat")
        bases.append(b)
    fleet_convert.convert_volumes(bases, large_block=10_000,
                                  small_block=100, batch_size=1000)
    for b in bases:
        ref = b + "_ref"
        os.replace(b + ".dat", ref + ".dat")
        ec_files.write_ec_files(ref, large_block=10_000, small_block=100,
                                batch_size=1000)
        assert _shard_digest(b) == _shard_digest(ref), b


def test_fleet_convert_crash_safety_tmp_rename(tmp_path, monkeypatch):
    """A mid-stream failure must leave NO partial shard set visible —
    the .tmp staging + abort cleanup holds with writers in flight."""
    bases = []
    for v in range(2):
        b = str(tmp_path / f"c{v}")
        _rand(120_000, v).tofile(b + ".dat")
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

class _FakeRun:
    """Device-array stand-in for one parity run of one unit, whose copy
    back (`np.asarray`) is logged, so the test can see writes interleave
    with transfers."""

    def __init__(self, data, log, idx):
        self.nbytes = data.nbytes
        self._data = data
        self._log = log
        self._idx = idx

    def devices(self):
        return {"fake"}

    def copy_to_host_async(self):
        pass

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        self._log.append(("d2h", self._idx))
        return self._data


def test_drain_streams_parity_writes_per_d2h_block(tmp_path, monkeypatch):
    """The fleet drain must fan out and SUBMIT each unit's parity the
    moment that unit's d2h lands — a parity flush interleaved between
    two units' transfers proves write_parity overlaps d2h instead of
    serializing behind the whole batch's."""
    from seaweedfs_tpu.models import rs
    code = rs.get_code(10, 4)
    log: list = []

    class StreamCodec:
        k, m = 10, 4

        def place_units(self, units):
            return units

        def encode_units_linear(self, placed, stripes):
            out = []
            for u in placed:
                if u is None:
                    out.append(None)
                    continue
                data = np.concatenate(u).reshape(stripes, 10, -1)
                par = code.encode_numpy(
                    data.transpose(1, 0, 2).reshape(10, -1))[code.k:]
                out.append(tuple(_FakeRun(row, log, len(log))
                                 for row in par))
            return out

    orig_flush = ec_files._ShardFlusher.flush

    def logged_flush(self):
        if any(self._jobs):
            log.append(("flush",))
        return orig_flush(self)

    monkeypatch.setattr(ec_files._ShardFlusher, "flush", logged_flush)
    bases = []
    for v in range(2):
        b = str(tmp_path / f"s{v}")
        _rand(60_000, v).tofile(b + ".dat")
        bases.append(b)
    stats: dict = {}
    fleet_convert.convert_volumes(bases, large_block=10_000,
                                  small_block=100, batch_size=1000,
                                  codec=StreamCodec(), stats=stats)
    d2h = [i for i, e in enumerate(log) if e[0] == "d2h"]
    flushes = [i for i, e in enumerate(log) if e[0] == "flush"]
    assert len(d2h) >= 8  # m runs a unit, units of two volumes
    # at least one parity flush lands BETWEEN two d2h events: the
    # writers were already busy while a later unit was still in flight
    assert any(d2h[j] < f < d2h[j + 1]
               for f in flushes for j in range(len(d2h) - 1)), log
    assert stats["d2h_s"] > 0  # the streamed next() was timed
    # and the output is still correct
    for b in bases:
        ref = b + "_ref"
        os.replace(b + ".dat", ref + ".dat")
        ec_files.write_ec_files(ref, large_block=10_000, small_block=100,
                                batch_size=1000)
        assert _shard_digest(b) == _shard_digest(ref), b
