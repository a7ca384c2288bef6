"""Rebuild keeps its batches in flight (`ec_files._rebuild_pipelined` over
`dispatch.dispatch_reconstruct` / `materialize_rows`): the rebuilt files
against the plain references, how many batches are out at once, and the
ways a call can end early: each without a hang, a `.tmp`, a renamed shard
or a live view of a map.  CPU, the XLA shell as the device codec."""

import gc
import os
import sys
import threading

import numpy as np
import pytest

from seaweedfs_tpu.models import lrc as lrc_ref
from seaweedfs_tpu.models import msr as msr_ref
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import codecs, dispatch
from seaweedfs_tpu.stats import pipeline
from seaweedfs_tpu.storage.ec import ec_files, layout

BATCH = 4096
# more batches than PIPELINE_DEPTH and a short last one (a multiple of
# msr_9_16's alpha all the same)
SHARD = 4 * BATCH + 704
BATCHES = 5
SEAM = ("stage", "h2d", "dispatch", "device_wait", "d2h_copy", "unstage")
CASES = [("rs_10_4", [3]), ("rs_10_4", [0, 5, 11, 13]),
         ("lrc_12_2_2", [3]), ("msr_9_16", [4])]
IDS = ["rs_1lost", "rs_4lost", "lrc_local_1lost", "msr_1lost"]
REFERENCE = {"rs_10_4": (10, rs.get_code(10, 4).encode_numpy),
             "lrc_12_2_2": (12, lrc_ref.encode),
             "msr_9_16": (9, msr_ref.encode)}


@pytest.fixture(autouse=True)
def _xla_shell(monkeypatch):
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    pipeline.reset()
    yield
    pipeline.reset()


def _shard_set(tmp_path, tag: str, lost: list[int], shard_size: int = SHARD):
    """The plain reference's shard files of seeded data under `tag`, the
    `lost` ones removed, and a `.vif` that names the code: (base, the
    whole set's bytes)."""
    k, encode = REFERENCE[tag]
    want = encode(np.random.default_rng(shard_size + k).integers(
        0, 256, (k, shard_size), dtype=np.uint8))
    base = str(tmp_path / "7")
    for i, row in enumerate(want):
        if i not in lost:
            row.tofile(base + layout.to_ext(i))
    ec_files.write_vif(base, k * shard_size, codec=tag)
    return base, want


def _shard_bytes(base: str, shard: int) -> bytes:
    with open(base + layout.to_ext(shard), "rb") as f:
        return f.read()


def _rebuild_job() -> dict:
    return next(j for j in pipeline.jobs_snapshot()
                if j["kind"] == "ec_rebuild")


def _in_thread(fn, timeout: float = 120.0):
    """fn() on a thread of its own: its result, or its exception raised
    here; a call that hangs fails the test where it would stop the run."""
    box: dict = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "rebuild_ec_files hangs"
    if "error" in box:
        raise box.pop("error")
    return box["result"]


# ---- the bytes ----------------------------------------------------------

@pytest.mark.parametrize("tag, lost", CASES, ids=IDS)
def test_rebuilt_files_equal_the_plain_reference(tag, lost, tmp_path):
    base, want = _shard_set(tmp_path, tag, lost)
    stats: dict = {}
    assert _in_thread(lambda: ec_files.rebuild_ec_files(
        base, batch_size=BATCH, stats=stats)) == lost
    for i in range(len(want)):
        assert _shard_bytes(base, i) == \
            want[i].tobytes(), f"shard file {i}"
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert stats["mode"] == "pipelined" and stats["codec"] == tag
    assert 1 <= stats["inflight_max"] <= ec_files.PIPELINE_DEPTH
    # every stage once a batch, on whichever thread, to the one job
    stages = _rebuild_job()["stages"]
    assert {stages[s]["items"] for s in SEAM} == {BATCHES}
    assert stats["reconstruct_s"] == pytest.approx(
        sum(stats[s + "_s"] for s in SEAM), rel=1e-9)
    assert stats["overlap_frac"] == ec_files.overlap_fraction(stats)


# ---- how many are out at once -------------------------------------------

def _held_drain(monkeypatch, until: int, patience: float = 5.0):
    """The drain materialises nothing before `until` batches are enqueued,
    waiting up to `patience` seconds for each: -> the list of how many
    batches were out, enqueued and not yet materialised, after each
    enqueue."""
    enqueued = threading.Semaphore(0)
    out: list[int] = []
    lock = threading.Lock()
    counts = {"enqueued": 0, "materialised": 0}
    real_dispatch, real_rows = (ec_files._dispatch_reconstruct,
                                ec_files._materialize_rows)

    def dispatch_spy(*args, **kw):
        pending = real_dispatch(*args, **kw)
        with lock:
            counts["enqueued"] += 1
            out.append(counts["enqueued"] - counts["materialised"])
        enqueued.release()
        return pending

    def rows_spy(pending, **kw):
        if counts["materialised"] == 0:
            for _ in range(until):
                enqueued.acquire(timeout=patience)
        rebuilt = real_rows(pending, **kw)
        with lock:
            counts["materialised"] += 1
        return rebuilt

    monkeypatch.setattr(ec_files, "_dispatch_reconstruct", dispatch_spy)
    monkeypatch.setattr(ec_files, "_materialize_rows", rows_spy)
    return out


@pytest.mark.parametrize("tag, lost", [CASES[0], CASES[3]],
                         ids=[IDS[0], IDS[3]])
def test_a_device_codecs_batches_are_in_flight_together(
        tag, lost, tmp_path, monkeypatch):
    """The second batch is enqueued while the first is not back: a loop
    that waited for each batch would leave the drain waiting its five
    seconds out and read 1."""
    base, want = _shard_set(tmp_path, tag, lost)
    out = _held_drain(monkeypatch, until=2)
    stats: dict = {}
    _in_thread(lambda: ec_files.rebuild_ec_files(base, batch_size=BATCH,
                                                 stats=stats))
    assert 2 <= stats["inflight_max"] <= ec_files.PIPELINE_DEPTH
    assert stats["inflight_max"] == max(out)
    assert _shard_bytes(base, lost[0]) == \
        want[lost[0]].tobytes()


def test_never_more_than_the_depth_in_flight(tmp_path, monkeypatch):
    """A drain that does not start until PIPELINE_DEPTH batches are out:
    the caller stalls at the depth, and `inflight_max` says the depth."""
    base, want = _shard_set(tmp_path, "rs_10_4", [3])
    out = _held_drain(monkeypatch, until=ec_files.PIPELINE_DEPTH + 1,
                      patience=1.5)
    stats: dict = {}
    _in_thread(lambda: ec_files.rebuild_ec_files(base, batch_size=BATCH,
                                                 stats=stats))
    assert len(out) == BATCHES
    assert max(out) == stats["inflight_max"] == ec_files.PIPELINE_DEPTH
    assert stats["stall_s"] > 1.0  # the caller waited for a slot
    assert _shard_bytes(base, 3) == want[3].tobytes()


# ---- the ways a call ends early -----------------------------------------

class Boom(RuntimeError):
    pass


def _nth_call_raises(monkeypatch, module, name: str, nth: int):
    real = getattr(module, name)
    calls = []

    def spy(*args, **kw):
        calls.append(1)
        if len(calls) == nth:
            raise Boom(f"{name} call {nth}")
        return real(*args, **kw)

    monkeypatch.setattr(module, name, spy)


@pytest.mark.parametrize("how", ["cancel", "writer", "enqueue",
                                 "materialize"])
def test_a_call_that_ends_early_leaves_nothing(how, tmp_path, monkeypatch):
    """`cancel` after the second batch, a writer that fails mid-stream,
    the seam's first half raising on batch 3 and its second half on batch
    2: the call raises what ended it without a hang, no `.tmp` is left,
    no shard is renamed into place, and once the exception is dropped no
    view of a map is alive (the maps close)."""
    lost = [3]
    base, _ = _shard_set(tmp_path, "rs_10_4", lost)
    maps = []
    real_map = ec_files._map_lazy

    def map_spy(fd):
        maps.append(real_map(fd))
        return maps[-1]

    monkeypatch.setattr(ec_files, "_map_lazy", map_spy)
    kwargs: dict = {}
    if how == "cancel":
        seen = []
        kwargs["progress"] = seen.append
        kwargs["cancel"] = lambda: len(seen) >= 2
        raises = ec_files.EncodeCancelled
    elif how == "writer":
        _nth_call_raises(monkeypatch, ec_files, "_pwritev_all", 2)
        raises = Boom
    elif how == "enqueue":
        _nth_call_raises(monkeypatch, ec_files, "_dispatch_reconstruct", 3)
        raises = Boom
    else:
        _nth_call_raises(monkeypatch, ec_files, "_materialize_rows", 2)
        raises = Boom
    with pytest.raises(raises):
        _in_thread(lambda: ec_files.rebuild_ec_files(
            base, batch_size=BATCH, **kwargs))
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not os.path.exists(base + layout.to_ext(lost[0]))
    assert _rebuild_job()["state"] == "failed"
    assert not [t for t in threading.enumerate()
                if t.name == "ec-rebuild-drain"]
    assert len(maps) == 10
    gc.collect()  # the traceback's frames held the raising batch's rows
    for mm in maps:
        mm.close()  # BufferError while a view of it is alive


# ---- the host codec on the one pipeline -------------------------------------

def test_the_native_host_codec_is_host_serial(tmp_path, monkeypatch):
    """The native host codec rides the pipeline every codec rides: its
    batches are computed at the enqueue, booked as the seam's `dispatch`,
    and written by the drain as a device codec's are."""
    from seaweedfs_tpu import native
    if not native.available():
        pytest.skip("no native codec here")
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "cpp")
    base, want = _shard_set(tmp_path, "rs_10_4", [3, 12])
    stats: dict = {}
    assert ec_files.rebuild_ec_files(base, batch_size=BATCH,
                                     stats=stats) == [3, 12]
    assert stats["mode"] == "pipelined" and stats["inflight_max"] >= 1
    assert stats["dispatch_s"] > 0 and stats["unstage_s"] > 0
    assert "h2d_s" not in stats and "device_wait_s" not in stats
    for i in (3, 12):
        assert _shard_bytes(base, i) == \
            want[i].tobytes()


# ---- the seam's pair ------------------------------------------------------

@pytest.mark.parametrize("kind", ["jax", "numpy", "cpp"])
def test_the_pair_in_a_row_is_reconstruct_batch(kind):
    """A device codec's first half gives a handle that holds the rows it
    put and books `h2d` and `dispatch` alone; a host codec and the numpy
    reference compute there and hand their dict through the second."""
    if kind == "cpp":
        from seaweedfs_tpu import native
        if not native.available():
            pytest.skip("no native codec here")
    codec = codecs.resolve("rs_10_4", kind)
    shards = rs.get_code(10, 4).encode_numpy(
        np.random.default_rng(35).integers(0, 256, (10, 3000),
                                           dtype=np.uint8))
    ids, wanted = [0, 1, 2, 4, 5, 6, 7, 8, 9, 13], [3, 12]
    rows = [shards[i] for i in ids]
    job = pipeline.PipelineJob("seam", register=False)
    pending = dispatch.dispatch_reconstruct(codec, rows, ids, wanted,
                                            job=job)
    if kind == "jax":
        assert not isinstance(pending, dict) and pending.held[0] is rows
        assert set(job.stats) == {"h2d_s", "dispatch_s", "rows_staged"}
    else:
        assert isinstance(pending, dict)
        assert set(job.stats) == {"dispatch_s"}
    got = dispatch.materialize_rows(pending, job=job)
    assert ("device_wait_s" in job.stats) == (kind == "jax")
    both = dispatch.reconstruct_batch(codec, rows, ids, wanted)
    assert sorted(got) == sorted(both) == wanted
    for w in wanted:
        assert np.array_equal(got[w], shards[w])
        assert np.array_equal(both[w], shards[w])


# ---- two threads, one job ---------------------------------------------------

def test_many_small_batches_under_a_short_switch_interval(tmp_path):
    """Sixty-odd batches with the interpreter switching threads every few
    microseconds: the bytes, a stage entry a batch on either thread (a
    lost update of the job's books would show), the sum identity, and the
    depth."""
    lost = [0, 13]
    base, want = _shard_set(tmp_path, "rs_10_4", lost, shard_size=33_000)
    batches = -(-33_000 // 512)
    stats: dict = {}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        _in_thread(lambda: ec_files.rebuild_ec_files(base, batch_size=512,
                                                     stats=stats))
    finally:
        sys.setswitchinterval(interval)
    for i in lost:
        assert _shard_bytes(base, i) == \
            want[i].tobytes()
    stages = _rebuild_job()["stages"]
    assert {stages[s]["items"] for s in SEAM} == {batches}
    assert stats["reconstruct_s"] == pytest.approx(
        sum(stats[s + "_s"] for s in SEAM), rel=1e-9)
    assert 1 <= stats["inflight_max"] <= ec_files.PIPELINE_DEPTH
