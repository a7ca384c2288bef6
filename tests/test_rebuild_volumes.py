"""A rebuilder's backlog in one call (`ec_files.rebuild_ec_volumes`, the
list form of `/admin/ec/rebuild`): every rebuilt file against the plain
references (`models/rs.py`, `models/lrc.py`) on seeded random volumes at
small sizes, where a batch is a few KiB so that each volume has one to
three batches and the pipeline crosses volume boundaries with batches in
flight; the skips; what a cancel and a failed writer leave, volume by
volume and file by file; and the single form's answer, unchanged.  On the
CPU device codec (the XLA shell) and on the native host codec, both
through the dispatch seam's one pipeline."""

import asyncio
import gc
import json
import os
import threading
import types
import weakref

import numpy as np
import pytest

from seaweedfs_tpu.models import lrc as lrc_ref
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import dispatch, pallas_gf
from seaweedfs_tpu.stats import pipeline, profile
from seaweedfs_tpu.storage.ec import ec_files, layout

BATCH = 4096
# a 4 KiB and a 2 KiB batch a volume: the 16 MiB and the 10 MiB batch of a
# 26 MiB shard file (a 256 MiB volume under RS(10,4)) at 1/4096
TWO_BATCHES = BATCH + 2048
REFERENCE = {"rs_10_4": (10, rs.get_code(10, 4).encode_numpy),
             "lrc_12_2_2": (12, lrc_ref.encode)}
NODE7_LOST = [3, 10]  # a server of seven, after ec.encode's round-robin


@pytest.fixture(params=["jax", "cpp"], ids=["device_codec", "native_host"])
def codec_kind(request, monkeypatch):
    if request.param == "cpp":
        from seaweedfs_tpu import native
        if not native.available():
            pytest.skip("no native codec here")
    monkeypatch.setenv("WEEDTPU_EC_CODEC", request.param)
    pipeline.reset()
    yield request.param
    pipeline.reset()


def _volume(tmp_path, vid: int, tag: str, lost: list[int],
            shard_size: int = TWO_BATCHES, name: str | None = None):
    """The plain reference's shard files of seeded data under `tag`, the
    `lost` ones removed, and a `.vif` naming the code: (base, every file's
    bytes)."""
    k, encode = REFERENCE[tag]
    want = encode(np.random.default_rng([vid, shard_size]).integers(
        0, 256, (k, shard_size), dtype=np.uint8))
    base = str(tmp_path / (name or f"bench_{vid}"))
    for i, row in enumerate(want):
        if i not in lost:
            row.tofile(base + layout.to_ext(i))
    ec_files.write_vif(base, k * shard_size, codec=tag)
    return base, [row.tobytes() for row in want]


def _files(base: str, n: int) -> list:
    """Every shard file's bytes, None where it is absent."""
    out = []
    for i in range(n):
        p = base + layout.to_ext(i)
        if os.path.exists(p):
            with open(p, "rb") as f:
                out.append(f.read())
        else:
            out.append(None)
    return out


def _in_thread(fn, timeout: float = 120.0):
    box: dict = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "the rebuild hangs"
    if "error" in box:
        raise box.pop("error")
    return box["result"]


def _no_leftovers(tmp_path) -> None:
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(("ec-writer", "ec-rebuild-drain"))]


# ---- the bytes ------------------------------------------------------------

BACKLOGS = {
    # eight volumes of one lost server of seven
    "node7": [("rs_10_4", NODE7_LOST, TWO_BATCHES)] * 8,
    "unequal_sizes": [("rs_10_4", NODE7_LOST, s) for s in
                      (512, BATCH, 2 * BATCH + 704, TWO_BATCHES, 3 * BATCH)],
    "mixed_losses": [("rs_10_4", [3], TWO_BATCHES),
                     ("rs_10_4", [3, 10], BATCH),
                     ("rs_10_4", [0, 5, 11, 13], 2 * BATCH + 704),
                     ("rs_10_4", [13], TWO_BATCHES)],
    "rs_and_lrc": [("rs_10_4", NODE7_LOST, TWO_BATCHES),
                   ("lrc_12_2_2", [3], 2 * BATCH + 704),
                   ("lrc_12_2_2", [0, 6], TWO_BATCHES),
                   ("rs_10_4", [0, 5, 11, 13], BATCH)],
}


@pytest.mark.parametrize("backlog", sorted(BACKLOGS))
def test_every_rebuilt_file_equals_the_plain_reference(
        backlog, codec_kind, tmp_path):
    vols = [_volume(tmp_path, vid, tag, lost, size)
            for vid, (tag, lost, size) in enumerate(BACKLOGS[backlog], 1)]
    stats: dict = {}
    report = _in_thread(lambda: ec_files.rebuild_ec_volumes(
        [b for b, _ in vols], batch_size=BATCH, stats=stats))
    assert report["skipped"] == {}
    assert report["rebuilt"] == {
        base: lost for (base, _), (_, lost, _) in
        zip(vols, BACKLOGS[backlog])}
    for base, want in vols:
        assert _files(base, len(want)) == want, base
    _no_leftovers(tmp_path)
    spec = BACKLOGS[backlog]
    assert stats["volumes"] == len(spec)
    assert stats["lost_rows"] == max(len(lost) for _, lost, _ in spec)
    assert stats["codec"] == ",".join(sorted({t for t, _, _ in spec}))
    assert stats["mode"] == "pipelined"
    batches = sum(-(-size // BATCH) for _, _, size in spec)
    assert stats["spans_mapped"] == sum(
        -(-size // BATCH) * (6 if tag == "lrc_12_2_2" and len(lost) == 1
                             else REFERENCE[tag][0])
        for tag, lost, size in spec)
    assert 0.0 <= stats["boundaries_in_flight"] <= 1.0
    job = next(j for j in pipeline.jobs_snapshot()
               if j["kind"] == "ec_rebuild")
    assert job["state"] == "done"
    assert job["stages"]["unstage"]["items"] == batches
    assert 1 <= stats["inflight_max"] <= ec_files.PIPELINE_DEPTH


# the Pallas shell at a tile of `pallas_gf.IN_PLACE_QUANTUM`, which no
# other test builds programs for: a batch of eight tiles (a bucket), and
# last batches of three and five, which are none
TILE = 4096
WIDE_BATCH = 8 * TILE
TAILS = (3 * TILE, 5 * TILE)


def test_short_last_batches_go_up_at_their_own_widths(tmp_path, monkeypatch):
    """One list call over volumes whose last batches are three or five
    whole tiles, or none: every tail goes up from the maps at its own
    width (`narrow`, nothing staged), the programs built are the whole
    batch's and one a distinct tail, a second call builds none, and no
    map outlives either call."""
    ec_files._get_codec("jax")  # notes a JAX backend: counting is on
    codec = pallas_gf.PallasRSCodec(rs.get_code(10, 4), tile=TILE,
                                    interpret=True)
    monkeypatch.setattr(ec_files, "_get_codec",
                        lambda kind=None, tag=None: codec)
    monkeypatch.setattr(dispatch, "ROW_PUTS_FROM", 2 * TILE)
    maps = []
    real_map = ec_files._map_lazy

    def map_spy(fd):
        mm = real_map(fd)
        maps.append(weakref.ref(mm))
        return mm

    monkeypatch.setattr(ec_files, "_map_lazy", map_spy)
    sizes = [WIDE_BATCH + TAILS[0], WIDE_BATCH + TAILS[1],
             2 * WIDE_BATCH + TAILS[0], WIDE_BATCH]

    def programs() -> int:
        return profile.compiles_snapshot().get(
            "reconstruct", {}).get("count", 0)

    built = []
    for call in range(2):
        vols = [_volume(tmp_path, vid, "rs_10_4", NODE7_LOST, size,
                        name=f"call{call}_{vid}")
                for vid, size in enumerate(sizes, 1)]
        stats: dict = {}
        p0 = programs()
        report = _in_thread(lambda: ec_files.rebuild_ec_volumes(
            [b for b, _ in vols], batch_size=WIDE_BATCH, stats=stats))
        built.append(programs() - p0)
        assert len(report["rebuilt"]) == len(sizes)
        for base, want in vols:
            assert _files(base, 14) == want, base
        assert (stats["narrow"], stats["rows_staged"]) == (3, 0)
        assert stats["in_place"] == 8  # every batch, read where it lies
        _no_leftovers(tmp_path)
        gc.collect()
        assert maps and all(ref() is None or ref().closed for ref in maps)
    assert built == [1 + len(TAILS), 0]


def test_the_pipeline_crosses_every_boundary_with_a_batch_out(
        tmp_path, monkeypatch):
    """The drain materialises a batch only once the next one is enqueued
    (or the walk has ended): a walk that drained between volumes would
    wait its patience out at the first boundary and read below 1.0."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    vols = [_volume(tmp_path, vid, "rs_10_4", NODE7_LOST)
            for vid in range(1, 9)]
    cond = threading.Condition()
    counts = {"enqueued": 0, "materialised": 0}
    real_dispatch, real_rows = (ec_files._dispatch_reconstruct,
                                ec_files._materialize_rows)

    def dispatch_spy(*args, **kw):
        pending = real_dispatch(*args, **kw)
        with cond:
            counts["enqueued"] += 1
            cond.notify_all()
        return pending

    def rows_spy(pending, **kw):
        with cond:  # the next batch is out, or the walk has ended
            cond.wait_for(lambda: counts["enqueued"] >
                          counts["materialised"] + 1, timeout=2.0)
            counts["materialised"] += 1
        return real_rows(pending, **kw)

    monkeypatch.setattr(ec_files, "_dispatch_reconstruct", dispatch_spy)
    monkeypatch.setattr(ec_files, "_materialize_rows", rows_spy)
    stats: dict = {}
    report = _in_thread(lambda: ec_files.rebuild_ec_volumes(
        [b for b, _ in vols], batch_size=BATCH, stats=stats))
    assert len(report["rebuilt"]) == 8
    assert stats["boundaries_in_flight"] == 1.0
    assert stats["inflight_max"] >= 2
    for base, want in vols:
        assert _files(base, 14) == want


def test_many_small_volumes_under_a_short_switch_interval(tmp_path,
                                                          monkeypatch):
    """Twenty volumes of one to three 512-byte batches each, with the
    interpreter switching threads every few microseconds: the bytes, a
    stage entry a batch on either thread (a lost update of the job's books
    or of a volume's batch counts would show), and every volume committed
    once."""
    import sys
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    pipeline.reset()
    sizes = {vid: 512 * (1 + vid % 3) - 64 * (vid % 2) for vid in range(1, 21)}
    vols = [_volume(tmp_path, vid, "rs_10_4", NODE7_LOST, shard_size=size)
            for vid, size in sizes.items()]
    batches = sum(-(-size // 512) for size in sizes.values())
    stats: dict = {}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        report = _in_thread(lambda: ec_files.rebuild_ec_volumes(
            [b for b, _ in vols], batch_size=512, stats=stats))
    finally:
        sys.setswitchinterval(interval)
    assert len(report["rebuilt"]) == 20
    for base, want in vols:
        assert _files(base, 14) == want, base
    stages = next(j for j in pipeline.jobs_snapshot()
                  if j["kind"] == "ec_rebuild")["stages"]
    assert {stages[s]["items"] for s in ec_files.REBUILD_SUMS[
        "reconstruct"]} == {batches}
    assert stats["spans_mapped"] == 10 * batches
    _no_leftovers(tmp_path)


def test_skips_leave_their_files_and_the_others_go_on(codec_kind, tmp_path):
    whole, whole_want = _volume(tmp_path, 1, "rs_10_4", [])
    short, _ = _volume(tmp_path, 2, "rs_10_4", [0, 1, 2, 3, 4])
    good, good_want = _volume(tmp_path, 3, "rs_10_4", NODE7_LOST)
    short_before = _files(short, 14)
    stats: dict = {}
    report = _in_thread(lambda: ec_files.rebuild_ec_volumes(
        [whole, short, good], batch_size=BATCH, stats=stats))
    assert report["skipped"] == {
        whole: ec_files.NOTHING_MISSING,
        short: "need >= 10 shards to rebuild, have 9"}
    assert report["rebuilt"] == {good: NODE7_LOST}
    assert _files(whole, 14) == whole_want
    assert _files(short, 14) == short_before
    assert _files(good, 14) == good_want
    assert stats["volumes"] == 1
    _no_leftovers(tmp_path)


def test_a_call_of_skips_alone_opens_nothing(tmp_path):
    whole, _ = _volume(tmp_path, 1, "rs_10_4", [])
    stats: dict = {}
    report = ec_files.rebuild_ec_volumes([whole], stats=stats)
    assert report == {"rebuilt": {}, "skipped": {
        whole: ec_files.NOTHING_MISSING}}
    assert stats == {}


# ---- what an early end leaves --------------------------------------------

def _eight(tmp_path):
    vols = [_volume(tmp_path, vid, "rs_10_4", NODE7_LOST)
            for vid in range(1, 9)]
    return vols, [_files(b, 14) for b, _ in vols]


def _held_to(vols, before, committed: int) -> None:
    """The first `committed` volumes whole and equal to the reference, every
    other one exactly as it was before the call."""
    for j, ((base, want), was) in enumerate(zip(vols, before)):
        assert _files(base, 14) == (want if j < committed else was), base


def test_a_cancel_after_the_third_volume(codec_kind, tmp_path):
    """Cancelled once the third volume's last batch is out: the three are
    committed, the fourth (opened, in flight) and those after it are as
    they were, and the report says which are which."""
    vols, before = _eight(tmp_path)
    seen: list = []
    with pytest.raises(ec_files.EncodeCancelled) as raised:
        _in_thread(lambda: ec_files.rebuild_ec_volumes(
            [b for b, _ in vols], batch_size=BATCH, progress=seen.append,
            cancel=lambda: len(seen) >= 3 * 2))
    assert sorted(raised.value.report["rebuilt"]) == sorted(
        b for b, _ in vols[:3])
    _held_to(vols, before, 3)
    _no_leftovers(tmp_path)
    job = next(j for j in pipeline.jobs_snapshot()
               if j["kind"] == "ec_rebuild")
    assert job["state"] == "failed"


@pytest.mark.parametrize("when", [1, 2, "commit"],
                         ids=["first_write", "second_write", "commit"])
def test_a_writer_failing_in_volume_five(when, codec_kind, tmp_path,
                                         monkeypatch):
    """Volume 5's writer fails on its first or second write, or its commit
    fails (mid-walk or at the end, whichever comes): volumes 1-4 are
    committed, 5-8 untouched, however far the walk had gone past volume 5,
    and the call raises the error."""
    vols, before = _eight(tmp_path)
    real = ec_files._pwritev_all
    hits: list = []

    class Boom(OSError):
        pass

    # through the pipeline, volume 5's writes wait until volume 6's last
    # batch (the walk's twelfth) is out, so that volume 5's commit fails
    # mid-walk with volume 6 still to come whole
    sixth_out = threading.Event()
    if when != "commit":
        sixth_out.set()
    real_dispatch = ec_files._dispatch_reconstruct

    def dispatch(*args, **kw):
        pending = real_dispatch(*args, **kw)
        if kw.get("unit") == 11:
            sixth_out.set()
        return pending

    def pwritev(fd, bufs, off):
        if "bench_5.ec" in os.readlink(f"/proc/self/fd/{fd}"):
            hits.append(off)
            if len(hits) == when:
                raise Boom("disk gone under volume 5")
            sixth_out.wait(10)
        return real(fd, bufs, off)

    real_commit = ec_files._RebuildVolume.commit

    def commit(vol, pjob):
        if vol.base.endswith("bench_5"):
            raise Boom("rename refused under volume 5")
        return real_commit(vol, pjob)

    monkeypatch.setattr(ec_files, "_pwritev_all", pwritev)
    monkeypatch.setattr(ec_files, "_dispatch_reconstruct", dispatch)
    if when == "commit":
        monkeypatch.setattr(ec_files._RebuildVolume, "commit", commit)
    with pytest.raises(Boom) as raised:
        _in_thread(lambda: ec_files.rebuild_ec_volumes(
            [b for b, _ in vols], batch_size=BATCH))
    assert sorted(raised.value.report["rebuilt"]) == sorted(
        b for b, _ in vols[:4])
    _held_to(vols, before, 4)
    _no_leftovers(tmp_path)


def test_the_one_volume_case_raises_where_it_raised(tmp_path):
    """`rebuild_ec_files` is the list's case of one: [] where nothing is
    missing, a ValueError below k survivors, before anything is opened."""
    whole, _ = _volume(tmp_path, 1, "rs_10_4", [])
    short, _ = _volume(tmp_path, 2, "rs_10_4", [0, 1, 2, 3, 4])
    assert ec_files.rebuild_ec_files(whole) == []
    with pytest.raises(ValueError, match="need >= 10 shards"):
        ec_files.rebuild_ec_files(short)
    assert not pipeline.jobs_snapshot()


# ---- the endpoint ------------------------------------------------------------

def _call(handler, body: dict, limit_s: float = 120.0):
    async def _json():
        return body
    box: dict = {}

    def run():
        try:
            box["resp"] = asyncio.run(
                handler(types.SimpleNamespace(json=_json, query=body)))
        except BaseException as e:  # shown by the assert below
            box["error"] = e
    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(limit_s)
    assert not t.is_alive(), f"no answer within {limit_s} s"
    assert "error" not in box, box.get("error")
    return box["resp"].status, json.loads(box["resp"].body)


@pytest.fixture
def server(tmp_path, monkeypatch):
    """A volume server (never started: handlers are called directly) on a
    directory of shard sets with no `.dat`, as a rebuilder holds them after
    `ec.rebuild` copied the survivors over."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    pipeline.reset()
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18996)

    async def no_beat():
        return None
    monkeypatch.setattr(vs, "_heartbeat_once", no_beat)
    yield vs
    vs.store.close()
    pipeline.reset()


def test_the_list_form_answers_every_volume(server, tmp_path):
    vols = {vid: _volume(tmp_path, vid, tag, lost, name=str(vid))
            for vid, tag, lost in [(11, "rs_10_4", NODE7_LOST),
                                   (12, "lrc_12_2_2", [3]),
                                   (13, "rs_10_4", []),
                                   (14, "rs_10_4", [1, 2, 3, 4, 5])]}
    status, out = _call(server.handle_ec_rebuild,
                        {"volumes": [11, 12, 13, 14, 15, 11]})
    assert (status, out) == (200, {
        "volumes": [11, 12, 13, 14, 15],
        "rebuilt": {"11": NODE7_LOST, "12": [3]},
        "shard_files": 3,
        "skipped": {"13": ec_files.NOTHING_MISSING,
                    "14": "need >= 10 shards to rebuild, have 9",
                    "15": "no shards here"}})
    for vid in (11, 12):
        base, want = vols[vid]
        assert _files(base, len(want)) == want
    # one job under every vid it works on
    for vid in (11, 12, 13, 14):
        status, job = _call(server.handle_ec_progress,
                            {"volumeId": str(vid)})
        assert status == 200 and job["kind"] == "rebuild"
        assert job["volumes"] == [11, 12, 13, 14]
        assert job["stages"]["volumes"] == 2
        assert job["stages"]["lost_rows"] == 2
        assert job["state"] == "done"


def test_the_list_form_cancelled_on_any_vid(server, tmp_path, monkeypatch):
    """/admin/ec/cancel on the last listed vid while the third volume's
    last batch goes up: 409, the first three committed, the rest untouched
    and named."""
    vids = list(range(21, 29))
    vols = [_volume(tmp_path, vid, "rs_10_4", NODE7_LOST, name=str(vid))
            for vid in vids]
    before = [_files(b, 14) for b, _ in vols]
    real = ec_files.rebuild_ec_volumes

    def spy(bases, progress=None, **kw):
        seen: list = []

        def counted(n):
            progress(n)
            seen.append(n)
            if len(seen) == 3 * 2:
                assert _call(server.handle_ec_cancel,
                             {"volume": vids[-1]})[0] == 200
        return real(bases, batch_size=BATCH, progress=counted, **kw)

    monkeypatch.setattr(ec_files, "rebuild_ec_volumes", spy)
    status, out = _call(server.handle_ec_rebuild, {"volumes": vids})
    assert status == 409
    assert out == {"error": "cancelled", "volumes": vids,
                   "rebuilt": {str(v): NODE7_LOST for v in vids[:3]},
                   "shard_files": 6, "skipped": {},
                   "untouched": vids[3:]}
    _held_to(vols, before, 3)
    assert _call(server.handle_ec_progress,
                 {"volumeId": str(vids[0])})[1]["state"] == "cancelled"


def test_the_single_form_answers_as_before(server, tmp_path):
    base, want = _volume(tmp_path, 31, "rs_10_4", NODE7_LOST, name="31")
    status, out = _call(server.handle_ec_rebuild, {"volume": 31})
    assert (status, out) == (200, {"rebuilt": NODE7_LOST})
    assert _files(base, 14) == want
    status, job = _call(server.handle_ec_progress, {"volumeId": "31"})
    assert job["kind"] == "rebuild" and "volumes" not in job
    assert job["stages"]["volumes"] == 1


@pytest.mark.parametrize("body", [{"volumes": []}, {"volumes": ["x"]}],
                         ids=["empty", "not_an_id"])
def test_a_bad_list_is_a_400(server, body):
    status, out = _call(server.handle_ec_rebuild, body)
    assert status == 400 and "error" in out


# ---- the shell -------------------------------------------------------------

class _Cluster:
    """What `ec.rebuild` asks of a cluster, recorded: four volumes over two
    servers a and b (a holds most of 1 and 2, b most of 3; 4 has nine
    shards left), and the rebuilders' answers: `answers[url]` is the
    answer of the list call to `url`, or the exception it raises; `jobs`
    what /admin/ec/progress says under a vid, call after call."""

    def __init__(self, answers: dict | None = None, jobs: dict | None = None):
        a, b = "a:8080", "b:8080"
        self.locs = {
            1: {s: [a] for s in range(14) if s not in (3, 10)},
            2: {s: [a] if s < 9 else [b] for s in range(14) if s != 13},
            3: {s: [b] if s < 11 else [a] for s in range(14) if s != 0},
            4: {s: [a] for s in range(9)},
        }
        self.answers = answers or {
            a: _answer([1, 2], {"1": [3, 10]},
                       {"2": "need >= 10 shards to rebuild, have 9"}),
            b: _answer([3], {"3": [0]})}
        self.jobs = jobs or {}
        self.calls: list = []

    def topology(self):
        return {"nodes": {"a": {"ec_shards": ["1", "2", "3", "4"]},
                          "b": {"ec_shards": ["2", "3"]}}}

    def master_get(self, path):
        return {"volumes": {}}

    def master_get_raw(self, url, path, volumeId):
        self.calls.append((url, path, {"volume": int(volumeId)}))
        seen = self.jobs.get((url, int(volumeId)))
        if not seen:
            raise RuntimeError(f"{url}{path}: no encode job")
        return seen.pop(0) if len(seen) > 1 else seen[0]

    def ec_shard_locations(self, vid):
        return self.locs[vid]

    def vs_post(self, url, path, body, **kw):
        self.calls.append((url, path, body))
        if path == "/admin/ec/rebuild":
            assert kw == {"timeout": 600.0, "answer_errors": True}
            answer = self.answers[url]
            if isinstance(answer, BaseException):
                raise answer
            return answer
        return {}


def _answer(vids, rebuilt, skipped=None, **extra) -> dict:
    return {"volumes": vids, "rebuilt": rebuilt,
            "shard_files": sum(map(len, rebuilt.values())),
            "skipped": skipped or {}, **extra}


def _rebuild_all(env) -> tuple[list, str]:
    """Run `ec.rebuild`'s body: (its exception or None, its lines)."""
    import io
    from seaweedfs_tpu.shell.commands import _ec_rebuild_all
    out = io.StringIO()
    try:
        _ec_rebuild_all(env, out)
        raised = None
    except RuntimeError as e:
        raised = e
    return raised, out.getvalue().splitlines()


def _posts(env, path: str) -> dict:
    return {(url, body["volume"]): body.get("shards")
            for url, p, body in env.calls if p == path}


def test_the_shell_makes_one_list_call_per_rebuilder():
    env = _Cluster()
    raised, lines = _rebuild_all(env)
    assert raised is None
    rebuilds = [(url, body) for url, path, body in env.calls
                if path == "/admin/ec/rebuild"]
    assert rebuilds == [("a:8080", {"volumes": [1, 2]}),
                        ("b:8080", {"volumes": [3]})]
    # survivors copied before the call, borrowed shards deleted and every
    # volume rebuilt mounted after it
    order = [(url, path, body.get("volume")) for url, path, body in env.calls]
    first_rebuild = order.index(("a:8080", "/admin/ec/rebuild", None))
    assert all(p == "/admin/ec/copy" for _, p, _ in order[:first_rebuild])
    assert ("a:8080", "/admin/ec/copy", 2) in order
    assert _posts(env, "/admin/ec/delete_shards") == {
        ("a:8080", 1): [], ("a:8080", 2): [9, 10, 11, 12],
        ("b:8080", 3): [11, 12, 13]}
    assert set(_posts(env, "/admin/ec/mount")) == {("a:8080", 1),
                                                   ("b:8080", 3)}
    assert lines == [
        "volume 4: only 9 shards left, cannot rebuild",
        "volume 1: rebuilt [3, 10] on a:8080",
        "volume 2: not rebuilt on a:8080: need >= 10 shards to rebuild, "
        "have 9",
        "volume 3: rebuilt [0] on b:8080"]


def test_the_shell_mounts_what_a_failed_call_committed_and_goes_on():
    """Rebuilder a's call fails on its second volume (a 500 whose answer
    says volume 1 committed): volume 1 is mounted, both volumes' borrowed
    shards are deleted, rebuilder b is still called, and the command fails
    naming volume 2."""
    env = _Cluster(answers={
        "a:8080": _answer([1, 2], {"1": [3, 10]}, error="disk gone",
                          untouched=[2]),
        "b:8080": _answer([3], {"3": [0]})})
    raised, lines = _rebuild_all(env)
    assert "volumes [2] not rebuilt" in str(raised)
    assert set(_posts(env, "/admin/ec/mount")) == {("a:8080", 1),
                                                   ("b:8080", 3)}
    assert set(_posts(env, "/admin/ec/delete_shards")) == {
        ("a:8080", 1), ("a:8080", 2), ("b:8080", 3)}
    assert lines[1:] == ["volume 1: rebuilt [3, 10] on a:8080",
                         "volume 2: not rebuilt on a:8080: disk gone",
                         "volume 3: rebuilt [0] on b:8080"]


def test_the_shell_follows_a_call_that_outlasts_its_timeout(monkeypatch):
    """Rebuilder a gives no answer in time: its job is followed on
    /admin/ec/progress (under the first listed vid it holds) until it
    ends, and its answer there is used as the call's."""
    from seaweedfs_tpu.shell import commands
    monkeypatch.setattr(commands, "REBUILD_POLL_S", 0.0)
    done = _answer([1, 2], {"1": [3, 10], "2": [13]})
    job = {"kind": "rebuild", "volumes": [1, 2]}
    env = _Cluster(
        answers={"a:8080": TimeoutError("timed out"),
                 "b:8080": _answer([3], {"3": [0]})},
        jobs={("a:8080", 1): [dict(job, state="running"),
                              dict(job, state="running"),
                              dict(job, state="done", answer=done)]})
    raised, lines = _rebuild_all(env)
    assert raised is None
    assert sum(p == "/admin/ec/progress" for _, p, _ in env.calls) == 3
    assert set(_posts(env, "/admin/ec/mount")) == {
        ("a:8080", 1), ("a:8080", 2), ("b:8080", 3)}
    assert lines[1:] == ["a:8080: no answer in 600 s, following its job",
                         "volume 1: rebuilt [3, 10] on a:8080",
                         "volume 2: rebuilt [13] on a:8080",
                         "volume 3: rebuilt [0] on b:8080"]


@pytest.mark.parametrize("case", ["error_answer", "error_raised", "timeout"])
def test_the_shell_reads_an_error_answer_and_times_out(case):
    """CommandEnv's request against a real HTTP server: a 500 whose answer
    is a JSON object with `error` is returned where the caller asks for it
    (and raised as RuntimeError where not), and a server that does not
    answer in time raises TimeoutError, which `ec.rebuild` follows up."""
    import http.server
    import time
    from seaweedfs_tpu.shell.commands import CommandEnv

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            if case == "timeout":
                time.sleep(1.0)
            body = json.dumps(_answer([1], {}, error="disk gone",
                                      untouched=[1])).encode()
            try:
                self.send_response(500)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except OSError:  # the client that timed out has gone
                pass

        def log_message(self, *args):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"127.0.0.1:{srv.server_address[1]}"
    env = CommandEnv(url)
    try:
        if case == "error_answer":
            got = env.vs_post(url, "/admin/ec/rebuild", {"volumes": [1]},
                              answer_errors=True)
            assert got["error"] == "disk gone" and got["untouched"] == [1]
        elif case == "error_raised":
            with pytest.raises(RuntimeError, match="disk gone"):
                env.vs_post(url, "/admin/ec/rebuild", {"volumes": [1]})
        else:
            with pytest.raises(TimeoutError):
                env.vs_post(url, "/admin/ec/rebuild", {"volumes": [1]},
                            timeout=0.2, answer_errors=True)
    finally:
        srv.shutdown()
        srv.server_close()
