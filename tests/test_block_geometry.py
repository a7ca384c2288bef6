"""A volume's block geometry is the volume's: given at encode, recorded in
the `.vif`, read back by mount, read, rebuild and decode.

Seeded volumes at a small size whose layout has both kinds of row (64 KiB
large blocks, 4 KiB small ones, a 32 KiB batch that cuts a large block in
two columns), under every registered family, on the XLA device codec and on
a host codec: (a) the files against the models' plain references cut with
the same blocks, (b) the served path from the request's two fields to reads
of a set mounted from its `.vif` alone, which must fail where the record is
ignored, (c) rebuild and decode with no block size handed in, (d) the fleet
stream, (e) a `.vif` from before the record and a request with a bad pair,
(f) what the job and its stage events say of the two kinds of unit."""

import json
import os

import jax
import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.ops import codecs, fleet_convert
from seaweedfs_tpu.stats import pipeline
from seaweedfs_tpu.stats.profile import KERNELS
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume
from tests.test_fleet_convert import TAGS, _files_of, _model_encode
from tests.test_lrc_azure import _call, _no_leftovers
from tests.test_stage_tracing import _annotations

LARGE, SMALL, BATCH = 65536, 4096, 32768
# the XLA shell as the device codec; the native host codec where this host
# has the library, the numpy reference behind the seam where not
KINDS = ["jax", "cpp" if native.available() else "numpy"]
# .dat sizes by k.  The encode loop's `>` is strict: a .dat of exactly one
# large row's bytes has no large row
DAT_SIZES = {
    "exactly_one_large_row": lambda k: k * LARGE,
    "one_byte_more": lambda k: k * LARGE + 1,
    "two_large_rows_and_whole_small_rows": lambda k: 2 * k * LARGE
    + 5 * k * SMALL,
    "tail_ends_inside_a_small_row": lambda k: 2 * k * LARGE + 3 * k * SMALL
    + 777,
}
LARGE_ROWS = {"exactly_one_large_row": 0, "one_byte_more": 1,
              "two_large_rows_and_whole_small_rows": 2,
              "tail_ends_inside_a_small_row": 2}


@pytest.fixture(autouse=True)
def _clean_observatory(monkeypatch):
    monkeypatch.delenv("WEEDTPU_CONVERT_CODEC", raising=False)
    pipeline.reset()
    KERNELS.reset()
    yield
    pipeline.reset()


def model_files(tag: str, raw: bytes, large: int, small: int) -> list[bytes]:
    """The shard files `raw` must convert to under blocks of `large` and
    `small` bytes: upstream's row-major striping k wide, by hand, under
    the model's reference encode."""
    spec = codecs.parse_tag(tag)
    files = [bytearray() for _ in range(spec.k)]
    at = 0
    while len(raw) - at > spec.k * large:
        for j in range(spec.k):
            files[j] += raw[at:at + large]
            at += large
    while at < len(raw):
        for j in range(spec.k):
            files[j] += raw[at:at + small].ljust(small, b"\0")
            at += small
    return [f.tobytes() for f in _model_encode(tag)(np.array(
        [np.frombuffer(bytes(f), dtype=np.uint8) for f in files]))]


def _seeded_dat(tmp_path, name: str, size: int, seed: int = 38):
    base = str(tmp_path / name)
    raw = np.random.default_rng(seed).bytes(size)
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    return base, raw


# -- (a) the files, against the models' references -------------------------

@pytest.mark.parametrize("shape", sorted(DAT_SIZES))
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("tag", TAGS)
def test_files_equal_the_reference_cut_with_the_same_blocks(
        tmp_path, monkeypatch, tag, kind, shape):
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    spec = codecs.parse_tag(tag)
    base, raw = _seeded_dat(tmp_path, "5", DAT_SIZES[shape](spec.k))
    stats: dict = {}
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=BATCH, codec_tag=tag, stats=stats)
    assert _files_of(base, spec.n) == model_files(tag, raw, LARGE, SMALL)
    assert ec_files.read_vif(base) == {
        "version": ec_files.read_vif(base)["version"],
        "dat_file_size": len(raw), "codec": tag,
        "large_block_bytes": LARGE, "small_block_bytes": SMALL}
    assert ec_files.volume_blocks(base) == (LARGE, SMALL)
    # what the job says of the layout: rows and units of each kind
    rows = LARGE_ROWS[shape]
    small_rows = -(-(len(raw) - rows * spec.k * LARGE) // (spec.k * SMALL))
    assert (stats["large_block"], stats["small_block"], stats["large_rows"],
            stats["small_rows"]) == (LARGE, SMALL, rows, small_rows)
    assert stats["large_row_share"] == round(
        rows * spec.k * LARGE / len(raw), 4)
    assert stats["units_column"] == rows * (LARGE // BATCH)
    # span units under every codec: BATCH // SMALL small rows a unit
    assert stats["units_rows"] == -(-small_rows // (BATCH // SMALL))
    _no_leftovers(base)


# -- (b) the served path -----------------------------------------------------

def _needle_volume(tmp_path, k: int, vid: int = 3):
    """A sealed volume of seeded needles, two large rows and a tail that
    ends inside a small row: (base, {needle id: data})."""
    vol = Volume(str(tmp_path), "", vid)
    rng = np.random.default_rng(38)
    blobs = {}
    want = 2 * k * LARGE + 3 * k * SMALL + 777
    i = 0
    while os.path.getsize(vol._base + ".dat") < want:
        i += 1
        data = rng.bytes(int(rng.integers(100, 90000)))
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        vol.flush()
        blobs[i] = data
    vol.close()
    return str(tmp_path / str(vid)), blobs


@pytest.fixture
def served(tmp_path, monkeypatch, request):
    """(volume server, base, blobs) for the test's `tag` and `kind`: a
    server that is never started (handlers are called directly) on a
    directory with one sealed volume, id 3."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    params = getattr(request.node, "callspec", None)
    params = params.params if params else {}
    tag, kind = params.get("tag", "rs_10_4"), params.get("kind", "jax")
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base, blobs = _needle_volume(tmp_path, codecs.parse_tag(tag).k)
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18996)

    async def no_beat():
        return None
    monkeypatch.setattr(vs, "_heartbeat_once", no_beat)
    yield vs, base, blobs
    vs.store.close()


def _generate(vs, tag: str, **fields) -> dict:
    status, out = _call(vs.handle_ec_generate,
                        {"volume": 3, "codec": tag, **fields})
    assert status == 200, out
    return out


def _read_all(ev, blobs: dict, skip=None) -> list[int]:
    """Needle ids whose read through `ev` does not give the bytes written
    (a read that raises counts)."""
    wrong = []
    for nid, data in blobs.items():
        try:
            got = ev.read_needle(nid, skip_shards=skip).data
        except Exception:  # misrouted bytes parse as no needle at all
            got = None
        if got != data:
            wrong.append(nid)
    return wrong


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("tag", TAGS)
def test_a_set_mounts_and_reads_from_its_vif_alone(served, monkeypatch, tag,
                                                   kind):
    """generate with the two fields -> the answer, the job's `stages` and
    the `.vif` say them; the set mounts with no argument and serves every
    needle, healthy and with two shards withheld, in large rows, in small
    rows and across the boundary; a mount that ignores the record (the
    constants forced) must get needles wrong, so this test can tell."""
    vs, base, blobs = served
    spec = codecs.parse_tag(tag)
    out = _generate(vs, tag, large_block_bytes=LARGE,
                    small_block_bytes=SMALL)
    assert (out["large_block_bytes"], out["small_block_bytes"]) == \
        (LARGE, SMALL)
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert status == 200 and job["state"] == "done"
    stages = job["stages"]
    assert (stages["large_block"], stages["small_block"],
            stages["large_rows"]) == (LARGE, SMALL, 2)
    # the served batch is 16 MiB: a 64 KiB block is no column cut
    assert stages["units_column"] == 0 and stages["units_rows"] >= 2
    assert 0.85 < stages["large_row_share"] < 1.0
    with open(base + ".dat", "rb") as f:
        assert _files_of(base, spec.n) == model_files(tag, f.read(), LARGE,
                                                      SMALL)

    status, out = _call(vs.handle_ec_mount, {"volume": 3})
    assert status == 200 and out["shards"] == list(range(spec.n))
    mounted = vs.store.get_ec_volume(3)
    assert (mounted.large_block, mounted.small_block) == (LARGE, SMALL)
    assert _call(vs.handle_ec_unmount, {"volume": 3})[0] == 200

    ev = ec_volume.EcVolume(base)  # a restart: the files, nothing else
    try:
        assert (ev.large_block, ev.small_block) == (LARGE, SMALL)
        kinds = set()
        for nid in blobs:
            off, size = ev.find_needle(nid)
            kinds.add(frozenset(
                iv.is_large_block for iv in layout.locate_data(
                    LARGE, SMALL, ev.dat_size, off,
                    t.actual_size(size, ev.version), data_shards=spec.k)))
        assert kinds == {frozenset({True}), frozenset({False}),
                         frozenset({True, False})}
        assert _read_all(ev, blobs) == []
        assert _read_all(ev, blobs, skip=frozenset({0, 1})) == []
        assert ev.read_stats_snapshot()["reconstruct_batches"] > 0
    finally:
        ev.close()

    # the same files under a build that ignores the record
    monkeypatch.setattr(ec_files, "volume_blocks", lambda base, vif=None: (
        layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE))
    ev = ec_volume.EcVolume(base)
    try:
        assert (ev.large_block, ev.small_block) == (
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE)
        assert _read_all(ev, blobs)
    finally:
        ev.close()


# -- (c) rebuild and decode with no block size handed in ---------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("tag", TAGS)
def test_rebuild_and_decode_take_the_blocks_from_the_vif(served, tag, kind):
    """A lost shard file is rebuilt (the walk over shard files is
    block-agnostic) and the set decodes back to the `.dat`, through the
    library and through the server, each with no block size handed in."""
    vs, base, blobs = served
    spec = codecs.parse_tag(tag)
    _generate(vs, tag, large_block_bytes=LARGE, small_block_bytes=SMALL)
    want = _files_of(base, spec.n)
    with open(base + ".dat", "rb") as f:
        dat = f.read()

    os.remove(base + layout.to_ext(3))
    assert ec_files.rebuild_ec_files(base, batch_size=BATCH) == [3]
    os.remove(base + layout.to_ext(spec.n - 1))
    status, out = _call(vs.handle_ec_rebuild, {"volume": 3})
    assert (status, out) == (200, {"rebuilt": [spec.n - 1]})
    assert _files_of(base, spec.n) == want

    ec_files.write_dat_file(base, len(dat), out_path=base + ".decoded")
    with open(base + ".decoded", "rb") as f:
        assert f.read() == dat
    os.remove(base + ".decoded")

    # /admin/ec/to_volume: the .dat comes back from the data shards
    for loc in vs.store.locations:
        vol = loc.volumes.pop(3, None)
        if vol is not None:
            vol.close()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    assert _call(vs.handle_ec_to_volume, {"volume": 3})[0] == 200
    with open(base + ".dat", "rb") as f:
        assert f.read() == dat
    vol = vs.store.get_volume(3)
    assert all(vol.read_needle(nid).data == data
               for nid, data in blobs.items())
    _no_leftovers(base)


# -- (d) the fleet stream ------------------------------------------------------

@pytest.mark.parametrize("kind", ["fleet"] + KINDS)
@pytest.mark.parametrize("tag", TAGS)
def test_convert_volumes_leaves_what_write_ec_files_leaves(
        tmp_path, monkeypatch, tag, kind):
    monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", kind)
    spec = codecs.parse_tag(tag)
    sizes = [DAT_SIZES[s](spec.k) for s in sorted(DAT_SIZES)]
    made = [_seeded_dat(tmp_path, str(i + 1), n, seed=38 + i)
            for i, n in enumerate(sizes)]
    stats: dict = {}
    fleet_convert.convert_volumes(
        [base for base, _ in made], large_block=LARGE, small_block=SMALL,
        batch_size=BATCH, codec_tag=tag, stats=stats)
    for base, raw in made:
        single, _ = _seeded_dat(tmp_path, "single", 0)
        with open(single + ".dat", "wb") as f:
            f.write(raw)
        with monkeypatch.context() as m:
            m.setenv("WEEDTPU_EC_CODEC", "numpy")
            ec_files.write_ec_files(single, large_block=LARGE,
                                    small_block=SMALL, batch_size=BATCH,
                                    codec_tag=tag)
        assert _files_of(base, spec.n) == _files_of(single, spec.n) == \
            model_files(tag, raw, LARGE, SMALL), base
        assert ec_files.read_vif(base) == ec_files.read_vif(single)
        assert ec_files.volume_blocks(base) == (LARGE, SMALL)
    assert (stats["large_block"], stats["small_block"], stats["large_rows"]) \
        == (LARGE, SMALL, sum(LARGE_ROWS.values()))
    assert stats["units_column"] == stats["large_rows"] * (LARGE // BATCH)
    assert stats["large_row_share"] == round(
        stats["large_rows"] * spec.k * LARGE / sum(sizes), 4)
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


@pytest.mark.parametrize("tag", ["rs_10_4", "msr_9_16"])
def test_fleet_convert_takes_the_two_fields(served, tag):
    vs, base, blobs = served
    spec = codecs.parse_tag(tag)
    status, out = _call(vs.handle_ec_fleet_convert, {
        "volumes": [3], "codec": tag, "large_block_bytes": LARGE,
        "small_block_bytes": SMALL})
    assert status == 200 and out["converted"] == [3], out
    assert (out["large_block_bytes"], out["small_block_bytes"]) == \
        (LARGE, SMALL)
    with open(base + ".dat", "rb") as f:
        assert _files_of(base, spec.n) == model_files(tag, f.read(), LARGE,
                                                      SMALL)
    assert ec_files.volume_blocks(base) == (LARGE, SMALL)
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert (job["stages"]["large_block"], job["stages"]["large_rows"]) == \
        (LARGE, 2)
    ev = ec_volume.EcVolume(base)
    try:
        assert _read_all(ev, blobs, skip=frozenset({2})) == []
    finally:
        ev.close()


# -- (e) a .vif from before the record; a bad pair ---------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_vif_without_the_record_reads_as_before(tmp_path, monkeypatch,
                                                  kind):
    """No fields in the request: upstream's constants, said in the answer
    and recorded.  The same set with the record taken out of its `.vif`
    (every set on disk before this) mounts, reads and decodes alike."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base, blobs = _needle_volume(tmp_path, 1)
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18996)
    try:
        out = _generate(vs, "rs_10_4")
        assert (out["large_block_bytes"], out["small_block_bytes"]) == (
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE)
        vif = ec_files.read_vif(base)
        assert (vif["large_block_bytes"], vif["small_block_bytes"]) == (
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE)
        with open(base + ".vif", "w") as f:
            json.dump({k: v for k, v in vif.items()
                       if not k.endswith("_block_bytes")}, f)
        assert ec_files.volume_blocks(base) == (
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE)
        ev = ec_volume.EcVolume(base)
        try:
            assert (ev.large_block, ev.small_block) == (
                layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE)
            assert _read_all(ev, blobs) == []
            assert _read_all(ev, blobs, skip=frozenset({0, 1})) == []
        finally:
            ev.close()
        with open(base + ".dat", "rb") as f:
            dat = f.read()
        ec_files.write_dat_file(base, len(dat), out_path=base + ".decoded")
        with open(base + ".decoded", "rb") as f:
            assert f.read() == dat
    finally:
        vs.store.close()


BAD_PAIRS = {
    "zero": {"large_block_bytes": 0},
    "negative": {"small_block_bytes": -4096},
    "not_a_number": {"large_block_bytes": "32m"},
    "a_fraction": {"large_block_bytes": 65536.5},
    "small_over_large": {"large_block_bytes": 4096,
                         "small_block_bytes": 65536},
    "large_no_multiple_of_the_batch": {"large_block_bytes": 100_000_000},
    "small_no_multiple_of_the_batch": {"large_block_bytes": 1 << 30,
                                       "small_block_bytes": 20_000_000},
}


@pytest.mark.parametrize("handler", ["generate", "fleet_convert"])
@pytest.mark.parametrize("bad", sorted(BAD_PAIRS))
def test_a_bad_pair_is_a_400_and_nothing_is_written(served, bad, handler):
    vs, base, _blobs = served
    before = sorted(os.listdir(os.path.dirname(base)))
    if handler == "generate":
        status, out = _call(vs.handle_ec_generate,
                            {"volume": 3, **BAD_PAIRS[bad]})
    else:
        status, out = _call(vs.handle_ec_fleet_convert,
                            {"volumes": [3], **BAD_PAIRS[bad]})
    assert status == 400 and "block" in out["error"], out
    assert sorted(os.listdir(os.path.dirname(base))) == before
    assert not vs.store.get_volume(3).read_only  # nothing was frozen
    assert _call(vs.handle_ec_progress, {"volumeId": "3"})[0] == 404


def test_the_shell_passes_the_pair_through_when_given():
    from seaweedfs_tpu.shell import commands
    posts = []

    class Env:
        def require_lock(self):
            pass

        def volume_locations(self, vid):
            return ["a:1"]

        def vs_post(self, url, path, body):
            posts.append((path, body))
            if path == "/admin/ec/generate":
                raise RuntimeError("stop here")
            return {}
    for args, want in (
            (["-volumeId", "7"], {}),
            (["-volumeId", "7", "-largeBlockBytes", "33554432"],
             {"large_block_bytes": 33554432}),
            (["-volumeId", "7", "-largeBlockBytes", "33554432",
              "-smallBlockBytes", "1048576"],
             {"large_block_bytes": 33554432, "small_block_bytes": 1048576})):
        del posts[:]
        with pytest.raises(RuntimeError, match="stop here"):
            commands.cmd_ec_encode(Env(), args, None)
        path, body = posts[-1]
        assert path == "/admin/ec/generate"
        assert {k: v for k, v in body.items() if k.endswith("_bytes")} == want


# -- (f) the two kinds of unit on the trace -----------------------------------------

def test_stage_events_say_which_kind_of_unit(tmp_path, monkeypatch):
    """With a profiler session open, a unit's `read`, `h2d` and `dispatch`
    events carry `rows` and `block` (a column cut of a large-block row: one
    row of the large block), the job's annotation its layout, and /perf's
    `encode_parity` row the stripe counts it ran."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    base, _raw = _seeded_dat(tmp_path, "5", DAT_SIZES[
        "tail_ends_inside_a_small_row"](10))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                                batch_size=BATCH)
    finally:
        jax.profiler.stop_trace()
    found = _annotations(str(tmp_path / "trace"))
    for name in ("ec.encode.read", "codec.h2d", "codec.dispatch"):
        said = sorted((int(s["unit"]), int(s["rows"]), int(s["block"]))
                      for s in found[name])
        # two large rows in two columns each, then three small rows and a
        # short one as one unit of four
        assert said == [(0, 1, LARGE), (1, 1, LARGE), (2, 1, LARGE),
                        (3, 1, LARGE), (4, 4, SMALL)], name
    job, = found["job.ec.encode"]
    assert (int(job["large_block"]), int(job["small_block"]),
            int(job["large_rows"]), int(job["small_rows"])) == \
        (LARGE, SMALL, 2, 4)
    assert 0.85 < float(job["large_row_share"]) < 1.0
    row = next(r for r in pipeline.local_snapshot()["roofline"]["rows"]
               if r["kernel"] == "encode_parity")
    assert (row["rows_in"], row["rows_out"], row["stripes"]) == (10, 4,
                                                                 [1, 4])
