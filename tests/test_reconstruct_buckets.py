"""The reconstruct seam runs at a few widths, not at every row length:
the bucket rule, byte identity at the buckets' edges, and the count of
programs a device shell builds for degraded reads and for a rebuild."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.models import lrc as lrc_ref
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import codec_base, dispatch, gfmat_jax, lrc, pallas_gf
from seaweedfs_tpu.stats import pipeline, profile
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume

TILE = 256
LARGE, SMALL = 200, 100  # blocks this small: every needle is on every shard
TOP = TILE << (codec_base.BUCKETS - 1)
SHELLS = ["jax", "pallas"]


def _shell(kind: str, tile: int = TILE, code=None):
    """A device shell of its own at a tile tiny volumes can cross several
    buckets of.  The tests that count programs take a tile each: a
    program built for another test's width is not built again."""
    code = code or rs.get_code(10, 4)
    if kind == "pallas":
        return pallas_gf.PallasRSCodec(code, tile=tile, interpret=True)
    codec = gfmat_jax.JaxRSCodec(code)
    codec.tile = tile
    return codec


@pytest.fixture
def serve(monkeypatch):
    """serve(codec): every engine's codec selection resolves to `codec`,
    with the compile counter on."""
    ec_files._get_codec("jax")  # notes a JAX backend: counting is on

    def use(codec):
        monkeypatch.setattr(ec_files, "_get_codec",
                            lambda kind=None, tag=None: codec)
        return codec
    return use


def _programs() -> int:
    return profile.compiles_snapshot().get("reconstruct", {}).get("count", 0)


# ---- the rule ----------------------------------------------------------

@pytest.mark.parametrize("tile", [256, 32768, pallas_gf.TPU_TILE])
def test_bucket_rule(tile):
    top = tile << (codec_base.BUCKETS - 1)
    edges = {1, 2, tile - 1, tile, tile + 1, 2 * tile, 2 * tile + 1,
             3 * tile, top - 1, top, top + 1, 2 * top, 2 * top + 1,
             5 * top - 3}
    edges |= {int(x) for x in np.geomspace(1, 3 * top, 400)}
    widths = [codec_base.bucket(n, tile) for n in sorted(edges)]
    for n, w in zip(sorted(edges), widths):
        assert w >= n and w % tile == 0 and w <= 2 * max(n, tile), (n, w)
    assert widths == sorted(widths)  # monotone
    upto = {w for n, w in zip(sorted(edges), widths) if n <= top}
    assert upto == {tile << j for j in range(codec_base.BUCKETS)}
    if tile == pallas_gf.TPU_TILE:  # the chip: 128 KiB ... a rebuild batch
        assert top == ec_files.DEFAULT_BATCH == 16 << 20
        assert len(upto) <= 8
    assert codec_base.bucket(top + 1, tile) == 2 * top


# ---- byte identity at the edges ----------------------------------------

@pytest.mark.parametrize("kind", SHELLS)
@pytest.mark.parametrize("row_puts", [False, True],
                         ids=["one_put", "row_puts"])
@pytest.mark.parametrize("wanted", [[1], [0, 12], [0, 1, 5, 13]],
                         ids=lambda w: f"{len(w)}rows")
@pytest.mark.parametrize("n", [1, TILE - 1, TILE, TILE + 1, 2 * TILE + 1,
                               TOP + 1])
def test_reconstruct_matches_numpy_at_bucket_edges(kind, row_puts, wanted, n,
                                                   monkeypatch):
    """Both ways up (one array of the rows one after the other, or a put
    a row where rows are wide) at every edge of the width buckets."""
    if row_puts:
        monkeypatch.setattr(dispatch, "ROW_PUTS_FROM", 0)
    code = rs.get_code(10, 4)
    codec = _shell(kind)
    rng = np.random.default_rng(n * 31 + len(wanted))
    shards = code.encode_numpy(rng.integers(0, 256, (10, n), dtype=np.uint8))
    ids = [i for i in range(14) if i not in wanted][:10]
    wide = rng.integers(0, 256, (10, n + 5), dtype=np.uint8)
    wide[:, :n] = shards[ids]
    rows = wide[:, :n]  # stacked, and not contiguous
    assert not rows.flags["C_CONTIGUOUS"] or n == 1
    want = code.reconstruct_numpy(dict(zip(ids, rows)), wanted=wanted)
    got = dispatch.reconstruct_batch(codec, rows, ids, wanted)
    assert sorted(got) == sorted(wanted)
    for w in wanted:
        assert got[w].shape == (n,) and got[w].dtype == np.uint8
        assert np.array_equal(got[w], want[w]), (w, n)
        assert np.array_equal(got[w], shards[w])
    # the dict wrapper (MSRFileCodec's inner shell, older callers)
    out = codec.reconstruct({i: rows[r] for r, i in enumerate(ids)}, wanted)
    assert all(np.array_equal(np.asarray(out[w]), shards[w]) for w in wanted)


def test_a_staged_bucket_goes_up_as_it_is():
    stage = np.arange(10 * 2 * TILE, dtype=np.uint8).reshape(10, 2 * TILE)
    assert dispatch._staged(stage, list(range(10)), 2 * TILE) is stage
    part = dispatch._staged(stage[:, :TILE + 1], list(range(10)), 2 * TILE)
    assert part is not stage and part.shape == stage.shape
    assert np.array_equal(part[:, :TILE + 1], stage[:, :TILE + 1])
    assert not part[:, TILE + 1:].any()
    picked = dispatch._staged(list(stage), [9, 0], 2 * TILE)
    assert np.array_equal(picked, stage[[9, 0]])


# ---- programs built by degraded reads ----------------------------------

def _volume(tmp_path, sizes, seed):
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(seed)
    blobs = {}
    for i, size in enumerate(sizes, start=1):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=1000)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, blobs


@pytest.mark.parametrize("kind", SHELLS)
def test_degraded_reads_build_a_program_a_bucket_not_a_length(
        kind, tmp_path, serve, monkeypatch):
    """Shards 0 and 1 lost: 48 needles of 48 lengths reconstruct through
    EcVolume on a device shell and build no more programs than (width
    buckets) x (counts of rows wanted); 48 other lengths then build none."""
    monkeypatch.setenv("WEEDTPU_EC_RECONSTRUCT_CACHE", "0")
    first = [397 * i + 2011 for i in range(48)]   # 2,011 .. 20,670 bytes
    second = [397 * i + 2023 for i in range(48)]
    assert len(set(first) | set(second)) == 96
    base, blobs = _volume(tmp_path, first + second, seed=21)
    for sid in (0, 1):
        os.remove(base + layout.to_ext(sid))
    tile = 384
    codec = serve(_shell(kind, tile))
    shapes: set = set()
    lengths: set = set()
    real = dispatch._staged

    def staged(rows, order, width):
        lengths.add(len(rows[0]))
        return real(rows, order, width)

    monkeypatch.setattr(dispatch, "_staged", staged)
    real_stack = codec.reconstruct_stack

    def stack_spy(stack, present, wanted, linear):
        assert linear and stack.ndim == 1 and stack.size % 10 == 0
        shapes.add((len(wanted), 10, stack.size // 10))
        return real_stack(stack, present, wanted, linear)

    codec.reconstruct_stack = stack_spy
    ev = ec_volume.EcVolume(base)
    try:
        p0 = _programs()
        for nid in range(1, 49):
            assert ev.read_needle(nid).data == blobs[nid], nid
        built = _programs() - p0
        assert ev.read_stats_snapshot()["reconstruct_batches"] >= 40
        assert len(lengths) >= 30  # the rows really came in many lengths
        widths = {s[2] for s in shapes}
        assert widths <= {tile << j for j in range(codec_base.BUCKETS)}
        assert 1 <= built <= len(widths) * len({s[0] for s in shapes})
        assert built == len(shapes) < len(lengths) / 3
        seen = set(shapes)
        for nid in range(49, 97):
            assert ev.read_needle(nid).data == blobs[nid], nid
        assert shapes == seen  # the same buckets ...
        assert _programs() - p0 == built  # ... and nothing new built
    finally:
        ev.close()


# ---- rebuild: a batch is its own bucket --------------------------------

@pytest.mark.parametrize("kind", SHELLS)
def test_rebuild_puts_a_batch_once_and_builds_one_program(
        kind, tmp_path, serve, monkeypatch):
    batch = 4 * 640  # a bucket of the tile
    base = str(tmp_path / "1")
    rng = np.random.default_rng(4)
    rng.integers(0, 256, 230_000, dtype=np.uint8).tofile(base + ".dat")
    serve(_shell(kind, 640))
    ec_files.write_ec_files(base, large_block=8 * batch, small_block=batch,
                            batch_size=batch)
    shard_size = os.path.getsize(base + layout.to_ext(3))
    batches, rest = divmod(shard_size, batch)
    assert batches > 3 and rest == 0
    want = open(base + layout.to_ext(3), "rb").read()
    os.remove(base + layout.to_ext(3))

    puts = []
    real = dispatch._staged

    def staged(rows, order, width):
        out = real(rows, order, width)
        puts.append(out is rows)
        return out

    monkeypatch.setattr(dispatch, "_staged", staged)
    pipeline.reset()
    before = profile.KERNELS.snapshot().get("reconstruct[device]", {})
    p0 = _programs()
    stats: dict = {}
    assert ec_files.rebuild_ec_files(base, batch_size=batch,
                                     stats=stats) == [3]
    assert open(base + layout.to_ext(3), "rb").read() == want
    assert _programs() - p0 == 1
    # rows narrower than `ROW_PUTS_FROM` go up in one array: the one host
    # copy of a batch is the seam's, out of the maps
    assert puts == [False] * batches
    assert stats["rows_staged"] == 10 * batches
    job = next(j for j in pipeline.jobs_snapshot()
               if j["kind"] == "ec_rebuild")
    assert {job["stages"][s]["items"] for s in
            ("stage", "h2d", "dispatch", "device_wait", "d2h_copy",
             "unstage")} == {batches}
    after = profile.KERNELS.snapshot()["reconstruct[device]"]
    moved = {f: after[f] - before.get(f, 0.0)
             for f in ("calls", "bytes", "h2d_bytes", "d2h_bytes")}
    # ten rows up and the missing row back, a dispatch a batch: what /perf
    # roofline.rows (its `gbytes`, `calls`) said of a rebuild before
    assert moved == {"calls": batches, "bytes": 10 * shard_size,
                     "h2d_bytes": 10 * shard_size, "d2h_bytes": shard_size}


# ---- rebuild: rows a bucket wide go up from the shard files' maps -------

BATCH = 4 * 640  # a bucket of the tile 640


def _code(tag: str):
    return lrc.get_code(12, 2, 2) if tag == "lrc_12_2_2" else \
        rs.get_code(10, 4)


def _shard_set(tmp_path, tag: str, shard_size: int, lost: list[int]):
    """The plain reference's shard files of seeded data under `tag`
    (`models/lrc.py`, `models/rs.py`), the `lost` ones removed: (base, the
    whole set's bytes)."""
    data = np.random.default_rng(shard_size).integers(
        0, 256, (_code(tag).k, shard_size), dtype=np.uint8)
    want = lrc_ref.encode(data) if tag == "lrc_12_2_2" else \
        rs.get_code(10, 4).encode_numpy(data)
    base = str(tmp_path / "7")
    for i, row in enumerate(want):
        if i not in lost:
            row.tofile(base + layout.to_ext(i))
    return base, want


def _spied_rebuild(monkeypatch, base, tag, lost, batch=BATCH,
                   row_puts=BATCH):
    """`rebuild_ec_files` at `ROW_PUTS_FROM` = `row_puts` (one batch),
    with what the seam was handed: per call the rows' length and whether
    every row shares memory with a map the engine made, and the lengths
    `_staged` was entered with."""
    monkeypatch.setattr(dispatch, "ROW_PUTS_FROM", row_puts)
    maps, calls, staged = [], [], []
    real_map, real_seam, real_staged = (
        ec_files._map_lazy, ec_files._dispatch_reconstruct,
        dispatch._staged)

    def map_spy(fd):
        maps.append(real_map(fd))
        return maps[-1]

    def seam_spy(codec, rows, ids, wanted, **kw):
        views = [np.frombuffer(m, dtype=np.uint8) for m in maps]
        calls.append((len(rows[0]), all(
            r.ndim == 1 and any(np.shares_memory(r, v) for v in views)
            for r in rows)))
        del views
        return real_seam(codec, rows, ids, wanted, **kw)

    def staged_spy(rows, order, width):
        staged.append(len(rows[0]))
        return real_staged(rows, order, width)

    monkeypatch.setattr(ec_files, "_map_lazy", map_spy)
    monkeypatch.setattr(ec_files, "_dispatch_reconstruct", seam_spy)
    monkeypatch.setattr(dispatch, "_staged", staged_spy)
    stats: dict = {}
    assert ec_files.rebuild_ec_files(base, batch_size=batch, stats=stats,
                                     codec_tag=tag) == lost
    return stats, calls, staged


@pytest.mark.parametrize("kind", SHELLS)
@pytest.mark.parametrize("tag, lost, survivors", [
    ("rs_10_4", [3], 10), ("rs_10_4", [0, 5, 11, 13], 10),
    ("lrc_12_2_2", [3], 6)], ids=["rs_1lost", "rs_4lost", "lrc_1lost"])
def test_rebuild_puts_whole_buckets_from_the_maps(
        kind, tag, lost, survivors, tmp_path, serve, monkeypatch):
    """Every batch a bucket wide and wide enough to go up row by row:
    the seam is handed views of the maps, copies none of them on the
    host, and the files are the plain reference's."""
    base, want = _shard_set(tmp_path, tag, 4 * BATCH, lost)
    serve(_shell(kind, 640, _code(tag)))
    stats, calls, staged = _spied_rebuild(monkeypatch, base, tag, lost)
    assert calls == [(BATCH, True)] * 4
    assert staged == []
    assert (stats["rows_staged"], stats["survivors"]) == (0, survivors)
    assert stats["stage_s"] > 0 and stats["unstage_s"] > 0
    for i in lost:
        assert open(base + layout.to_ext(i), "rb").read() == \
            want[i].tobytes(), i


# a last batch after three whole buckets of four tiles: (shell, tile, the
# batch's length, `ROW_PUTS_FROM` in tiles); the tiles of 8,192 and 12,288
# are multiples of `pallas_gf.IN_PLACE_QUANTUM`, so the Pallas program can
# read rows of whole tiles where they lie, and no other test builds them
TAILS = {
    "jax": ("jax", 640, 700, 4),
    "pallas": ("pallas", 640, 700, 4),
    "pallas_whole_tiles": ("pallas", 12288, 3 * 12288, 2),
    "jax_whole_tiles": ("jax", 12288, 3 * 12288, 2),
    "pallas_tiles_and_700": ("pallas", 8192, 3 * 8192 + 700, 2),
    "pallas_under_row_puts": ("pallas", 8192, 3 * 8192, 4),
}


@pytest.mark.parametrize("case", list(TAILS))
def test_rebuild_stages_only_a_last_batch_short_of_its_bucket(
        case, tmp_path, serve, monkeypatch):
    """A last batch short of its bucket goes up from the maps at its own
    width where it is at least `ROW_PUTS_FROM` and the codec's program
    reads rows of its length in place (the Pallas shell, whole tiles):
    nothing staged, `narrow` 1, its own program, no padding put.  Under
    the XLA shell, or a tail that is no whole tiles or under
    `ROW_PUTS_FROM`, it alone is staged into its bucket."""
    kind, tile, tail, row_puts = TAILS[case]
    batch = 4 * tile
    narrow = case == "pallas_whole_tiles"
    base, want = _shard_set(tmp_path, "rs_10_4", 3 * batch + tail, [3])
    serve(_shell(kind, tile))
    before = profile.KERNELS.snapshot().get("reconstruct[device]", {})
    p0 = _programs()
    stats, calls, staged = _spied_rebuild(monkeypatch, base, "rs_10_4", [3],
                                          batch, row_puts * tile)
    assert calls == [(batch, True)] * 3 + [(tail, True)]
    assert open(base + layout.to_ext(3), "rb").read() == want[3].tobytes()
    width = tail if narrow else codec_base.bucket(tail, tile)
    after = profile.KERNELS.snapshot()["reconstruct[device]"]
    assert after["h2d_bytes"] - before.get("h2d_bytes", 0) == \
        10 * (3 * batch + width)
    if narrow:  # the bucket's program and the tail's
        assert staged == [] and _programs() - p0 == 2
        assert (stats["rows_staged"], stats["narrow"]) == (0, 1)
        assert stats["in_place"] == 4
    else:  # that batch alone, e.g. 700 bytes in a 1,280 bucket
        assert staged == [tail]
        assert (stats["rows_staged"], stats["narrow"]) == (10, 0)


@pytest.mark.parametrize("kind", SHELLS)
@pytest.mark.parametrize("in_place", [True, False],
                         ids=["from_where_they_lie", "staged"])
def test_seam_takes_rows_or_an_array_alike(kind, in_place, monkeypatch):
    """A list of 1-D rows and the same rows as one array: the same bytes
    out of the same program, whichever way the width sends them up."""
    tile = 896
    n = 2 * tile
    monkeypatch.setattr(dispatch, "ROW_PUTS_FROM", n if in_place else n + 1)
    staged = []
    real = dispatch._staged

    def staged_spy(rows, order, width):
        staged.append(type(rows))
        return real(rows, order, width)

    monkeypatch.setattr(dispatch, "_staged", staged_spy)
    code = rs.get_code(10, 4)
    codec = _shell(kind, tile)
    ec_files._get_codec("jax")  # the compile counter is on
    shards = code.encode_numpy(np.random.default_rng(29).integers(
        0, 256, (10, n), dtype=np.uint8))
    ids, wanted = [0, 1, 2, 4, 5, 6, 7, 8, 9, 13], [3, 12]
    stack = np.ascontiguousarray(shards[ids])
    job = pipeline.PipelineJob("seam", register=False)
    p0 = _programs()
    got_rows = dispatch.reconstruct_batch(codec, list(stack), ids, wanted,
                                          job=job)
    built = _programs() - p0
    got_array = dispatch.reconstruct_batch(codec, stack, ids, wanted,
                                           job=job)
    assert _programs() - p0 == built <= 1
    for w in wanted:
        assert np.array_equal(got_rows[w], shards[w])
        assert np.array_equal(got_array[w], shards[w])
    # in place nothing is stacked; else the list is, once, and the array
    # is a staged bucket already
    assert staged == ([] if in_place else [list, np.ndarray])
    assert job.stats.get("rows_staged", 0) == (0 if in_place else 10)
