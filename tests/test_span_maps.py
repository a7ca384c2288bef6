"""The bulk engines map their sources with no page made ready
(`ec_files._map_lazy`: a batch or a unit reads its own span of the map and
whoever reads a page first takes its fault): the files against the plain
references at every shape of volume, how many spans were selected, that no
map is populated and that none outlives the call, however it ends.  CPU,
the XLA shell as the single-volume device codec, the unit-sharded mesh or
the XLA shell for the fleet stream; `mmap.mmap` wrapped."""

import gc
import mmap
import os
import threading
import weakref

import numpy as np
import pytest

from seaweedfs_tpu.ops import codecs, fleet_convert
from seaweedfs_tpu.stats import pipeline
from seaweedfs_tpu.storage.ec import ec_files
from tests.test_fleet_convert import (BATCH as E_BATCH, LARGE, SMALL,
                                      _files_of, _model_files)
from tests.test_rebuild_pipeline import (BATCH as R_BATCH, Boom, _in_thread,
                                         _nth_call_raises, _shard_bytes,
                                         _shard_set)

TAGS = ["rs_10_4", "lrc_12_2_2", "msr_9_16"]
LOST = {"rs_10_4": [3], "lrc_12_2_2": [3], "msr_9_16": [4]}
SURVIVORS = {"rs_10_4": 10, "lrc_12_2_2": 6, "msr_9_16": 9}
# shard file sizes: no multiple of the batch, one short batch, nothing,
# whole batches (each a multiple of msr_9_16's alpha)
SHARD_SIZES = {"ragged": 4 * R_BATCH + 704, "one_batch": 704, "empty": 0,
               "whole_batches": 3 * R_BATCH}
# .dat sizes by k: a large row and small rows that end inside a row, one
# unit, nothing, two whole units of eight small rows
DAT_SIZES = {"ragged": lambda k: k * LARGE + 5 * k * SMALL + 777,
             "one_unit": lambda k: 3 * k * SMALL,
             "empty": lambda k: 0,
             "whole_units": lambda k: 16 * k * SMALL}


@pytest.fixture(autouse=True)
def _xla_shell(monkeypatch):
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    monkeypatch.delenv("WEEDTPU_CONVERT_CODEC", raising=False)
    pipeline.reset()
    yield
    pipeline.reset()


@pytest.fixture
def maps_made(monkeypatch):
    """Every file map made through `mmap.mmap` while the test runs: a list
    of (weak reference, flags, length, the thread that made it)."""
    made = []

    class Spy(mmap.mmap):
        def __new__(cls, fileno, length, *args, **kw):
            self = super().__new__(cls, fileno, length, *args, **kw)
            if fileno != -1:
                made.append((weakref.ref(self), kw.get("flags", 0), length,
                             threading.current_thread().name))
            return self

    monkeypatch.setattr(mmap, "mmap", Spy)
    return made


def _alive(made) -> list:
    gc.collect()  # a dropped traceback's frames held the last batch's rows
    return [ref() for ref, *_ in made
            if ref() is not None and not ref().closed]


def _dat(tmp_path, tag: str, shape: str):
    k = codecs.parse_tag(tag).k
    raw = np.random.default_rng(k).integers(
        0, 256, DAT_SIZES[shape](k), dtype=np.uint8).tobytes()
    base = str(tmp_path / "1")
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    return base, raw, k


def _encode(base: str, tag: str, **kw):
    return ec_files.write_ec_files(base, large_block=LARGE,
                                   small_block=SMALL, batch_size=E_BATCH,
                                   codec_tag=tag, **kw)


# ---- the bytes, and how many spans were selected ------------------------

@pytest.mark.parametrize("shape", list(SHARD_SIZES))
@pytest.mark.parametrize("tag", TAGS)
def test_rebuild_from_lazy_maps_equals_the_plain_reference(
        tag, shape, tmp_path, maps_made):
    size = SHARD_SIZES[shape]
    base, want = _shard_set(tmp_path, tag, LOST[tag], size)
    stats: dict = {}
    assert _in_thread(lambda: ec_files.rebuild_ec_files(
        base, batch_size=R_BATCH, stats=stats)) == LOST[tag]
    for i in range(len(want)):
        assert _shard_bytes(base, i) == want[i].tobytes(), f"shard file {i}"
    batches = -(-size // R_BATCH)
    assert stats["survivors"] == SURVIVORS[tag]
    assert stats["spans_mapped"] == batches * SURVIVORS[tag]
    # a map a survivor file (an empty file has nothing to map), made after
    # `open` in a stage of its own, none of them populated, none left
    assert len(maps_made) == (SURVIVORS[tag] if size else 0)
    assert not any(flags & mmap.MAP_POPULATE for _, flags, *_ in maps_made)
    assert stats["map_s"] > 0 and stats["open_s"] > 0
    assert not _alive(maps_made)


@pytest.mark.parametrize("shape", list(DAT_SIZES))
@pytest.mark.parametrize("tag", TAGS)
def test_encode_from_a_lazy_map_equals_the_plain_reference(
        tag, shape, tmp_path, maps_made):
    base, raw, k = _dat(tmp_path, tag, shape)
    stats: dict = {}
    _encode(base, tag, stats=stats)
    n = codecs.parse_tag(tag).n
    assert _files_of(base, n) == _model_files(tag, raw)
    # the units that hold data, each selected once in the .dat's one map
    units = sum(1 for row_start, _, col, *_ in ec_files._iter_spans(
        len(raw), LARGE, SMALL, E_BATCH, k) if row_start + col < len(raw))
    assert stats["spans_mapped"] == units
    assert units == {"ragged": LARGE // E_BATCH + 1, "one_unit": 1,
                     "empty": 0, "whole_units": 2}[shape]
    assert [(flags & mmap.MAP_POPULATE, length, thread)
            for _, flags, length, thread in maps_made] == \
        ([(0, 0, threading.current_thread().name)] if raw else [])
    assert ("map_s" in stats) == bool(raw)
    assert not _alive(maps_made)


def _fleet_dats(tmp_path, tag: str) -> tuple[list, list, int]:
    """A volume of every shape of DAT_SIZES, one base each."""
    k = codecs.parse_tag(tag).k
    bases, raws = [], []
    for i, shape in enumerate(DAT_SIZES):
        raw = np.random.default_rng(k + i).integers(
            0, 256, DAT_SIZES[shape](k), dtype=np.uint8).tobytes()
        base = str(tmp_path / shape)
        with open(base + ".dat", "wb") as f:
            f.write(raw)
        bases.append(base)
        raws.append(raw)
    return bases, raws, k


def _convert(bases, **kw):
    return fleet_convert.convert_volumes(bases, large_block=LARGE,
                                         small_block=SMALL,
                                         batch_size=E_BATCH, **kw)


@pytest.mark.parametrize("kind", ["fleet", "jax"])
@pytest.mark.parametrize("tag", TAGS)
def test_fleet_from_lazy_maps_equals_the_plain_reference(
        tag, kind, tmp_path, monkeypatch, maps_made):
    """One conversion of a volume of every shape: the plain reference's
    files, one map a non-empty volume made on the calling thread in
    `map`, and a unit selected from a map for every unit that holds data
    (spans of the map, on the mesh and under the XLA shell alike)."""
    monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", kind)
    bases, raws, k = _fleet_dats(tmp_path, tag)
    stats: dict = {}
    _convert(bases, codec_tag=tag, stats=stats)
    n = codecs.parse_tag(tag).n
    for base, raw in zip(bases, raws):
        if raw:
            assert _files_of(base, n) == _model_files(tag, raw), base
    holding = sum(1 for raw in raws for row_start, _, col, *_ in
                  ec_files._iter_spans(len(raw), LARGE, SMALL, E_BATCH, k)
                  if row_start + col < len(raw))
    assert stats["spans_mapped"] == holding == \
        stats["units_column"] + stats["units_rows"]
    assert [(flags & mmap.MAP_POPULATE, length, thread)
            for _, flags, length, thread in maps_made] == \
        [(0, 0, threading.current_thread().name)] * sum(map(bool, raws))
    assert stats["map_s"] > 0 and stats["open_s"] > 0
    assert not _alive(maps_made)


def _open_under(path) -> list[str]:
    """Files under `path` this process holds open."""
    fds = "/proc/self/fd"
    out = []
    for fd in os.listdir(fds):
        try:
            target = os.readlink(os.path.join(fds, fd))
        except OSError:
            continue
        if target.startswith(str(path)):
            out.append(target)
    return out


@pytest.mark.parametrize("stage", ["open", "map"])
def test_a_fleet_that_cannot_open_or_map_leaves_nothing(stage, tmp_path,
                                                        monkeypatch,
                                                        maps_made):
    """A volume whose `.dat` is gone (`open`), or a map that fails on the
    second volume (`map`): the call raises it, and every volume opened
    before is rolled back: no `.tmp`, no open file, no map, no writer."""
    monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", "fleet")
    bases, _, _ = _fleet_dats(tmp_path, "rs_10_4")
    if stage == "open":
        os.remove(bases[2] + ".dat")
        raises = FileNotFoundError
    else:
        _nth_call_raises(monkeypatch, fleet_convert, "_map_lazy", 2)
        raises = Boom
    with pytest.raises(raises):
        _convert(bases)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not _open_under(tmp_path)
    assert len(maps_made) == {"open": 0, "map": 1}[stage]
    assert not _alive(maps_made)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-writer")]
    job = next(j for j in pipeline.jobs_snapshot()
               if j["kind"] == "fleet_convert")
    assert job["state"] == "failed"


# ---- nothing is populated --------------------------------------------------

@pytest.mark.parametrize("engine", ["rebuild", "encode", "fleet"])
def test_no_engine_populates(engine, tmp_path, maps_made):
    """Every bulk engine maps through `_map_lazy`: no map of a source
    file asks for `MAP_POPULATE`."""
    if engine == "rebuild":
        base, _ = _shard_set(tmp_path, "rs_10_4", [3])
        ec_files.rebuild_ec_files(base, batch_size=R_BATCH)
    elif engine == "encode":
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        _encode(base, "rs_10_4")
    else:
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        _convert([base])
    populated = [bool(flags & mmap.MAP_POPULATE)
                 for _, flags, *_ in maps_made]
    assert populated == [False] * {"rebuild": 10, "encode": 1,
                                   "fleet": 1}[engine]
    assert not _alive(maps_made)


# ---- every way out --------------------------------------------------------

def _ending(how: str, monkeypatch, seam: tuple, module=ec_files) -> tuple:
    """Arrange for the call to end `how` -> (kwargs of the call, what it
    raises): `cancel` after the second batch, a writer that fails
    mid-stream, the seam's first half (as `module` calls it) raising on its
    third call, its second half on its second."""
    if how == "last":
        return {}, None
    if how == "cancel":
        seen: list = []
        return ({"progress": seen.append, "cancel": lambda: len(seen) >= 2},
                ec_files.EncodeCancelled)
    if how == "writer":
        _nth_call_raises(monkeypatch, ec_files, "_pwritev_all", 2)
    else:
        name, nth = {"enqueue": (seam[0], 3), "materialize": (seam[1], 2)}[how]
        _nth_call_raises(monkeypatch, module, name, nth)
    return {}, Boom


@pytest.mark.parametrize("how", ["last", "cancel", "writer", "enqueue",
                                 "materialize"])
@pytest.mark.parametrize("engine", ["rebuild", "encode", "fleet"])
def test_no_map_outlives_the_call(engine, how, tmp_path, monkeypatch,
                                  maps_made):
    """The call raises what ended it and nothing else (no `BufferError`
    from a map closed under a live view), leaves no `.tmp`, no thread and,
    once the exception is dropped, no open map.  The fleet runs on the
    unit-sharded mesh, its units spans of the map."""
    if engine == "rebuild":
        base, _ = _shard_set(tmp_path, "rs_10_4", [3])
        kwargs, raises = _ending(how, monkeypatch, (
            "_dispatch_reconstruct", "_materialize_rows"))
        call = lambda: ec_files.rebuild_ec_files(  # noqa: E731
            base, batch_size=R_BATCH, **kwargs)
        made, threads = 10, {"ec-rebuild-drain"}
    elif engine == "encode":
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        kwargs, raises = _ending(how, monkeypatch, (
            "_dispatch_parity", "_materialize"))
        call = lambda: _encode(base, "rs_10_4", **kwargs)  # noqa: E731
        made, threads = 1, {"ec-reader", "ec-drain"}
    else:
        monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", "fleet")
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        kwargs, raises = _ending(how, monkeypatch, (
            "dispatch_parity_batch", "unit_parity_shards"), fleet_convert)
        call = lambda: _convert([base], **kwargs)  # noqa: E731
        made, threads = 1, {"fleet-reader", "fleet-drain"}
    if raises is None:
        _in_thread(call)
    else:
        with pytest.raises(raises):
            _in_thread(call)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not {t.name for t in threading.enumerate()} & threads
    assert len(maps_made) == made
    assert not _alive(maps_made)
    job = next(j for j in pipeline.jobs_snapshot()
               if j["kind"] == {"fleet": "fleet_convert"}.get(
                   engine, "ec_" + engine))
    assert job["state"] == ("failed" if raises else "done")
