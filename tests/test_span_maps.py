"""The single-volume engines map their sources with no page made ready
(`ec_files._map_lazy`: a batch or a unit reads its own span of the map and
whoever reads a page first takes its fault): the files against the plain
references at every shape of volume, how many spans were selected, that no
map is populated and that none outlives the call, however it ends.  CPU,
the XLA shell as the device codec; `mmap.mmap` wrapped."""

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


# ---- nothing is populated, whatever WEEDTPU_EC_PREFAULT says -------------

@pytest.mark.parametrize("prefault", ["auto", "always"])
@pytest.mark.parametrize("engine", ["rebuild", "encode", "fleet"])
def test_only_the_fleet_populates_whole_files(engine, prefault, tmp_path,
                                              monkeypatch, maps_made):
    """The single-volume engines no longer read the variable; the fleet's
    `_VolumeJob` still maps through `_map_readonly` (the control: the spy
    sees a populated map where there is one)."""
    monkeypatch.setenv("WEEDTPU_EC_PREFAULT", prefault)
    if engine == "rebuild":
        base, _ = _shard_set(tmp_path, "rs_10_4", [3])
        ec_files.rebuild_ec_files(base, batch_size=R_BATCH)
    elif engine == "encode":
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        _encode(base, "rs_10_4")
    else:
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        fleet_convert.convert_volumes([base], large_block=LARGE,
                                      small_block=SMALL, batch_size=E_BATCH)
    populated = [bool(flags & mmap.MAP_POPULATE)
                 for _, flags, *_ in maps_made]
    assert populated == {"rebuild": [False] * 10, "encode": [False],
                         "fleet": [True]}[engine]
    assert not _alive(maps_made)


# ---- every way out --------------------------------------------------------

def _ending(how: str, monkeypatch, seam: tuple) -> tuple:
    """Arrange for the call to end `how` -> (kwargs of the call, what it
    raises): `cancel` after the second batch, a writer that fails
    mid-stream, the seam's first half raising on its third call, its second
    half on its second."""
    if how == "last":
        return {}, None
    if how == "cancel":
        seen: list = []
        return ({"progress": seen.append, "cancel": lambda: len(seen) >= 2},
                ec_files.EncodeCancelled)
    name, nth = {"writer": ("_pwritev_all", 2), "enqueue": (seam[0], 3),
                 "materialize": (seam[1], 2)}[how]
    _nth_call_raises(monkeypatch, ec_files, name, nth)
    return {}, Boom


@pytest.mark.parametrize("how", ["last", "cancel", "writer", "enqueue",
                                 "materialize"])
@pytest.mark.parametrize("engine", ["rebuild", "encode"])
def test_no_map_outlives_the_call(engine, how, tmp_path, monkeypatch,
                                  maps_made):
    """The call raises what ended it and nothing else (no `BufferError`
    from a map closed under a live view), leaves no `.tmp`, no thread and,
    once the exception is dropped, no open map."""
    if engine == "rebuild":
        base, _ = _shard_set(tmp_path, "rs_10_4", [3])
        kwargs, raises = _ending(how, monkeypatch, (
            "_dispatch_reconstruct", "_materialize_rows"))
        call = lambda: ec_files.rebuild_ec_files(  # noqa: E731
            base, batch_size=R_BATCH, **kwargs)
        made, threads = 10, {"ec-rebuild-drain"}
    else:
        base, _, _ = _dat(tmp_path, "rs_10_4", "ragged")
        kwargs, raises = _ending(how, monkeypatch, (
            "_dispatch_parity", "_materialize"))
        call = lambda: _encode(base, "rs_10_4", **kwargs)  # noqa: E731
        made, threads = 1, {"ec-reader", "ec-drain"}
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
               if j["kind"] == "ec_" + engine)
    assert job["state"] == ("failed" if raises else "done")
