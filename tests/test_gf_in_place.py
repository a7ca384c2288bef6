"""`_gf_apply` reads the pieces the seams put where they lie (PR 39).

An encode unit's stripe rows or column cut and a rebuild batch's survivor
rows go into the kernel as the 1-D arrays they were put as, and the
parity comes out as the seam wants it, with no `[k, W]` stack laid out
first (`pallas_gf.in_place_block`, `_gf_apply_in_place`); every other
linear input keeps `codec_base.stacked`.  Here, under the Pallas
interpreter on the CPU at blocks that are multiples of DEFAULT_TILE, each
form is held byte for byte to the numpy reference through the dispatch
seam, and the job's `in_place` count says which path the program took.
tests/test_tpu_aot.py holds the served sizes to the same program shape
compiled for a v5e: one custom call and no layout op round it.
"""

import numpy as np
import pytest

from seaweedfs_tpu.models import msr as msr_ref
from seaweedfs_tpu.ops import codecs, dispatch, msr, pallas_gf
from seaweedfs_tpu.stats import pipeline

TILE = pallas_gf.DEFAULT_TILE


def _codec(tag: str):
    code = codecs._code_for(codecs.parse_tag(tag))
    if tag.startswith("msr"):
        return msr.MSRFileCodec(
            pallas_gf.PallasRSCodec(code, interpret=True), code)
    return pallas_gf.PallasRSCodec(code, interpret=True)


def _job():
    stats = {"in_place": 0, "rows_staged": 0}
    return pipeline.PipelineJob("t", stats, register=False), stats


def _encode(tag, rows, block, cut, rng):
    """A unit of `rows` stripe rows of k blocks through `dispatch_parity`,
    put as the engine selects it: a row a piece, or (`cut`) the last row
    staged after a span of the others (`ec_files._unit_spans` on the
    volume's last unit), or a column cut's k pieces (`cut == "column"`),
    or two halves that cut the middle row (no engine puts that; the rule
    takes it).
    -> (parity runs, the reference's, the job's stats)."""
    codec = _codec(tag)
    k = codec.k
    dat = rng.integers(0, 256, (rows, k, block), dtype=np.uint8)
    if cut == "column":
        spans = [dat[0, j] for j in range(k)]
    elif cut == "staged_last":
        spans = [dat[:-1].reshape(-1), dat[-1].reshape(-1).copy()]
    elif cut == "halves":  # pieces of whole blocks that cut a row
        spans = np.split(dat.reshape(-1), 2)
    else:
        spans = [dat[r].reshape(-1) for r in range(rows)]
    files = dat.transpose(1, 0, 2).reshape(k, -1)
    if tag.startswith("msr"):
        want = msr_ref.encode(files)[k:]
    else:
        want = codec.code.encode_numpy(files)[k:]
    job, stats = _job()
    runs = dispatch.materialize(
        dispatch.dispatch_parity(codec, spans, job=job, stripes=rows,
                                 block=block), job=job)
    return runs, want, stats


def _rebuild(tag, lost, width, rng):
    """The rebuild of `lost` through `reconstruct_batch` from every other
    shard, `width` bytes a row: a tuple of rows where that is at least
    ROW_PUTS_FROM (a rebuild batch), one staged array else (a degraded
    read).  -> (rebuilt rows, the reference's, the job's stats)."""
    codec = _codec(tag)
    code = codec.code
    data = rng.integers(0, 256, (code.k, width), dtype=np.uint8)
    shards = code.encode_numpy(data)
    have = [i for i in range(code.n) if i not in lost]
    job, stats = _job()
    out = dispatch.reconstruct_batch(codec, [shards[i] for i in have], have,
                                     list(lost), job=job)
    want = code.reconstruct_numpy({i: shards[i] for i in have}, list(lost))
    return [out[w] for w in lost], [want[w] for w in lost], stats


WIDE = dispatch.ROW_PUTS_FROM  # a rebuild batch's rows go up one by one


@pytest.mark.parametrize("kind, tag, shape, in_place", [
    ("encode", "rs_10_4", (16, TILE, "rows"), 1),
    ("encode", "rs_10_4", (3, 2 * TILE, "staged_last"), 1),
    ("encode", "lrc_12_2_2", (16, TILE, "rows"), 1),
    ("encode", "lrc_12_2_2", (3, TILE, "staged_last"), 1),
    ("encode", "rs_10_4", (1, 2 * TILE, "column"), 1),
    ("encode", "rs_10_4", (3, TILE, "halves"), 1),
    ("rebuild", "rs_10_4", ((3,), WIDE), 1),
    ("rebuild", "rs_10_4", ((0, 12), WIDE), 1),
    ("rebuild", "lrc_12_2_2", ((3,), WIDE), 1),
    ("rebuild", "lrc_12_2_2", ((0, 1), WIDE), 1),
    # the forms that keep codec_base.stacked
    ("encode", "msr_9_16", (2, 4096, "rows"), 0),
    ("rebuild", "rs_10_4", ((0, 1), TILE), 0),
    ("encode", "rs_10_4", (1, TILE + 4096, "column"), 0),
], ids=["unit_16_rows_10_4", "short_last_unit_staged_row_10_4",
        "lrc_unit_16_rows_4_12", "lrc_short_last_unit_4_12",
        "column_cut_10_4", "two_pieces_cutting_a_row_10_4",
        "rebuild_1_lost_10_4", "rebuild_2_lost_10_4",
        "lrc_local_rebuild_1_6", "lrc_global_rebuild_2_12",
        "pmmsr_unit_stacked", "read_one_array_stacked",
        "width_no_tile_multiple_stacked"])
def test_in_place_bytes_match_the_reference(kind, tag, shape, in_place):
    """Byte for byte against `encode_numpy` / `reconstruct_numpy` (PM-MSR:
    `models.msr.encode`), and `in_place` 1 for the one unit or batch where
    the program read its pieces where they lie, 0 where it laid them out
    (`alpha` > 1, the read's one-array stack, a block that is no tile
    multiple)."""
    rng = np.random.default_rng(39)
    if kind == "encode":
        got, want, stats = _encode(tag, *shape, rng)
        assert [len(g) for g in got] == [shape[0] * shape[1]] * len(want)
    else:
        got, want, stats = _rebuild(tag, *shape, rng)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert stats["in_place"] == in_place
    if in_place:  # nothing was copied on the host either
        assert stats["rows_staged"] == 0


@pytest.mark.parametrize("lengths, k, stripes, tile, block", [
    ((10 << 20,) * 16, 10, 16, 131072, 1 << 20),  # sixteen-row unit
    ((16 << 20,) * 10, 10, 1, 131072, 16 << 20),  # column cut
    ((16 << 20,) * 6, 6, 0, 131072, 16 << 20),  # LRC local rebuild batch
    ((20 << 20, 10 << 20), 10, 3, 131072, 1 << 20),  # short last unit
    ((10 << 20,) * 16, 10, 16, 256, None),  # a tiny tile: stacked
    ((10 * (192 << 10),) * 16, 10, 16, 131072, None),  # 192 KiB blocks
    ((15 << 20, 15 << 20), 10, 3, 131072, 1 << 20),  # a piece cuts a row
    ((31 << 19, 29 << 19), 10, 3, 131072, None),  # a piece cuts a block
    ((16 << 20,) * 9, 10, 0, 131072, None),  # rows short of k
])
def test_in_place_block_rule(lengths, k, stripes, tile, block):
    """The rule on shapes alone: whole blocks a piece, the block a tile
    multiple, the tile a multiple of IN_PLACE_QUANTUM."""
    assert pallas_gf.in_place_block(lengths, k, stripes, tile) == block
