"""A traffic file's block sizes are literals (the harness's `fill` offers
no name for them): whatever a cell's traffic or its configuration's
`seal_call` says in a request body is the `codec` block's own number, for
every cell whose configuration uses the file, and a cell whose block is not
the program's default says it in every conversion request it makes."""

import json
import pathlib

import pytest

from seaweedfs_tpu.storage.ec import layout

ROOT = pathlib.Path(__file__).parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}
FIELDS = ("large_block_bytes", "small_block_bytes")
DEFAULTS = dict(zip(FIELDS, (layout.LARGE_BLOCK_SIZE,
                             layout.SMALL_BLOCK_SIZE)))
CONVERSIONS = ("/admin/ec/generate", "/admin/ec/fleet_convert")


def _traffic(name: str) -> dict:
    return json.loads((ROOT / "benchmark" / "traffic" /
                       (name + ".json")).read_text())


def _conversion_bodies(cell: dict) -> list[dict]:
    """The bodies of every conversion request the cell's files make: the
    configuration's seal call, the traffic's set-up, untimed steps and
    timed call."""
    traffic = _traffic(cell["traffic"])
    steps = list(CONFIGS[cell["config"]]["seal_call"]["steps"]) + \
        list(traffic.get("setup", []))
    op = traffic.get("op", {})
    steps += list(op.get("before", [])) + \
        ([op["timed"]] if "timed" in op else [])
    return [s["body"] for s in steps if s["path"] in CONVERSIONS]


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_block_literals_are_the_codec_blocks(cell):
    codec = CONFIGS[cell["config"]]["codec"]
    bodies = _conversion_bodies(cell)
    for body in bodies:
        for field in FIELDS:
            if field in body:
                assert body[field] == codec[field], (cell["name"], body)
            else:  # unsaid: the program's default must be the block's
                assert codec[field] == DEFAULTS[field], (cell["name"], body)
    expect = _traffic(cell["traffic"]).get("op", {}).get("expect", {})
    for field in FIELDS:
        if field in expect:
            assert expect[field] == codec[field], cell["name"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_reduced_block_is_stated_beside_the_codec_block(name):
    """`reduced` names top-level keys: a configuration that lists a block
    size there states it at the top level, equal to its codec block's."""
    cfg = CONFIGS[name]
    for field in FIELDS:
        if field in cfg["reduced"]:
            assert cfg[field] == cfg["codec"][field] != DEFAULTS[field]
        else:
            assert cfg["codec"][field] == DEFAULTS[field]


def test_the_large_block_cell_walks_both_kinds_of_row():
    """vol30g.encode at seed 0's size: two large-block rows, two thirds of
    the bytes, then small-block rows; four column-cut units and two
    sixteen-row units under the served batch."""
    from seaweedfs_tpu.storage.ec import ec_files
    codec = CONFIGS["vol30g-rs10_4-lb32m"]["codec"]
    large, small = (codec[f] for f in FIELDS)
    dat = 1_000_018_144
    geo = ec_files.block_geometry([dat], large, small,
                                  codec["data_shards"])
    assert (geo["large_rows"], geo["small_rows"], geo["large_row_share"]) \
        == (2, 32, 0.6711)
    units = list(ec_files._iter_spans(dat, large, small,
                                      ec_files.DEFAULT_BATCH,
                                      codec["data_shards"]))
    assert [(block, step, rows) for _, block, _, step, _, rows in units] == \
        [(large, 16 << 20, 1)] * 4 + [(small, small, 16)] * 2
    assert layout.shard_file_size(dat, large, small) == 96 << 20
