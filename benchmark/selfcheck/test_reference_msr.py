"""reference_msr.py, the plain reference of the configuration
`pmmsr-9_16-1g`, held to the program's own plain reference
(`seaweedfs_tpu/models/msr.py`), to the program's code object
(`seaweedfs_tpu/ops/msr.py`) and layout rule (`storage/ec/layout.py`) at a
small size, to hand-worked cases of the 9-wide layout and to a fixed
vector; its multiply held to the shared table; its `codec` block taken
through the harness's seam; and the reader of the cell's two metrics."""

import hashlib
import importlib.util
import json
import os
import re

import numpy as np
import pytest

import harness
import reference_msr
from conftest import BENCH

MIB = 1024 * 1024


def block(large=1024 * MIB, small=MIB):
    return {"reference": "reference_msr", "tag": "msr_9_16",
            "family": "msr", "data_shards": 9, "parity_shards": 9,
            "large_block_bytes": large, "small_block_bytes": small}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "pmmsr-9_16-1g.json")) as f:
        return json.load(f)


def test_the_configurations_block_is_this_modules(config):
    assert config["codec"] == block()
    assert (config["sub_packetization"], config["helpers"],
            config["nodes"]) == (reference_msr.ALPHA, reference_msr.D,
                                 reference_msr.N) == (8, 16, 18)
    ref = harness.reference_of(config["codec"])
    assert ref is reference_msr
    assert ref.set_of("msr_9_16") == (9, 9)
    assert ref.shard_count(config["codec"]) == 18
    for tag in ("rs_10_4", "msr_8_14", "lrc_12_2_2"):
        with pytest.raises(ValueError):
            ref.set_of(tag)
    with pytest.raises(ValueError):
        ref.shard_count(dict(block(), family="rs"))
    with pytest.raises(ValueError):  # a block holds whole words of columns
        ref.shard_file_size(block(large=MIB, small=100), 1)
    # the seal call and the timed call ask the program for the tag
    assert config["seal_call"]["steps"][0]["body"]["codec"] == "{codec}"
    assert config["expect"]["codecs"] == ["PallasRSCodec"]
    with open(os.path.join(BENCH, "traffic", "encode_msr.json")) as f:
        mix = json.load(f)
    assert mix["op"]["timed"]["body"] == {"volume": "{vid}",
                                         "codec": "{codec}"}
    assert mix["op"]["expect"] == {"shards": list(range(18))}
    assert mix["op"]["before"][0]["body"]["shards"] == list(range(18))
    assert mix["final_check_shards"] == "all"


def test_matrix_is_the_constructions_and_the_programs():
    pm = reference_msr.parity_matrix()
    assert pm.shape == (72, 72)
    assert hashlib.sha256(pm.tobytes()).hexdigest() == PARITY_SHA256
    ops = pytest.importorskip("seaweedfs_tpu.ops.msr")
    assert np.array_equal(pm, ops.get_code(9, 16).parity_matrix)
    model = pytest.importorskip("seaweedfs_tpu.models.msr")
    data = np.random.default_rng(2).integers(0, 256, (9, 8 * 64),
                                             dtype=np.uint8)
    assert np.array_equal(reference_msr.parity_of(data),
                          model.encode(data)[9:])


def test_apply_is_the_tables_product():
    rng = np.random.default_rng(6)
    rows = rng.integers(0, 256, (72, 4096), dtype=np.uint8)
    rows[:, :256] = np.arange(256, dtype=np.uint8)  # every element
    for matrix in (reference_msr.parity_matrix(),
                   rng.integers(0, 256, (3, 72), dtype=np.uint8)):
        assert np.array_equal(reference_msr.apply(matrix, rows),
                              reference_msr.gf_matmul(matrix, rows))


def program_shards(codec, raw):
    """The shard files `raw` encodes to by the program's own layout rule
    (`locate_data`) and its plain PM-MSR reference."""
    model = pytest.importorskip("seaweedfs_tpu.models.msr")
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    size = layout.shard_file_size(len(raw), large, small, 9)
    data = np.zeros((9, size), dtype=np.uint8)
    at = 0
    for iv in layout.locate_data(large, small, len(raw), 0, len(raw), 9):
        shard, off = iv.to_shard_id_and_offset(large, small)
        data[shard, off:off + iv.size] = np.frombuffer(
            raw, dtype=np.uint8, count=iv.size, offset=at)
        at += iv.size
    assert at == len(raw)
    return list(model.encode(data)), size


@pytest.mark.parametrize("codec, dat_bytes, large_rows", [
    (block(small=MIB // 8), 3 * 9 * MIB // 8 - 999, 0),
    # a row of large blocks is 2.25 MiB: one large row, then small rows
    # of 9 x 32 KiB, the last padded
    (block(large=MIB // 4, small=MIB // 32), 4 * MIB - 999, 1),
], ids=["small_rows", "large_rows"])
def test_against_the_program_at_a_small_size(tmp_path, codec, dat_bytes,
                                             large_rows):
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    assert layout.n_large_rows(dat_bytes, large, small, 9) == large_rows
    raw = np.random.default_rng(3).bytes(dat_bytes)
    dat = tmp_path / "v.dat"
    dat.write_bytes(raw)
    shards, size = reference_msr.reference_shards(codec, str(dat))
    want, want_size = program_shards(codec, raw)
    assert size == want_size == reference_msr.shard_file_size(codec,
                                                              dat_bytes)
    assert len(shards) == len(want) == 18
    assert shards == [hashlib.sha256(np.ascontiguousarray(row)).hexdigest()
                      for row in want]
    rng = np.random.default_rng(5)
    edges = [0, small - 1, 9 * large - 1, 9 * large, dat_bytes - 1]
    for at in [*edges, *rng.integers(0, dat_bytes, 200)]:
        at = int(min(at, dat_bytes - 1))
        n = int(min(rng.integers(1, 3 * small), dat_bytes - at))
        want_set = {iv.to_shard_id_and_offset(large, small)[0] for iv in
                    layout.locate_data(large, small, dat_bytes, at, n, 9)}
        assert reference_msr.shards_touched(codec, dat_bytes, at, n) == \
            want_set


def test_layout_hand_worked():
    codec = block()
    # 1 GB in rows of 9 x 1 MiB: 106 rows, a 106 MiB shard file (RS(10,4)
    # at the same size: 96; LRC(12,2,2): 80)
    assert reference_msr.shard_file_size(codec, 1_000_018_144) == 106 * MIB
    assert reference_msr.shard_file_size(codec, 9 * MIB) == MIB
    assert reference_msr.shard_file_size(codec, 9 * MIB + 1) == 2 * MIB

    def touched(offset, length):
        return reference_msr.shards_touched(codec, 40 * MIB, offset, length)
    assert touched(0, 10) == {0}
    assert touched(MIB - 1, 2) == {0, 1}
    assert touched(8 * MIB + 5, MIB) == {8, 0}     # wraps into the next row
    assert touched(9 * MIB, 1) == {0}              # row 1, block 0
    assert touched(0, 9 * MIB) == set(range(9))


def test_encode_fixed_vector(tmp_path):
    rng = np.random.default_rng(7)
    dat = tmp_path / "v.dat"
    dat.write_bytes(rng.bytes(9 * MIB // 16 + 12345))
    shards, size = reference_msr.reference_shards(block(small=MIB // 16),
                                                  str(dat))
    assert size == 2 * MIB // 16 and len(shards) == 18
    assert hashlib.sha256("".join(shards).encode()).hexdigest() == \
        ENCODE_SHA256


def test_through_the_harness_seam(tmp_path):
    """describe_volume and compare_shards take the 18-file set from the
    module and the block; one altered byte of a parity file is one file
    wrong."""
    from conftest import hand_made_volume
    codec = block(large=1 << 20, small=4096)
    raw = np.random.default_rng(11).bytes(3 * 9 * 4096 - 100)
    placed = [(1, 8, 100), (2, 8000, 500), (3, 5 * 4096 + 8, 1000)]
    srv, base, loaded, _bodies = hand_made_volume(tmp_path, raw, placed)
    volume = harness.describe_volume(srv, loaded, reference_msr, codec)
    assert len(volume["shards_sha256"]) == 18
    assert volume["shard_size"] == 3 * 4096
    want, _size = program_shards(codec, raw)
    for i, row in enumerate(want):
        with open(f"{base}.ec{i:02d}", "wb") as f:
            f.write(np.ascontiguousarray(row).tobytes())
    assert harness.compare_shards(base, volume) == []
    with open(base + ".ec17", "r+b") as f:
        f.seek(777)
        byte = f.read(1)
        f.seek(777)
        f.write(bytes([byte[0] ^ 1]))
    assert harness.compare_shards(base, volume) == [
        f"{base}.ec17 differs from the reference"]
    assert harness.compare_shards(base, volume, only=[3]) == []


def test_the_readers_two_metrics():
    """`encode_program`: operations from the slice's bytes alone, the
    layout as busy less kernel; nothing to read, nothing returned."""
    spec = importlib.util.spec_from_file_location(
        "readers.encode_program",
        os.path.join(BENCH, "readers", "encode_program.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    table = harness.kernel_table()["kernels"]
    assert table["gf_apply_int8"]["bound"] == "int8_ops"
    # the 72-row apply alone: an RS or LRC trace holds no such event
    pattern, = table["gf_apply_int8"]["patterns"]
    line = "%_gf_apply.{} = u8[{},2097152]{{1,0:T(8,128)(4,1)}} custom-call("
    assert re.search(pattern, line.format(3, 72))
    assert not re.search(pattern, line.format(1, 4))
    assert re.search(table["gf_apply"]["patterns"][0], line.format(3, 72))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    params = {}
    for name in ("gf_apply_int8_roofline", "encode_layout_s_per_gb"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            params[name] = json.load(f)["params"]
    assert params["gf_apply_int8_roofline"]["ops_per_dat_byte"] == \
        2 * 64 * 72 == 9216
    dev = {"busy_s": 0.34, "kernel_s": {"gf_apply_int8": 0.0625,
                                        "gf_apply": 0.0625}}
    ev = {"kernels": table, "peak": peak,
          "slice": {"bytes": 2e9, "trace": {"devices": [dev],
                                            "window_s": 1.0}}}
    least = 2e9 * 9216 / 393e12
    assert reader.read(ev, params["gf_apply_int8_roofline"]) == \
        pytest.approx(100 * least / 0.0625)
    assert reader.read(ev, params["encode_layout_s_per_gb"]) == \
        pytest.approx((0.34 - 0.0625) / 2)
    # a trace without the kernel, or no trace: the metric is left out
    dev["kernel_s"] = {}
    assert reader.read(ev, params["gf_apply_int8_roofline"]) is None
    assert reader.read(ev, params["encode_layout_s_per_gb"]) is None
    assert reader.read({"slice": None}, params["encode_layout_s_per_gb"]) \
        is None


PARITY_SHA256 = "d51cd6f0b60fff9476b7d5e28159c1caad5616c4ab2559b41faae6a592df0f6c"
ENCODE_SHA256 = "4fba5a0a86893c1cecefdd1f7a01976cc61e367e797c61acd3b1aa072dc0f584"
