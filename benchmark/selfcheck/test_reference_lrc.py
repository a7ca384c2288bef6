"""reference_lrc.py, the plain reference of the configuration
`azure-lrc12_2_2-1g`, held to the program's own plain reference
(`seaweedfs_tpu/models/lrc.py`) and layout rule (`storage/ec/layout.py`)
at a small size, to hand-worked cases of the 12-wide layout, and to a
fixed vector; and its `codec` block taken through the harness's seam."""

import hashlib
import json
import os

import numpy as np
import pytest

import harness
import reference_lrc
from conftest import BENCH

MIB = 1024 * 1024


def block(large=1024 * MIB, small=MIB):
    return {"reference": "reference_lrc", "tag": "lrc_12_2_2",
            "family": "lrc", "data_shards": 12, "parity_shards": 4,
            "large_block_bytes": large, "small_block_bytes": small}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "azure-lrc12_2_2-1g.json")) as f:
        return json.load(f)


def test_the_configurations_block_is_this_modules(config):
    assert config["codec"] == block()
    ref = harness.reference_of(config["codec"])
    assert ref is reference_lrc
    assert ref.set_of("lrc_12_2_2") == (12, 4)
    assert ref.shard_count(config["codec"]) == 16
    for tag in ("rs_10_4", "lrc_10_2_2", "lrc_12_2_3"):
        with pytest.raises(ValueError):
            ref.set_of(tag)
    with pytest.raises(ValueError):
        ref.shard_count(dict(block(), family="rs"))
    with pytest.raises(ValueError):
        ref.shard_file_size(block(large=MIB, small=2 * MIB), 1)
    # the seal call and the set-up ask the program for the tag
    assert config["seal_call"]["steps"][0]["body"]["codec"] == "{codec}"
    with open(os.path.join(BENCH, "traffic",
                           "rebuild_local_1lost.json")) as f:
        mix = json.load(f)
    assert mix["setup"][0]["body"] == {"volume": "{vid}", "codec": "{codec}"}
    assert mix["op"]["check_shards"] == [3] and \
        mix["final_check_shards"] == "all"


def test_generator_is_the_papers_and_the_programs_plain_reference():
    pm = reference_lrc.PARITY
    assert pm.shape == (4, 12)
    assert pm[0].tolist() == [1] * 6 + [0] * 6
    assert pm[1].tolist() == [0] * 6 + [1] * 6
    coeff = pm[2].tolist()
    assert coeff == [1, 2, 3, 4, 5, 6, 0x10, 0x20, 0x30, 0x40, 0x50, 0x60]
    # global row 1 is the squares, in the benchmark's own field arithmetic
    squares = [int(reference_lrc.gf_matmul(
        np.array([[c]], dtype=np.uint8),
        np.array([[c]], dtype=np.uint8))[0, 0]) for c in coeff]
    assert pm[3].tolist() == squares
    assert hashlib.sha256(pm.tobytes()).hexdigest() == PARITY_SHA256
    models = pytest.importorskip("seaweedfs_tpu.models.lrc")
    assert np.array_equal(pm, models.PARITY)


def program_shards(codec, raw):
    """The shard files `raw` encodes to by the program's own layout rule
    (`locate_data`: every byte of the `.dat` to its shard and offset) and
    its plain LRC reference."""
    models = pytest.importorskip("seaweedfs_tpu.models.lrc")
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    size = layout.shard_file_size(len(raw), large, small, 12)
    data = np.zeros((12, size), dtype=np.uint8)
    at = 0
    for iv in layout.locate_data(large, small, len(raw), 0, len(raw), 12):
        shard, off = iv.to_shard_id_and_offset(large, small)
        data[shard, off:off + iv.size] = np.frombuffer(
            raw, dtype=np.uint8, count=iv.size, offset=at)
        at += iv.size
    assert at == len(raw)
    return list(models.encode(data)), size


@pytest.mark.parametrize("codec, dat_bytes, large_rows", [
    (block(), 3 * 12 * MIB - 999, 0),
    # a row of large blocks is 6 MiB: one large row, then 6 MiB - 999
    # bytes in four small rows of 12 x 128 KiB, the last padded
    (block(large=MIB // 2, small=MIB // 8), 12 * MIB - 999, 1),
], ids=["small_rows", "large_rows"])
def test_against_the_program_at_a_small_size(tmp_path, codec, dat_bytes,
                                             large_rows):
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    assert layout.n_large_rows(dat_bytes, large, small, 12) == large_rows
    raw = np.random.default_rng(3).bytes(dat_bytes)
    dat = tmp_path / "v.dat"
    dat.write_bytes(raw)
    shards, size = reference_lrc.reference_shards(codec, str(dat))
    want, want_size = program_shards(codec, raw)
    assert size == want_size == reference_lrc.shard_file_size(codec,
                                                              dat_bytes)
    assert len(shards) == len(want) == 16
    assert shards == [hashlib.sha256(np.ascontiguousarray(row)).hexdigest()
                      for row in want]
    rng = np.random.default_rng(5)
    edges = [0, small - 1, 12 * large - 1, 12 * large, dat_bytes - 1]
    for at in [*edges, *rng.integers(0, dat_bytes, 200)]:
        at = int(min(at, dat_bytes - 1))
        n = int(min(rng.integers(1, 3 * small), dat_bytes - at))
        want_set = {iv.to_shard_id_and_offset(large, small)[0] for iv in
                    layout.locate_data(large, small, dat_bytes, at, n, 12)}
        assert reference_lrc.shards_touched(codec, dat_bytes, at, n) == \
            want_set


def test_layout_hand_worked():
    codec = block()
    # 1 GB in rows of 12 x 1 MiB: 80 rows, an 80 MiB shard file (RS(10,4)
    # at the same size: 96)
    assert reference_lrc.shard_file_size(codec, 1_000_018_144) == 80 * MIB
    assert reference_lrc.shard_file_size(codec, 12 * MIB) == MIB
    assert reference_lrc.shard_file_size(codec, 12 * MIB + 1) == 2 * MIB

    def touched(offset, length):
        return reference_lrc.shards_touched(codec, 40 * MIB, offset, length)
    assert touched(0, 10) == {0}
    assert touched(MIB - 1, 2) == {0, 1}
    assert touched(11 * MIB + 5, MIB) == {11, 0}   # wraps into the next row
    assert touched(12 * MIB, 1) == {0}             # row 1, block 0
    assert touched(10 * MIB, 1) == {10}            # where RS(10,4) says 0
    assert touched(0, 12 * MIB) == set(range(12))
    # shards 0 and 6 lost, one in each local group: a needle of at most
    # 1 MiB touches two neighbouring blocks, so never both
    for at in range(0, 24 * MIB, MIB // 2):
        assert not {0, 6} <= touched(at, MIB)


def test_encode_fixed_vector(tmp_path):
    rng = np.random.default_rng(7)
    dat = tmp_path / "v.dat"
    dat.write_bytes(rng.bytes(12 * MIB + 12345))
    shards, size = reference_lrc.reference_shards(block(), str(dat))
    assert size == 2 * MIB and len(shards) == 16
    assert hashlib.sha256("".join(shards).encode()).hexdigest() == \
        ENCODE_SHA256
    # the local parity of group 0 is the XOR of shard files 0-5
    raw = np.frombuffer(dat.read_bytes(), dtype=np.uint8)
    pad = np.zeros(24 * MIB, dtype=np.uint8)
    pad[:len(raw)] = raw
    rows = pad.reshape(2, 12, MIB)
    xor = np.bitwise_xor.reduce(rows[:, :6], axis=1).reshape(-1)
    assert hashlib.sha256(xor.tobytes()).hexdigest() == shards[12]


def test_through_the_harness_seam(tmp_path):
    """describe_volume and compare_shards take the 16-file set from the
    module and the block."""
    from conftest import hand_made_volume
    codec = block(large=1 << 20, small=4096)
    raw = np.random.default_rng(11).bytes(3 * 12 * 4096 - 100)
    placed = [(1, 8, 100), (2, 8000, 500), (3, 5 * 4096 + 8, 1000)]
    srv, base, loaded, _bodies = hand_made_volume(tmp_path, raw, placed)
    volume = harness.describe_volume(srv, loaded, reference_lrc, codec)
    assert len(volume["shards_sha256"]) == 16
    assert volume["shard_size"] == 3 * 4096
    want, _size = program_shards(codec, raw)
    for i, row in enumerate(want):
        with open(f"{base}.ec{i:02d}", "wb") as f:
            f.write(np.ascontiguousarray(row).tobytes())
    assert harness.compare_shards(base, volume) == []
    with open(base + ".ec14", "r+b") as f:  # a global parity, one byte
        f.seek(777)
        byte = f.read(1)
        f.seek(777)
        f.write(bytes([byte[0] ^ 1]))
    assert harness.compare_shards(base, volume) == [
        f"{base}.ec14 differs from the reference"]
    assert harness.compare_shards(base, volume, only=[3]) == []


PARITY_SHA256 = "c3df2de661e00eac479def8f2a06f3ab3c170d0c106506f58cb49c674c20af66"
ENCODE_SHA256 = "7e473b061d70e523941a04630be91a53fbc1e413059aa4e613efdc7c2251c4f2"
