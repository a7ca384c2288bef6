"""reference.py, held to fixed vectors taken before it read the `codec`
block (PR 27: the RS(10,4) hashes are the parent's), to the program's
`models/rs.py` and `storage/ec/layout.py` at small sizes under three
blocks, and to hand-worked cases of the layout rule."""

import hashlib
import json
import os

import numpy as np
import pytest

import reference
from conftest import BENCH

MIB = reference.MIB


def block(k=10, m=4, large=1024 * MIB, small=MIB):
    return {"reference": "reference", "tag": f"rs_{k}_{m}", "family": "rs",
            "data_shards": k, "parity_shards": m,
            "large_block_bytes": large, "small_block_bytes": small}


@pytest.fixture(scope="module")
def default():
    """The block both configurations state."""
    with open(os.path.join(BENCH, "configs", "ecvol-rs10_4-1g.json")) as f:
        codec = json.load(f)["codec"]
    assert codec == block()
    return codec


def test_parity_matrix_fixed_vector():
    pm = reference.parity_matrix(10, 4)
    assert pm.shape == (4, 10)
    # klauspost/reedsolomon's RS(10,4) parity rows (Vandermonde, made
    # systematic) in GF(2^8) / 0x11D
    assert hashlib.sha256(pm.tobytes()).hexdigest() == PARITY_SHA256
    assert pm[0].tolist() == PARITY_ROW0


def test_encode_fixed_vector(tmp_path, default):
    rng = np.random.default_rng(7)
    dat = tmp_path / "v.dat"
    dat.write_bytes(rng.bytes(10 * MIB + 12345))
    shards, size = reference.reference_shards(default, str(dat))
    assert size == 2 * MIB == reference.shard_file_size(default,
                                                        10 * MIB + 12345)
    assert shards == ENCODE_SHARDS_SHA256
    assert hashlib.sha256("".join(shards).encode()).hexdigest() == \
        ENCODE_SHA256


def program_shards(codec, raw):
    """The shard files `raw` encodes to by the program's own layout rule
    (`locate_data`: every byte of the `.dat` to its shard and offset) and
    its RS code, a column at a time."""
    rs = pytest.importorskip("seaweedfs_tpu.models.rs")
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    k, m = codec["data_shards"], codec["parity_shards"]
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    size = layout.shard_file_size(len(raw), large, small, k)
    data = np.zeros((k, size), dtype=np.uint8)
    at = 0
    for iv in layout.locate_data(large, small, len(raw), 0, len(raw), k):
        shard, off = iv.to_shard_id_and_offset(large, small)
        data[shard, off:off + iv.size] = np.frombuffer(
            raw, dtype=np.uint8, count=iv.size, offset=at)
        at += iv.size
    assert at == len(raw)
    code = rs.get_code(k, m)
    assert np.array_equal(code.parity_matrix, reference.parity_matrix(k, m))
    return list(code.encode_numpy(data)), size


@pytest.mark.parametrize("codec, dat_bytes, large_rows", [
    (block(), 3 * 10 * MIB - 999, 0),
    (block(6, 3), 3 * 6 * MIB - 999, 0),
    # a row of large blocks is 20 MiB: one large row (more than one row's
    # bytes remain once), then 20 MiB - 999 bytes in four small rows
    (block(large=2 * MIB, small=MIB // 2), 40 * MIB - 999, 1),
    # exactly two large rows' bytes: the loop cuts one, not two
    (block(6, 3, large=MIB, small=MIB // 4), 12 * MIB, 1),
], ids=["rs_10_4", "rs_6_3", "rs_10_4-large_rows", "rs_6_3-large_rows"])
def test_against_the_program_at_a_small_size(tmp_path, codec, dat_bytes,
                                             large_rows):
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    k = codec["data_shards"]
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    assert layout.n_large_rows(dat_bytes, large, small, k) == large_rows
    raw = np.random.default_rng(3).bytes(dat_bytes)
    dat = tmp_path / "v.dat"
    dat.write_bytes(raw)
    shards, size = reference.reference_shards(codec, str(dat))
    want, want_size = program_shards(codec, raw)
    assert size == want_size and len(shards) == len(want) == \
        reference.shard_count(codec)
    assert shards == [hashlib.sha256(np.ascontiguousarray(row)).hexdigest()
                      for row in want]
    # which shard files hold a needle, against the program's intervals
    rng = np.random.default_rng(5)
    edges = [0, small - 1, k * large - 1, k * large, dat_bytes - 1]
    for at in [*edges, *rng.integers(0, dat_bytes, 200)]:
        n = int(min(rng.integers(1, 3 * small), dat_bytes - at))
        want = {iv.to_shard_id_and_offset(large, small)[0] for iv in
                layout.locate_data(large, small, dat_bytes, int(at), n, k)}
        assert reference.shards_touched(codec, dat_bytes, int(at), n) == want


def test_shard_file_size_follows_the_encode_loop():
    layout = pytest.importorskip("seaweedfs_tpu.storage.ec.layout")
    codec = block(6, 3, large=4096, small=512)
    row = 6 * 4096
    for size in (1, 511, 512, 6 * 512, 6 * 512 + 1, row - 1, row, row + 1,
                 2 * row - 1, 2 * row, 2 * row + 1, 5 * row + 6 * 512 * 3):
        assert reference.shard_file_size(codec, size) == \
            layout.shard_file_size(size, 4096, 512, 6), size
    # hand-worked: two rows' bytes exactly are one large row and a row's
    # bytes in small rows (`ec_locate.go`'s formula would say two)
    assert reference.shard_file_size(codec, 2 * row) == 4096 + 8 * 512
    # the default block never reaches a large row at 1 GB
    assert reference.shard_file_size(block(), 1_000_018_144) == 96 * MIB


def test_a_block_of_another_family_or_shape_is_refused():
    with pytest.raises(ValueError):
        reference.shard_count(dict(block(), family="lrc"))
    with pytest.raises(ValueError):
        reference.shard_file_size(block(large=MIB, small=2 * MIB), 1)
    assert reference.set_of("rs_6_3") == (6, 3)
    with pytest.raises(ValueError):
        reference.set_of("lrc_12_2_2")


def test_record_length_matches_the_program():
    t = pytest.importorskip("seaweedfs_tpu.storage.types")
    for size in (1, 7, 8, 9, 100, 4095, 4096, 1 << 20):
        assert reference.record_length(size) == t.actual_size(size)
    # hand-worked: 16 + 4 + 12 = 32 is aligned already and still gets 8
    assert reference.record_length(4) == 40
    assert reference.record_length(5) == 40


def test_healthy_degraded_split_hand_worked(default):
    mib = MIB
    dat = 30 * mib

    def touched(offset, length):
        return reference.shards_touched(default, dat, offset, length)
    # block b of the .dat lives in shard b % 10
    assert touched(0, 10) == {0}
    assert touched(mib - 1, 1) == {0}
    assert touched(mib - 1, 2) == {0, 1}          # straddles blocks 0 | 1
    assert touched(2 * mib, mib) == {2}            # exactly block 2
    assert touched(2 * mib, mib + 1) == {2, 3}
    assert touched(9 * mib + 5, mib) == {9, 0}     # wraps into the next row
    assert touched(10 * mib, 1) == {0}             # row 1, block 0
    assert touched(25 * mib + 7, 100) == {5}
    assert touched(0, 10 * mib) == set(range(10))
    lost = {0, 1}
    # a 300 KiB needle at 1.9 MiB sits in block 1 and block 2: degraded;
    # the same needle at 2.1 MiB sits in block 2 alone: healthy
    assert touched(int(1.9 * mib), 300 * 1024) & lost
    assert not touched(int(2.1 * mib), 300 * 1024) & lost


def test_split_with_large_rows_hand_worked():
    # RS(6,3), 4 KiB large blocks, 512 B small: a `.dat` of 60,000 bytes
    # has two large rows (2 x 24,576; 10,848 remain) and four small rows
    codec = block(6, 3, large=4096, small=512)
    dat = 60000

    def touched(offset, length):
        return reference.shards_touched(codec, dat, offset, length)
    assert touched(0, 4096) == {0}
    assert touched(4095, 2) == {0, 1}
    assert touched(24576, 1) == {0}                # second large row
    assert touched(5 * 4096 + 24576, 4097) == {5, 0}   # large into small
    assert touched(49152 + 512, 512) == {1}        # small rows start here
    assert touched(49152 + 7 * 512, 1) == {1}      # second small row
    assert touched(49152 - 1, 6 * 512) == set(range(6))


def test_read_idx(tmp_path):
    p = tmp_path / "v.idx"
    entry = reference.IDX_ENTRY
    p.write_bytes(entry.pack(1, 1, 100) + entry.pack(2, 20, 200) +
                  entry.pack(1, 0, -1) + entry.pack(3, 50, 300))
    assert reference.read_idx(str(p)) == {2: (160, 200), 3: (400, 300)}
    assert reference.needle_id_of("3,01637037d6") == 1
    assert reference.needle_id_of("12,2a5b00000001") == 0x2a5b


PARITY_SHA256 = "6aea6e4fb966660ad42092d4bbd140751dfe0a8214d9170d34e7f4207b86f882"
PARITY_ROW0 = [129, 150, 175, 184, 210, 196, 254, 232, 3, 2]
ENCODE_SHA256 = "05750c64aa897d93c61968676380261c58dea8b0eda00519d76306836d1a0992"
# taken on the parent (6d27555, reference.py with K, M = 10, 4 as constants)
ENCODE_SHARDS_SHA256 = [
    "58fc7e16b7fece5e137898ab9bc1f3fc415bc44d1281a9a832030747a84597e8",
    "8597547c1ea011d36dc3212d7220f894bb2f29910207af8b0f70b6886e8beb4e",
    "aac6a4f043425e16dff12e7adc85ff5ed97546ebc304eaa3a721282bf72241a1",
    "aecdbb13c31f14e1d255f6f48515ebc2564f310d163dd07390ca9ae0340881f1",
    "025f4c68a6e841756a0c5d5ea0d7347f47463fd82a4e4372b649aff56b18b0a3",
    "7a262ee72875e19b3c58f8c647099a4ee2ec1da8a60fdaa226e2db4191858d1e",
    "c698992fd300d43f008b965d0e06cd451db865552042f3074476811ec1739071",
    "dfb103cb7ee534bdced624252a807cdc3c55b2593fc9bb3be1673d45bc477548",
    "e3ad5f7d81bb0c45197d6070b4306f9637c2712773002549b28dda0fe019f842",
    "fe34be74f5df8bfbfa68e83c3d75a9dabafe6fe360794210e76aaf4bd9ef8cce",
    "83ec425cd41c4557571b3e5502faa9e8d34b8915f5b21223405f13ae0484a053",
    "ed5daa99f906f5cfad8e8c6b2171ece1df625a7ce04b24f02bf91e2e1c8ca3de",
    "5a14515befee9e72345ab22c1c1711544fc5a1e5ae52bfd6497277a02c0c1682",
    "3a081e5ef4298d4109ebe4a657eec4bbe17d701b12119a0384402a33c98197da",
]
