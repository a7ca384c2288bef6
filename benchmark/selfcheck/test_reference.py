import hashlib

import numpy as np
import pytest

import reference


def test_parity_matrix_fixed_vector():
    pm = reference.parity_matrix()
    assert pm.shape == (4, 10)
    # klauspost/reedsolomon's RS(10,4) parity rows (Vandermonde, made
    # systematic) in GF(2^8) / 0x11D
    assert hashlib.sha256(pm.tobytes()).hexdigest() == PARITY_SHA256
    assert pm[0].tolist() == PARITY_ROW0


def test_encode_fixed_vector(tmp_path):
    rng = np.random.default_rng(7)
    dat = tmp_path / "v.dat"
    dat.write_bytes(rng.bytes(10 * reference.MIB + 12345))
    shards, size = reference.reference_shards(str(dat))
    assert size == 2 * reference.MIB
    assert hashlib.sha256("".join(shards).encode()).hexdigest() == \
        ENCODE_SHA256


def test_against_the_program_at_a_small_size(tmp_path):
    rs = pytest.importorskip("seaweedfs_tpu.models.rs")
    code = rs.get_code(10, 4)
    assert np.array_equal(code.parity_matrix, reference.parity_matrix())
    rng = np.random.default_rng(3)
    dat = tmp_path / "v.dat"
    raw = rng.bytes(3 * 10 * reference.MIB - 999)
    dat.write_bytes(raw)
    shards, size = reference.reference_shards(str(dat))
    rows = size // reference.MIB
    padded = np.zeros(rows * 10 * reference.MIB, dtype=np.uint8)
    padded[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    grid = padded.reshape(rows, 10, reference.MIB)
    hashers = [hashlib.sha256() for _ in range(14)]
    for r in range(rows):
        full = code.encode_numpy(grid[r])
        for h, row in zip(hashers, full):
            h.update(np.ascontiguousarray(row))
    assert [h.hexdigest() for h in hashers] == shards


def test_record_length_matches_the_program():
    t = pytest.importorskip("seaweedfs_tpu.storage.types")
    for size in (1, 7, 8, 9, 100, 4095, 4096, 1 << 20):
        assert reference.record_length(size) == t.actual_size(size)
    # hand-worked: 16 + 4 + 12 = 32 is aligned already and still gets 8
    assert reference.record_length(4) == 40
    assert reference.record_length(5) == 40


def test_healthy_degraded_split_hand_worked():
    mib = reference.MIB
    touched = reference.shards_touched
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
