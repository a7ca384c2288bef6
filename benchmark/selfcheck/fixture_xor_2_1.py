"""A reference module of another family and shard count, for the
selfcheck alone: two data shard files and one XOR parity, rows of two
small blocks, no large rows.  It is written to the contract of a
reference module (README.md) and to nothing else, so what passes with it
passes with any module a configuration brings."""

from __future__ import annotations

import hashlib
import os

import numpy as np

from reference import needle_id_of, read_idx, record_length  # noqa: F401

CODEC = {"reference": "fixture_xor_2_1", "tag": "xor_2_1", "family": "xor",
         "data_shards": 2, "parity_shards": 1,
         "large_block_bytes": 1 << 30, "small_block_bytes": 4096}


def set_of(tag: str) -> tuple[int, int]:
    if tag != "xor_2_1":
        raise ValueError(f"{tag!r}: this fixture knows xor_2_1 alone")
    return 2, 1


def shard_count(codec: dict) -> int:
    return codec["data_shards"] + codec["parity_shards"]


def shard_file_size(codec: dict, dat_size: int) -> int:
    block = codec["small_block_bytes"]
    return -(-dat_size // (2 * block)) * block


def shard_bytes(codec: dict, raw: bytes) -> list[bytes]:
    block = codec["small_block_bytes"]
    size = shard_file_size(codec, len(raw))
    rows = np.zeros(2 * size, dtype=np.uint8)
    rows[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    rows = rows.reshape(-1, 2, block)
    a, b = rows[:, 0].reshape(-1), rows[:, 1].reshape(-1)
    return [a.tobytes(), b.tobytes(), (a ^ b).tobytes()]


def reference_shards(codec: dict, dat_path: str) -> tuple[list[str], int]:
    with open(dat_path, "rb") as f:
        raw = f.read()
    return [hashlib.sha256(s).hexdigest() for s in shard_bytes(codec, raw)], \
        shard_file_size(codec, os.path.getsize(dat_path))


def shards_touched(codec: dict, dat_size: int, offset: int,
                   length: int) -> set[int]:
    block = codec["small_block_bytes"]
    return {b % 2 for b in range(offset // block,
                                 (offset + length - 1) // block + 1)}
