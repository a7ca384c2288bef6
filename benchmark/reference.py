"""The benchmark's plain reference: numpy only, and its own copy.

What a `.dat` must encode to under RS(10,4) with the upstream layout
(`ec_encoder.go`: rows of ten 1 MiB blocks while no more than ten 1 GB
blocks remain, the last row zero-padded; shard j is block j of every row),
and which shards a needle's record touches.  Nothing here imports the
program under test; `selfcheck/` holds this file against
`seaweedfs_tpu/models/rs.py` at a small size and against fixed vectors.

Field: GF(2^8), primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator
2 — klauspost/reedsolomon's, which upstream SeaweedFS encodes with.  The
generator matrix is that library's default: a Vandermonde matrix
vm[r, c] = r**c made systematic by vm @ inv(vm[:k]).
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import os
import struct

import numpy as np

K, M = 10, 4
MIB = 1024 * 1024
SMALL_BLOCK = MIB
LARGE_BLOCK = 1024 * MIB
POLY = 0x11D

# needle record on disk, version 3: [cookie 4][id 8][size 4] body[size]
# [crc 4][timestamp 8] then 1-8 bytes of padding to a multiple of 8 (a
# record already aligned gets a full 8: the upstream quirk)
NEEDLE_HEADER = 16
NEEDLE_TRAILER = 12
IDX_ENTRY = struct.Struct(">QIi")  # id, offset in units of 8 bytes, size


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


_EXP, _LOG = _tables()


def _mul_table() -> np.ndarray:
    t = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    t[1:, 1:] = _EXP[_LOG[nz][:, None] + _LOG[nz][None, :]]
    return t


MUL = _mul_table()


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[m, k] x [k, n] over GF(2^8)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for j in range(a.shape[1]):
        for i in range(a.shape[0]):
            out[i] ^= MUL[a[i, j]][b[j]]
    return out


def _gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def _gf_inv_matrix(a: np.ndarray) -> np.ndarray:
    n = a.shape[0]
    aug = np.concatenate([a.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if aug[r, col])
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        inv = int(_EXP[(255 - _LOG[aug[col, col]]) % 255])
        aug[col] = MUL[inv][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[aug[r, col]][aug[col]]
    return aug[:, n:]


def parity_matrix(k: int = K, m: int = M) -> np.ndarray:
    """The [m, k] parity rows of the systematic Vandermonde generator."""
    vm = np.array([[_gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return gf_matmul(vm, _gf_inv_matrix(vm[:k]))[k:]


def shard_file_size(dat_size: int) -> int:
    if dat_size > K * LARGE_BLOCK:
        raise ValueError(f"{dat_size} bytes: the large-block layout is not "
                         f"part of this reference")
    return -(-dat_size // (K * SMALL_BLOCK)) * SMALL_BLOCK


def reference_shards(dat_path: str) -> tuple[list[str], int]:
    """sha256 of each of the 14 shard files `dat_path` must encode to, and
    the size of a shard file."""
    size = os.path.getsize(dat_path)
    shard_size = shard_file_size(size)
    rows = shard_size // SMALL_BLOCK
    row_bytes = K * SMALL_BLOCK
    pm = parity_matrix()

    def one(r: int):
        with open(dat_path, "rb") as f:
            f.seek(r * row_bytes)
            raw = f.read(row_bytes)
        block = np.zeros(row_bytes, dtype=np.uint8)
        block[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        data = block.reshape(K, SMALL_BLOCK)
        return data, gf_matmul(pm, data)

    hashers = [hashlib.sha256() for _ in range(K + M)]
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as ex:
        for data, parity in ex.map(one, range(rows)):
            for h, block in zip(hashers, (*data, *parity)):
                h.update(block)
    return [h.hexdigest() for h in hashers], shard_size


def record_length(size: int) -> int:
    """Bytes a needle of body size `size` (the .idx entry's) takes in the
    `.dat`, padding included."""
    x = NEEDLE_HEADER + size + NEEDLE_TRAILER
    return x + 8 - x % 8


def read_idx(idx_path: str) -> dict[int, tuple[int, int]]:
    """{needle id: (byte offset in the .dat, body size)} of the live
    needles, the last entry of an id winning."""
    out: dict[int, tuple[int, int]] = {}
    with open(idx_path, "rb") as f:
        raw = f.read()
    for pos in range(0, len(raw) - len(raw) % IDX_ENTRY.size, IDX_ENTRY.size):
        nid, units, size = IDX_ENTRY.unpack_from(raw, pos)
        if size > 0:
            out[nid] = (units * 8, size)
        else:
            out.pop(nid, None)
    return out


def shards_touched(offset: int, length: int) -> set[int]:
    """Data shards that hold bytes [offset, offset + length) of a `.dat`
    under the small-block layout: byte b lives in shard (b // 1 MiB) % 10."""
    first = offset // SMALL_BLOCK
    last = (offset + length - 1) // SMALL_BLOCK
    if last - first >= K - 1:
        return set(range(K))
    return {b % K for b in range(first, last + 1)}


def needle_id_of(fid: str) -> int:
    """`vid,<key hex><cookie 8 hex>` -> key."""
    return int(fid.partition(",")[2][:-8], 16)
