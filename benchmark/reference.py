"""The benchmark's plain reference: numpy only, and its own copy.

Two things live here.  The volume's own format (`read_idx`,
`needle_id_of`, `record_length`) and GF(2^8), which every reference module
may import: a reference is independent of the program under test, not of
its siblings.  And the reference of the Reed-Solomon family, written to
the contract of a reference module (README.md, "A reference module"):
everything about a shard set is a function of the configuration's `codec`
block, so RS(10,4) at upstream's 1 GB / 1 MB blocks, RS(6,3) at 1 MiB or
RS(10,4) with a small `large_block_bytes` are blocks, not code.

What a `.dat` must encode to (`ec_encoder.go`'s loop: rows of k large
blocks while MORE than one large row remains, then rows of k small blocks,
the last zero-padded; shard j is block j of every row, large blocks
first, and shards k.. hold the parity of each row), and which shard files
hold a byte range of it.  Nothing here imports the program under test;
`selfcheck/` holds this file against `seaweedfs_tpu/models/rs.py` and
`storage/ec/layout.py` at small sizes and against fixed vectors.

Field: GF(2^8), primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator
2: klauspost/reedsolomon's, which upstream SeaweedFS encodes with.  The
generator matrix is that library's default: a Vandermonde matrix
vm[r, c] = r**c made systematic by vm @ inv(vm[:k]).
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import os
import re
import struct

import numpy as np

MIB = 1024 * 1024
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


def parity_matrix(k: int, m: int) -> np.ndarray:
    """The [m, k] parity rows of the systematic Vandermonde generator."""
    vm = np.array([[_gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return gf_matmul(vm, _gf_inv_matrix(vm[:k]))[k:]


# -- the Reed-Solomon family, by the contract of a reference module ------------

def set_of(tag: str) -> tuple[int, int]:
    """(data shards, parity shards) of the set the program's codec tag
    `rs_<k>_<m>` names."""
    found = re.fullmatch(r"rs_(\d+)_(\d+)", tag)
    if not found:
        raise ValueError(f"{tag!r} is no Reed-Solomon tag (rs_<k>_<m>): "
                         f"another family brings a reference of its own")
    return int(found.group(1)), int(found.group(2))


def _geometry(codec: dict) -> tuple[int, int, int, int]:
    """-> (k, m, large block, small block) of a `codec` block."""
    if codec["family"] != "rs":
        raise ValueError(f"family {codec['family']!r}: this module is the "
                         f"reference of `rs` alone")
    k, m = codec["data_shards"], codec["parity_shards"]
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    if not (k >= 1 and m >= 1 and k + m <= 256 and 0 < small <= large):
        raise ValueError(f"no RS layout: {codec}")
    return k, m, large, small


def shard_count(codec: dict) -> int:
    """How many shard files a set has."""
    k, m, _large, _small = _geometry(codec)
    return k + m


def _rows(codec: dict, dat_size: int) -> tuple[int, int]:
    """-> (large rows, small rows), as the encode loop cuts them: a large
    row while more than one large row's bytes remain, small rows for the
    rest."""
    k, _m, large, small = _geometry(codec)
    large_rows = max(0, (dat_size - 1) // (k * large))
    rest = dat_size - large_rows * k * large
    return large_rows, -(-rest // (k * small))


def shard_file_size(codec: dict, dat_size: int) -> int:
    _k, _m, large, small = _geometry(codec)
    large_rows, small_rows = _rows(codec, dat_size)
    return large_rows * large + small_rows * small


def reference_shards(codec: dict, dat_path: str) -> tuple[list[str], int]:
    """sha256 of each shard file `dat_path` must encode to, in shard
    order, and the size of a shard file."""
    k, m, large, small = _geometry(codec)
    size = os.path.getsize(dat_path)
    large_rows, small_rows = _rows(codec, size)
    pm = parity_matrix(k, m)
    # a unit is one step of every shard's file: bytes [at, at + n) of each
    # of a row's k blocks.  A small row is one unit; a large row goes in
    # steps of the small block, so no unit holds more than k small blocks
    units = [(r * k * large, large, at, min(small, large - at))
             for r in range(large_rows) for at in range(0, large, small)]
    small_from = large_rows * k * large
    units += [(small_from + r * k * small, small, 0, small)
              for r in range(small_rows)]
    fd = os.open(dat_path, os.O_RDONLY)

    def one(unit: tuple[int, int, int, int]):
        row_at, block, at, n = unit
        data = np.zeros((k, n), dtype=np.uint8)
        for j in range(k):
            raw = os.pread(fd, n, row_at + j * block + at)
            data[j, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        return data, gf_matmul(pm, data)

    hashers = [hashlib.sha256() for _ in range(k + m)]
    try:
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=min(8, os.cpu_count() or 1)) as ex:
            for data, parity in ex.map(one, units):
                for h, block in zip(hashers, (*data, *parity)):
                    h.update(block)
    finally:
        os.close(fd)
    return [h.hexdigest() for h in hashers], shard_file_size(codec, size)


def shards_touched(codec: dict, dat_size: int, offset: int,
                   length: int) -> set[int]:
    """Shard files that hold bytes [offset, offset + length) of a `.dat`
    of `dat_size` bytes: in the large rows byte b lives in shard
    (b // large block) % k, past them in ((b - large rows' bytes) //
    small block) % k."""
    k, _m, large, small = _geometry(codec)
    small_from = _rows(codec, dat_size)[0] * k * large
    touched: set[int] = set()
    at, end = offset, offset + length
    while at < end and len(touched) < k:
        if at < small_from:
            block = at // large
            at = (block + 1) * large
        else:
            block = (at - small_from) // small
            at = small_from + (block + 1) * small
        touched.add(block % k)
    return touched


# -- the volume's own format ------------------------------------------------------

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


def needle_id_of(fid: str) -> int:
    """`vid,<key hex><cookie 8 hex>` -> key."""
    return int(fid.partition(",")[2][:-8], 16)
