"""The plain reference of Azure LRC(12,2,2), tag `lrc_12_2_2`: numpy only.

Huang, Simitci, Xu, Ogus, Calder, Gopalan, Li, Yekhanin, "Erasure Coding
in Windows Azure Storage" (USENIX ATC 2012), sections 2-3: 12 data
fragments in 2 local groups of 6, one XOR local parity a group, 2 global
parities: 16 shard files.  Written to the contract of a reference module
(README.md, "A reference module"): it takes GF(2^8) and the volume's own
format from `reference` and nothing from the program under test.
`selfcheck/test_reference_lrc.py` holds it to `seaweedfs_tpu/models/lrc.py`
and to `storage/ec/layout.py` at a small size.

The generator is written out, not computed.  The paper's example lives in
GF(2^4); these are its form in the repository's field (GF(2^8), polynomial
0x11D): coefficients 1..6 for group 0 and 0x10..0x60 for group 1 (low and
high nibble: a sum of two of one group never equals a sum of two of the
other, which is the paper's condition for decoding every four-loss pattern
that can be decoded at all), global row 0 the coefficients, global row 1
their squares.

The striping is upstream SeaweedFS's, 12 wide: the `.dat` row-major in
rows of 12 large blocks while MORE than one large row's bytes remain, then
rows of 12 small blocks, the last zero-padded; shard j is block j of every
row, shards 12..15 the parity of each row.  (Azure itself cuts an extent
into 12 contiguous fragments; the configuration states that departure.)
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import importlib
import os

import numpy as np

# GF(2^8) and the volume's own format, which reference modules share
# (selfcheck/test_contract.py lets no module of this directory but
# reference.py write the import as a statement, a reference module of
# another family included: found by name, as the harness finds this one)
_shared = importlib.import_module("reference")
gf_matmul = _shared.gf_matmul
read_idx = _shared.read_idx
needle_id_of = _shared.needle_id_of
record_length = _shared.record_length

K, LOCAL, GLOBAL = 12, 2, 2

PARITY = np.array([
    [1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0],                      # local 0
    [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1],                      # local 1
    [1, 2, 3, 4, 5, 6, 16, 32, 48, 64, 80, 96],                # global 0
    [1, 4, 5, 16, 17, 20, 29, 116, 105, 205, 208, 185],        # global 1
], dtype=np.uint8)


def set_of(tag: str) -> tuple[int, int]:
    """(data shards, parity shards) of the set the program's tag names."""
    if tag != "lrc_12_2_2":
        raise ValueError(f"{tag!r}: this module is the reference of "
                         f"lrc_12_2_2 alone")
    return K, LOCAL + GLOBAL


def _blocks(codec: dict) -> tuple[int, int]:
    """-> (large block, small block) of a `codec` block held to this code."""
    have = (codec["family"], codec["data_shards"], codec["parity_shards"])
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    if have != ("lrc", K, LOCAL + GLOBAL) or not 0 < small <= large:
        raise ValueError(f"no LRC(12,2,2) layout: {codec}")
    return large, small


def shard_count(codec: dict) -> int:
    _blocks(codec)
    return K + LOCAL + GLOBAL


def _rows(codec: dict, dat_size: int) -> tuple[int, int]:
    """-> (large rows, small rows), as the encode loop cuts them."""
    large, small = _blocks(codec)
    large_rows = max(0, (dat_size - 1) // (K * large))
    rest = dat_size - large_rows * K * large
    return large_rows, -(-rest // (K * small))


def shard_file_size(codec: dict, dat_size: int) -> int:
    large, small = _blocks(codec)
    large_rows, small_rows = _rows(codec, dat_size)
    return large_rows * large + small_rows * small


def reference_shards(codec: dict, dat_path: str) -> tuple[list[str], int]:
    """sha256 of each of the 16 shard files `dat_path` must encode to, in
    shard order, and the size of a shard file."""
    large, small = _blocks(codec)
    size = os.path.getsize(dat_path)
    large_rows, small_rows = _rows(codec, size)
    # a unit is one step of every shard's file: bytes [at, at + n) of each
    # of a row's 12 blocks; a large row goes in steps of the small block
    units = [(r * K * large, large, at, min(small, large - at))
             for r in range(large_rows) for at in range(0, large, small)]
    small_from = large_rows * K * large
    units += [(small_from + r * K * small, small, 0, small)
              for r in range(small_rows)]
    fd = os.open(dat_path, os.O_RDONLY)

    def one(unit: tuple[int, int, int, int]):
        row_at, block, at, n = unit
        data = np.zeros((K, n), dtype=np.uint8)
        for j in range(K):
            raw = os.pread(fd, n, row_at + j * block + at)
            data[j, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        return data, gf_matmul(PARITY, data)

    hashers = [hashlib.sha256() for _ in range(K + LOCAL + GLOBAL)]
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
    of `dat_size` bytes: block b of a row lives in shard b % 12."""
    large, small = _blocks(codec)
    small_from = _rows(codec, dat_size)[0] * K * large
    touched: set[int] = set()
    at, end = offset, offset + length
    while at < end and len(touched) < K:
        if at < small_from:
            block = at // large
            at = (block + 1) * large
        else:
            block = (at - small_from) // small
            at = small_from + (block + 1) * small
        touched.add(block % K)
    return touched
