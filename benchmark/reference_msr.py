"""The plain reference of PM-MSR(9,16), tag `msr_9_16`: numpy only.

Rashmi, Shah, Kumar, "Optimal Exact-Regenerating Codes for Distributed
Storage at the MSR and MBR Points via a Product-Matrix Construction"
(arXiv:1005.4178), section V, in the systematic form of Le Scouarnec,
"Fast Product-Matrix Regenerating Codes" (arXiv:1412.3022): k = 9 data
nodes, d = 2k - 2 = 16 helpers a repair, alpha = k - 1 = 8 symbols a node,
n = 18 nodes, 2.0x.  Psi = [Phi  Lambda Phi] with Phi [n, alpha]
Vandermonde, the message matrix M = [S1; S2] of two symmetric alpha x alpha
blocks (72 message symbols), node i stores psi_i M; systematic, so M is
what makes nodes 0..8 store the data.  Written to the contract of a
reference module (README.md, "A reference module"): it takes GF(2^8) and
the volume's own format from `reference` and nothing from the program
under test.  `selfcheck/test_reference_msr.py` holds it to
`seaweedfs_tpu/models/msr.py`, to `seaweedfs_tpu/ops/msr.py` and to
`storage/ec/layout.py` at a small size.

The [72, 72] matrix that takes a column of the nine data nodes' symbols to
the nine parity nodes' is computed once, from the definition above
(`parity_matrix`), and applied a stripe row at a time (`apply`).  That is
72 multiplications a `.dat` byte where Reed-Solomon (10,4) has 4, and by
`reference.gf_matmul`'s table look-ups a 1 GB volume takes minutes (2.1 s
a 9 MiB row on one core here), so `apply` multiplies as the field defines
it, c x = sum over the bits s of c of 2^s x, on eight bytes a machine
word: the eight doublings of a row once, then exclusive-ors.
`selfcheck/test_reference_msr.py` holds it to `gf_matmul`.

Departures from the papers (the configuration states them under
`assumed`): k = 9, d = 16 is the repository's registered geometry and no
headline of 1412.3022; n = 18 = d + 2 where the construction needs n >= d
+ 1; the evaluation points are x_i = 2^i in GF(2^8) / 0x11D and lambda_i =
x_i^alpha; a node is a shard file and a symbol a byte, sub-row a of node i
being the byte set {t * alpha + a} of its file.

The striping is upstream SeaweedFS's, 9 wide: the `.dat` row-major in rows
of 9 large blocks while MORE than one large row's bytes remain, then rows
of 9 small blocks, the last zero-padded; shard j is block j of every row,
shards 9..17 the parity nodes' bytes of each row.
"""

from __future__ import annotations

import concurrent.futures
import functools
import hashlib
import importlib
import multiprocessing
import os

import numpy as np

# GF(2^8) and the volume's own format, which reference modules share
# (found by name, as the harness finds this module)
_shared = importlib.import_module("reference")
gf_matmul = _shared.gf_matmul
MUL = _shared.MUL
read_idx = _shared.read_idx
needle_id_of = _shared.needle_id_of
record_length = _shared.record_length

K, D = 9, 16
ALPHA = K - 1     # symbols a node: sub_packetization
N = D + 2         # nodes, and shard files


def set_of(tag: str) -> tuple[int, int]:
    """(data shards, parity shards) of the set the program's tag names."""
    if tag != "msr_9_16":
        raise ValueError(f"{tag!r}: this module is the reference of "
                         f"msr_9_16 alone")
    return K, N - K


def _power(x: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = int(MUL[out, x])
    return out


def _inverse(a: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); `a` is square and invertible."""
    n = a.shape[0]
    aug = np.concatenate([a.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for c in range(n):
        hit = next(r for r in range(c, n) if aug[r, c])
        aug[[c, hit]] = aug[[hit, c]]
        inv = next(v for v in range(1, 256) if MUL[v, aug[c, c]] == 1)
        aug[c] = MUL[inv][aug[c]]
        for r in range(n):
            if r != c and aug[r, c]:
                aug[r] ^= MUL[aug[r, c]][aug[c]]
    return aug[:, n:]


@functools.lru_cache(maxsize=1)
def parity_matrix() -> np.ndarray:
    """[72, 72]: a column of the data nodes' symbols (node i's symbol a at
    i * alpha + a) -> the parity nodes' symbols, from the construction.
    `stored` takes the 72 message symbols (the upper triangles of S1 and
    S2) to what every node stores, node i's symbol c being
    sum_u phi_i[u] S1[u, c] + lambda_i phi_i[u] S2[u, c]; the data nodes'
    part of it is inverted to make the code systematic."""
    x = [_power(2, i) for i in range(N)]
    phi = [[_power(xi, t) for t in range(ALPHA)] for xi in x]
    lam = [_power(xi, ALPHA) for xi in x]
    triangle = [(p, q) for p in range(ALPHA) for q in range(p, ALPHA)]
    place = {pq: s for s, pq in enumerate(triangle)}
    half = len(triangle)
    stored = np.zeros((N * ALPHA, 2 * half), dtype=np.uint8)
    for i in range(N):
        for c in range(ALPHA):
            for u in range(ALPHA):
                s = place[(min(u, c), max(u, c))]
                stored[i * ALPHA + c, s] ^= phi[i][u]
                stored[i * ALPHA + c, half + s] ^= MUL[lam[i], phi[i][u]]
    data_nodes = stored[:K * ALPHA]
    return gf_matmul(stored[K * ALPHA:], _inverse(data_nodes))


LOW7 = np.uint64(0x7F7F7F7F7F7F7F7F)
ONES = np.uint64(0x0101010101010101)


def _doubled(words: np.ndarray) -> np.ndarray:
    """2 x of every byte of `words` (uint64, eight field elements each)
    in GF(2^8) / 0x11D: shift left, and where the top bit fell off, reduce
    by the polynomial's low byte 0x1D."""
    return ((words & LOW7) << np.uint64(1)) ^ \
        (((words >> np.uint64(7)) & ONES) * np.uint64(0x1D))


def apply(matrix: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """[m, k] x [k, n] over GF(2^8), n a multiple of 8: `gf_matmul`'s
    product, by doubling and exclusive-or on machine words."""
    m, k = matrix.shape
    words = np.ascontiguousarray(rows).view(np.uint64)
    out = np.zeros((m, words.shape[1]), dtype=np.uint64)
    for j in range(k):
        power = words[j]  # 2^s times row j
        for s in range(8):
            for i in np.flatnonzero((matrix[:, j] >> s) & 1):
                np.bitwise_xor(out[i], power, out=out[i])
            power = _doubled(power)
    return out.view(np.uint8)


def _blocks(codec: dict) -> tuple[int, int]:
    """-> (large block, small block) of a `codec` block held to this code."""
    have = (codec["family"], codec["data_shards"], codec["parity_shards"])
    large, small = codec["large_block_bytes"], codec["small_block_bytes"]
    # a block is whole columns of alpha bytes, eight to a machine word
    if have != ("msr", K, N - K) or not 0 < small <= large or \
            small % (8 * ALPHA) or large % (8 * ALPHA):
        raise ValueError(f"no PM-MSR(9,16) layout: {codec}")
    return large, small


def shard_count(codec: dict) -> int:
    _blocks(codec)
    return N


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


def parity_of(data: np.ndarray) -> np.ndarray:
    """[9, n] bytes of the data nodes' files (n a multiple of alpha) ->
    [9, n] bytes of the parity nodes' files: the files' bytes split into
    their alpha sub-rows, the [72, 72] matrix, and the product merged
    back."""
    n = data.shape[1]
    symbols = data.reshape(K, n // ALPHA, ALPHA).transpose(0, 2, 1)
    out = apply(parity_matrix(), symbols.reshape(K * ALPHA, n // ALPHA))
    return out.reshape(N - K, ALPHA, n // ALPHA).transpose(0, 2, 1).reshape(
        N - K, n)


def _row(dat_path: str, unit: tuple[int, int, int, int]) -> np.ndarray:
    """One step of every data shard's file: bytes [at, at + n) of each of
    a row's 9 blocks, zeros past the end of the `.dat`."""
    row_at, block, at, n = unit
    data = np.zeros((K, n), dtype=np.uint8)
    with open(dat_path, "rb") as f:
        for j in range(K):
            f.seek(row_at + j * block + at)
            raw = f.read(n)
            data[j, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return data


def _row_parity(task: tuple) -> np.ndarray:
    return parity_of(_row(*task))


def reference_shards(codec: dict, dat_path: str) -> tuple[list[str], int]:
    """sha256 of each of the 18 shard files `dat_path` must encode to, in
    shard order, and the size of a shard file.  The rows' parity is
    computed in worker processes (`apply` is thousands of short numpy
    calls a row, which threads of one interpreter only slow down: 0.34 s a
    row on one core here, 0.5-1.0 s a row on two to eight threads) and
    hashed here in row order."""
    large, small = _blocks(codec)
    size = os.path.getsize(dat_path)
    large_rows, small_rows = _rows(codec, size)
    # a unit is one step of every shard's file: bytes [at, at + n) of each
    # of a row's 9 blocks; a large row goes in steps of the small block
    units = [(r * K * large, large, at, min(small, large - at))
             for r in range(large_rows) for at in range(0, large, small)]
    small_from = large_rows * K * large
    units += [(small_from + r * K * small, small, 0, small)
              for r in range(small_rows)]
    hashers = [hashlib.sha256() for _ in range(N)]
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1, max(1, len(units))),
            mp_context=multiprocessing.get_context("fork")) as ex:
        parities = ex.map(_row_parity, [(dat_path, u) for u in units])
        for unit, parity in zip(units, parities):
            for h, block in zip(hashers, (*_row(dat_path, unit), *parity)):
                h.update(block)
    return [h.hexdigest() for h in hashers], shard_file_size(codec, size)


def shards_touched(codec: dict, dat_size: int, offset: int,
                   length: int) -> set[int]:
    """Shard files that hold bytes [offset, offset + length) of a `.dat`
    of `dat_size` bytes: block b of a row lives in shard b % 9."""
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
