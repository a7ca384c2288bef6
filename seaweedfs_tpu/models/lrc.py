"""Azure LRC(12,2,2): the plain reference of the tag `lrc_12_2_2`.

Huang, Simitci, Xu, Ogus, Calder, Gopalan, Li, Yekhanin, "Erasure Coding
in Windows Azure Storage" (USENIX ATC 2012), sections 2-3: 12 data
fragments in 2 local groups of 6, one XOR local parity a group, 2 global
parities; 16 fragments, 1.33x.  Any three losses decode, and so does every
four-loss pattern that is decodable in principle (1,568 of 1,820: the code
is maximally recoverable); one lost data fragment is read back from the 6
of its group.

This module is to that code what `models/rs.py` is to Reed-Solomon, and
plainer: numpy only, the generator written out, `encode`, and
`reconstruct` by Gaussian elimination over whatever survives.  It shares
the field (`ops/gf`: GF(2^8), polynomial 0x11D) with the program and
nothing else: not `ops/lrc.py`'s construction, group geometry or
`decode_select`.  tests/test_lrc_azure.py holds the program to it.

Departure from the paper: its example is in GF(2^4); the coefficients
here are the paper's form in GF(2^8).  Group 0 takes 1..6 (the low
nibble), group 1 takes 0x10..0x60 (the high nibble), so a sum of two from
one group never equals a sum of two from the other, which is the paper's
condition; global row 0 is the coefficients, global row 1 their squares.
"""

from __future__ import annotations

import numpy as np

from seaweedfs_tpu.ops import gf

K = 12          # data fragments: two local groups of 6
N = 16          # fragments

# parity rows of the generator: fragments 12..15
PARITY = np.array([
    [1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0],                      # local 0
    [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1],                      # local 1
    [1, 2, 3, 4, 5, 6, 16, 32, 48, 64, 80, 96],                # global 0
    [1, 4, 5, 16, 17, 20, 29, 116, 105, 205, 208, 185],        # global 1
], dtype=np.uint8)

GENERATOR = np.concatenate([np.eye(K, dtype=np.uint8), PARITY], axis=0)


def encode(data: np.ndarray) -> np.ndarray:
    """[12, n] data bytes -> [16, n] fragments (systematic)."""
    data = np.asarray(data, dtype=np.uint8)
    assert data.shape[0] == K, data.shape
    return np.concatenate([data, gf.gf_matmul(PARITY, data)], axis=0)


def _express(rows: np.ndarray, targets: np.ndarray) -> np.ndarray | None:
    """X with X @ rows == targets over GF(2^8), by Gauss-Jordan
    elimination on rows^T with the targets carried along; None when a
    target lies outside the rows' span."""
    s, k = rows.shape
    aug = np.concatenate([rows.T, targets.T], axis=1).astype(np.uint8)
    pivots: list[tuple[int, int]] = []  # (row of aug, column)
    r = 0
    for c in range(s):
        hit = next((i for i in range(r, k) if aug[i, c]), None)
        if hit is None:
            continue
        aug[[r, hit]] = aug[[hit, r]]
        aug[r] = gf.GF_MUL_TABLE[gf.gf_inv(int(aug[r, c]))][aug[r]]
        for i in range(k):
            if i != r and aug[i, c]:
                aug[i] ^= gf.GF_MUL_TABLE[int(aug[i, c])][aug[r]]
        pivots.append((r, c))
        r += 1
    if aug[r:, s:].any():  # a target needs a direction no survivor has
        return None
    x = np.zeros((targets.shape[0], s), dtype=np.uint8)
    for row, c in pivots:
        x[:, c] = aug[row, s:]
    return x


def decodable(lost) -> bool:
    """Whether the fragments `lost` can all be rebuilt from the rest."""
    lost = sorted(set(lost))
    keep = [i for i in range(N) if i not in lost]
    return _express(GENERATOR[keep], GENERATOR[lost]) is not None


def reconstruct(shards: dict[int, np.ndarray],
                wanted: list[int] | None = None) -> dict[int, np.ndarray]:
    """Rebuild the fragments `wanted` (default: every one absent) from
    the present ones, all of them offered to the elimination.  Raises
    ValueError for a pattern that cannot be decoded."""
    present = sorted(shards)
    if wanted is None:
        wanted = [i for i in range(N) if i not in shards]
    if not wanted:
        return {}
    x = _express(GENERATOR[present], GENERATOR[list(wanted)])
    if x is None:
        raise ValueError(f"LRC(12,2,2): {list(wanted)} cannot be rebuilt "
                         f"from {present}")
    stack = np.stack([np.asarray(shards[i], dtype=np.uint8)
                      for i in present], axis=0)
    out = gf.gf_matmul(x, stack)
    return {w: out[i] for i, w in enumerate(wanted)}
