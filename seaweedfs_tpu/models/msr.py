"""PM-MSR(9,16): the plain reference of the tag `msr_9_16`.

Rashmi, Shah, Kumar, "Optimal Exact-Regenerating Codes for Distributed
Storage at the MSR and MBR Points via a Product-Matrix Construction"
(arXiv:1005.4178), section V: the MSR point at d = 2k - 2, beta = 1.
alpha = k - 1 = 8 symbols a node, B = k * alpha = 72 message symbols,
n = 18 nodes.  The encoding matrix is Psi = [Phi  Lambda Phi], [n, d],
with Phi [n, alpha] Vandermonde and Lambda diagonal; the message matrix is
M = [S1; S2], [d, alpha], two symmetric alpha x alpha blocks whose upper
triangles are the 2 * alpha (alpha + 1) / 2 = 72 message symbols; node i
stores psi_i M, alpha symbols.  Any k nodes decode (theorem 5), and a lost
node f is regenerated from any d helpers that each send one symbol,
stored_j phi_f^T (theorem 4).  Le Scouarnec, "Fast Product-Matrix
Regenerating Codes" (arXiv:1412.3022), is the systematic form and what it
costs: the first k nodes hold the data itself, which fixes M.

This module is to that code what `models/lrc.py` is to Azure's LRC, and
as plain: numpy only, in the paper's own form.  `message` solves for M
from the k data nodes' content, `encode` stores psi_i M at every node,
`repair` is theorem 4's exchange, `reconstruct` eliminates over whatever
nine nodes survive.  It shares the field (`ops/gf`: GF(2^8), polynomial
0x11D) with the program and nothing else: not `ops/msr.py`'s generator
`G`, its `parity_matrix`, its `decode_matrix` or its `repair_matrix`.
tests/test_msr_pm.py holds the program to it.

Departures from the papers, each also in the benchmark configuration's
`assumed`:
- k = 9, d = 16 is the repository's registered geometry; 1412.3022
  measures the family over a range of k and none is claimed as its
  headline here.
- n = 18 = d + 2; the construction needs n >= d + 1.
- The evaluation points are x_i = 2^i in GF(2^8) / 0x11D and lambda_i =
  x_i^alpha (the papers ask only that Phi be Vandermonde and the lambda_i
  distinct: 8 * 17 < 255 keeps them so).
- A node is a shard file and a symbol a byte: sub-row a of node i is the
  byte set {t * alpha + a} of its file (byte-interleaved
  sub-packetisation), so a column of the code is alpha consecutive bytes
  of each file.
"""

from __future__ import annotations

import numpy as np

from seaweedfs_tpu.ops import gf

K = 9                 # data nodes
D = 16                # helpers of a repair: 2k - 2
ALPHA = K - 1         # symbols a node
N = D + 2             # nodes

X = [gf.gf_pow(2, i) for i in range(N)]
PHI = np.array([[gf.gf_pow(x, t) for t in range(ALPHA)] for x in X],
               dtype=np.uint8)                                   # [n, alpha]
LAM = np.array([gf.gf_pow(x, ALPHA) for x in X], dtype=np.uint8)
PSI = np.concatenate([PHI, gf.GF_MUL_TABLE[LAM[:, None], PHI]], axis=1)

# the 36 places of a symmetric alpha x alpha block's upper triangle
TRIANGLE = [(p, q) for p in range(ALPHA) for q in range(p, ALPHA)]


def _solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """X with a @ X == b over GF(2^8): Gauss-Jordan elimination on the
    square matrix `a` with `b`, [rows of a, anything], carried along.
    Raises ValueError where `a` is singular."""
    n = a.shape[0]
    a = a.astype(np.uint8).copy()
    b = b.astype(np.uint8).copy()
    for c in range(n):
        hit = next((r for r in range(c, n) if a[r, c]), None)
        if hit is None:
            raise ValueError("singular over GF(2^8)")
        if hit != c:
            a[[c, hit]] = a[[hit, c]]
            b[[c, hit]] = b[[hit, c]]
        inv = gf.GF_MUL_TABLE[gf.gf_inv(int(a[c, c]))]
        a[c], b[c] = inv[a[c]], inv[b[c]]
        for r in range(n):
            if r != c and a[r, c]:
                f = gf.GF_MUL_TABLE[int(a[r, c])]
                a[r] ^= f[a[c]]
                b[r] ^= f[b[c]]
    return b


def split(rows: np.ndarray) -> np.ndarray:
    """[nodes, L] file rows -> [nodes, alpha, L / alpha] symbols: symbol a
    of column t of a node is byte t * alpha + a of its file."""
    rows = np.asarray(rows, dtype=np.uint8)
    nodes, width = rows.shape
    assert width % ALPHA == 0, rows.shape
    return rows.reshape(nodes, width // ALPHA, ALPHA).transpose(0, 2, 1)


def merge(symbols: np.ndarray) -> np.ndarray:
    """`split`, undone."""
    nodes, _alpha, cols = symbols.shape
    return symbols.transpose(0, 2, 1).reshape(nodes, cols * ALPHA)


def _stored_by(nodes: list[int]) -> np.ndarray:
    """The [len(nodes) * alpha, 72] matrix that takes the 72 message
    symbols (S1's triangle, then S2's) to what `nodes` store, written out
    from node i stores psi_i M: its symbol c is
    sum_u phi_i[u] S1[u, c] + lambda_i phi_i[u] S2[u, c]."""
    half = len(TRIANGLE)
    place = {pq: s for s, pq in enumerate(TRIANGLE)}
    out = np.zeros((len(nodes) * ALPHA, 2 * half), dtype=np.uint8)
    for r, i in enumerate(nodes):
        for c in range(ALPHA):
            for u in range(ALPHA):
                s = place[(min(u, c), max(u, c))]
                out[r * ALPHA + c, s] ^= PSI[i, u]
                out[r * ALPHA + c, half + s] ^= PSI[i, ALPHA + u]
    return out


def _message_from(nodes: list[int], symbols: np.ndarray) -> np.ndarray:
    """M, [d, alpha, cols], from what the k nodes `nodes` store
    ([k, alpha, cols]): the 72 message symbols solved for, and laid back
    into the two symmetric blocks."""
    cols = symbols.shape[2]
    free = _solve(_stored_by(nodes), symbols.reshape(K * ALPHA, cols))
    m = np.zeros((D, ALPHA, cols), dtype=np.uint8)
    for block in range(2):
        for s, (p, q) in enumerate(TRIANGLE):
            sym = free[block * len(TRIANGLE) + s]
            m[block * ALPHA + p, q] = sym
            m[block * ALPHA + q, p] = sym
    return m


def _store(m: np.ndarray, nodes: list[int]) -> np.ndarray:
    """psi_i M for each node of `nodes`: [len(nodes), alpha, cols]."""
    cols = m.shape[2]
    flat = gf.gf_matmul(PSI[nodes], m.reshape(D, ALPHA * cols))
    return flat.reshape(len(nodes), ALPHA, cols)


def message(data: np.ndarray) -> np.ndarray:
    """[9, L] data files -> the message matrix M of every column,
    [16, 8, L / 8]: systematic, so M is what makes the first k nodes
    store the data itself."""
    data = np.asarray(data, dtype=np.uint8)
    assert data.shape[0] == K, data.shape
    return _message_from(list(range(K)), split(data))


def encode(data: np.ndarray) -> np.ndarray:
    """[9, L] data files -> [18, L] node files (L a multiple of alpha)."""
    return merge(_store(message(data), list(range(N))))


def reconstruct(shards: dict[int, np.ndarray],
                wanted: list[int] | None = None) -> dict[int, np.ndarray]:
    """Rebuild the node files `wanted` (default: every one absent) from
    the first nine present: M by elimination over what they store, then
    psi_w M.  Raises ValueError with eight or fewer."""
    present = sorted(shards)
    if wanted is None:
        wanted = [i for i in range(N) if i not in shards]
    if not wanted:
        return {}
    if len(present) < K:
        raise ValueError(f"PM-MSR(9,16): {len(present)} nodes survive, "
                         f"any {K} decode and no fewer")
    use = present[:K]
    stored = split(np.stack([np.asarray(shards[i], dtype=np.uint8)
                             for i in use], axis=0))
    out = merge(_store(_message_from(use, stored), list(wanted)))
    return {w: out[i] for i, w in enumerate(wanted)}


def repair(shards: dict[int, np.ndarray], lost: int,
           helpers: list[int]) -> tuple[np.ndarray, int]:
    """Regenerate node `lost` from d = 16 `helpers` as theorem 4 states
    it -> (the node's file, bytes the helpers sent).  Helper j sends the
    one symbol a column stored_j phi_f^T; the d of them are Psi_H [S1
    phi_f^T; S2 phi_f^T], so the rebuilder inverts Psi_H, and since S1 and
    S2 are symmetric, (S1 phi_f^T)^T + lambda_f (S2 phi_f^T)^T is phi_f S1
    + lambda_f phi_f S2: the node's content."""
    if len(helpers) != D or lost in helpers or len(set(helpers)) != D:
        raise ValueError(f"a repair of node {lost} takes {D} distinct "
                         f"helpers other than itself, got {helpers}")
    phi_f = PHI[lost][None, :]                                   # [1, alpha]
    sent = np.concatenate(
        [gf.gf_matmul(phi_f, split(np.asarray(shards[j])[None, :])[0])
         for j in helpers], axis=0)                              # [d, cols]
    both = _solve(PSI[helpers], sent)     # [S1 phi_f^T; S2 phi_f^T]
    node = both[:ALPHA] ^ gf.GF_MUL_TABLE[int(LAM[lost])][both[ALPHA:]]
    return merge(node[None])[0], int(sent.size)
