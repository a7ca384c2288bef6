"""PM-MSR(k, d): product-matrix minimum-storage regenerating codes.

RS repairs one lost shard by shipping k full shards over the wire.
Regenerating codes (Dimakis et al.; construction from Rashmi, Shah &
Kumar, arXiv:1005.4178 / PAPERS.md 1412.3022) hit the cut-set bound
instead: each of d > k helpers ships a 1/alpha fraction of its shard,
for a total of d/alpha shard-equivalents.  The default PM-MSR(9,16)
has alpha = k-1 = 8, so a repair moves 16/8 = 2 shard-equivalents
instead of 9 — a repair_network_ratio of d/(k*alpha) = 16/72 = 0.222
against the naive k-shard copy, under the 0.334 reduced-read RS floor.

Construction (product-matrix, MSR point, beta = 1)
--------------------------------------------------
alpha = k - 1, d = 2*alpha = 2k - 2, n >= d + 1 nodes (d + 2 here).
Node i has an encoding row psi_i = [phi_i, lambda_i * phi_i] of length d, where
phi_i = [1, x_i, .., x_i^(alpha-1)] is Vandermonde over distinct
x_i = g^i and lambda_i = x_i^alpha (distinct while alpha*i < 255 for
all i).  The message is M = [[S1],[S2]] with S1, S2 symmetric
alpha x alpha — exactly B = alpha*(alpha+1) = k*alpha free symbols —
and node i stores the alpha symbols psi_i @ M.

Repair of node f: every helper j ships the single symbol
stored_j . phi_f (the same phi_f combination for all helpers); the
collected d-vector equals Psi_H @ [S1 phi_f^T; S2 phi_f^T], so the
rebuilder applies R = [I | lambda_f I] @ inv(Psi_H) and, because S1
and S2 are symmetric, R @ received is node f's content transposed.
`repair_coeff` / `repair_matrix` expose exactly these two matrices to
ops/regen.py's planner.

Byte layout: sub-packetization is BYTE-INTERLEAVED.  Sub-row a of
node i's shard file is the byte set {t*alpha + a}; coupling is purely
local, so reconstructing byte range [o, o+s) of one shard touches only
the survivors' same alpha-aligned range, ragged tails behave exactly
as in RS, and a helper's partial read over sub-range [o, s) is one
contiguous pread of file bytes [o*alpha, (o+s)*alpha).

Two classes:
- PMMSRCode: the inner code over n*alpha "virtual rows", systematised
  so its parity_matrix [k*alpha, k*alpha] drops straight into the
  RSCodecBase / NativeRSCodec / matrix_apply_factory seam (the XLA
  bit-sliced, fused Pallas and AVX2 backends run it unchanged).
- MSRFileCodec: the file-level wrapper (k files in, n files out); what
  the storage layer sees.  Over a device shell it has the shells' linear
  surface (`encode_parity_linear`, `decode_basis`, `reconstruct_stack`):
  whole files' rows cross as 1-D arrays and the program that applies the
  matrix splits them into sub-rows and merges the product back
  (codec_base.stacked / unstacked, `alpha`), so the host copies no byte.
  A `[k, L]` array and a host shell go through the reshapes below.
"""

from __future__ import annotations

import functools

import numpy as np

from seaweedfs_tpu.ops import gf

DEFAULT_K = 9
DEFAULT_D = 16
GENERATOR = 2


class PMMSRCode:
    """Inner product-matrix MSR code over virtual rows.

    Virtual row i*alpha + a is sub-row a of node i.  Systematic in the
    first k nodes' rows; `parity_matrix` is [m*alpha, k*alpha].  The
    code is node-MDS (any k whole nodes decode), NOT row-MDS — decoding
    goes through `decode_select`, which picks whole nodes."""

    family = "msr"

    def __init__(self, k: int = DEFAULT_K, d: int = DEFAULT_D,
                 n: int | None = None):
        if d != 2 * (k - 1):
            raise ValueError(f"PM-MSR needs d == 2k-2, got k={k} d={d}")
        self.k_nodes = k
        self.d = d
        self.alpha = k - 1
        self.n_nodes = n if n is not None else d + 2  # 2k-2 helpers + lost + 1
        if self.n_nodes < d + 1:
            raise ValueError(f"need n >= d+1 nodes, got {self.n_nodes}")
        if self.alpha * (self.n_nodes - 1) >= gf.ORDER:
            raise ValueError(f"PM-MSR({k},{d}) lambdas collide in GF(2^8)")
        self.m_nodes = self.n_nodes - k
        a = self.alpha
        # per-node encoding rows psi_i = [phi_i, lambda_i * phi_i]
        self.x = np.array([gf.gf_pow(GENERATOR, i)
                           for i in range(self.n_nodes)], dtype=np.uint8)
        self.phi = np.array(
            [[gf.gf_pow(int(xi), t) for t in range(a)] for xi in self.x],
            dtype=np.uint8)
        self.lam = np.array([gf.gf_pow(int(xi), a) for xi in self.x],
                            dtype=np.uint8)
        assert len(set(int(v) for v in self.lam)) == self.n_nodes
        self.psi = np.concatenate(
            [self.phi, gf.GF_MUL_TABLE[self.lam[:, None], self.phi]], axis=1)
        # E maps the B = k*alpha free symbols (upper triangles of S1, S2)
        # to the n*alpha stored symbols; systematise against the first k
        # nodes to get the generator G with parity block G[k*alpha:].
        B = a * (a + 1)
        tri = {}
        for p in range(a):
            for q in range(p, a):
                tri[(p, q)] = len(tri)
        E = np.zeros((self.n_nodes * a, B), dtype=np.uint8)
        half = B // 2
        for i in range(self.n_nodes):
            for col in range(a):  # stored symbol: phi_i @ S1[:,col] + ...
                for u in range(a):
                    s = tri[(min(u, col), max(u, col))]
                    E[i * a + col, s] ^= int(self.phi[i, u])
                    E[i * a + col, half + s] ^= gf.gf_mul(
                        int(self.lam[i]), int(self.phi[i, u]))
        D = E[: k * a]
        G = gf.gf_matmul(E, gf.gf_mat_inv(D))
        assert np.array_equal(G[: k * a], np.eye(k * a, dtype=np.uint8))
        self.G = G
        self.parity_matrix = np.ascontiguousarray(G[k * a:])
        # RSCodecBase surface: virtual-row dimensions
        self.k = k * a
        self.m = self.m_nodes * a
        self.n = self.n_nodes * a
        self.tag = f"msr_{k}_{d}"

    # ---- node geometry ---------------------------------------------------

    def node_rows(self, i: int) -> list[int]:
        return list(range(i * self.alpha, (i + 1) * self.alpha))

    def whole_nodes(self, rows) -> list[int]:
        """Node ids whose full alpha sub-rows appear in `rows`."""
        have = set(rows)
        return [i for i in range(self.n_nodes)
                if all(r in have for r in self.node_rows(i))]

    # ---- decoding (virtual-row protocol for the codec shells) ------------

    def decodable(self, lost_nodes: list[int]) -> bool:
        return len(set(lost_nodes)) <= self.n_nodes - self.k_nodes

    def decode_select(self, available: list[int],
                      wanted: list[int]) -> list[int]:
        """First k whole surviving nodes, as sorted virtual rows.  The
        PM code is node-MDS, so any k whole nodes form a basis."""
        nodes = self.whole_nodes(available)
        if len(nodes) < self.k_nodes:
            raise ValueError(
                f"msr: {len(nodes)} whole nodes available, need "
                f"{self.k_nodes}")
        basis: list[int] = []
        for i in nodes[: self.k_nodes]:
            basis.extend(self.node_rows(i))
        return sorted(basis)

    def decode_matrix(self, available: list[int],
                      wanted: list[int]) -> np.ndarray:
        basis = self.decode_select(list(available), list(wanted))
        inv = gf.gf_mat_inv(self.G[basis])
        return gf.gf_matmul(self.G[list(wanted)], inv)

    # ---- regenerating repair (consumed by ops/regen.py) ------------------

    def repair_coeff(self, lost_node: int) -> np.ndarray:
        """[1, alpha] helper-side combination: every helper ships
        phi_f @ its own sub-rows — one row per alpha stored."""
        return self.phi[lost_node][None, :].copy()

    def repair_matrix(self, lost_node: int,
                      helpers: list[int]) -> np.ndarray:
        """[alpha, d] rebuilder matrix R: node f's sub-rows are
        R @ stacked helper symbols (helpers in the given order)."""
        if len(helpers) != self.d:
            raise ValueError(f"msr repair needs d={self.d} helpers, "
                             f"got {len(helpers)}")
        if lost_node in helpers:
            raise ValueError("lost node cannot help itself")
        psi_h = self.psi[list(helpers)]  # [d, d] — invertible Vandermonde
        inv = gf.gf_mat_inv(psi_h)
        a = self.alpha
        lam_f = int(self.lam[lost_node])
        # [I | lambda_f I] @ inv(Psi_H)
        return gf.gf_matmul(
            np.concatenate([np.eye(a, dtype=np.uint8),
                            lam_f * np.eye(a, dtype=np.uint8)], axis=1),
            inv)

    def repair_ratio(self) -> float:
        """Repair bytes over naive k-shard copy: d / (k * alpha)."""
        return self.d / (self.k_nodes * self.alpha)


def interleave_split(data, k: int, alpha: int):
    """[k, L] file rows -> [k*alpha, L/alpha] virtual sub-rows.
    Works on numpy and jax arrays alike (pure reshape/swap)."""
    kk, L = data.shape
    assert kk == k and L % alpha == 0, (data.shape, k, alpha)
    return data.reshape(k, L // alpha, alpha).swapaxes(1, 2).reshape(
        k * alpha, L // alpha)


def interleave_merge(virt, m: int, alpha: int):
    """[m*alpha, S] virtual sub-rows -> [m, S*alpha] file rows."""
    rows, S = virt.shape
    assert rows == m * alpha, (virt.shape, m, alpha)
    return virt.reshape(m, alpha, S).swapaxes(1, 2).reshape(m, S * alpha)


class MSRFileCodec:
    """File-level MSR codec: k shard files in, n out.

    Wraps an inner RSCodecBase-style shell over PMMSRCode's virtual
    rows and owns the byte-interleave reshapes.  Propagates the inner
    backend's `_factory` / host nature so ops/dispatch routes the
    wrapped kernels exactly as it would the bare shell."""

    family = "msr"

    def __init__(self, inner, code: PMMSRCode | None = None):
        self.inner = inner
        self.code = code if code is not None else inner.code
        assert isinstance(self.code, PMMSRCode)
        self.k = self.code.k_nodes
        self.m = self.code.m_nodes
        self.n = self.code.n_nodes
        self.alpha = self.code.alpha
        factory = getattr(inner, "_factory", None)
        if factory is not None:
            self._factory = factory
        self.host_backend = getattr(inner, "host_backend", False)
        if hasattr(inner, "encode_units_linear"):
            # the fleet stream's surface (parallel/mesh.FleetUnitEncoder):
            # a unit's pieces hold rows of k file blocks and come back as
            # m file runs, split and merged in the mesh program
            self.unit_slots = inner.unit_slots
            self.place_units = inner.place_units
            self.encode_units_linear = functools.partial(
                inner.encode_units_linear, alpha=self.alpha)

    @property
    def tile(self) -> int:
        """The reconstruct seam's bucket unit, in file bytes: alpha times
        the shell's (its parity matrix's), so that the narrowest bucket is
        one tile of sub-row bytes."""
        return self.inner.tile * self.alpha

    def encode_parity_linear(self, spans, stripes: int) -> tuple:
        """`stripes` stripe rows of a `.dat`, k blocks wide, as 1-D arrays
        -> the m parity files' runs, m arrays of [W]: one program of the
        device shell (RSCodecBase.encode_parity_linear, `alpha`)."""
        return self.inner.encode_parity_linear(spans, stripes, self.alpha)

    def decode_basis(self, present, wanted: list[int]) -> tuple:
        """The k surviving files whose rows `reconstruct_stack` takes, in
        the order it takes them."""
        return tuple(self.decode_select(list(present), list(wanted)))

    def reconstruct_stack(self, stack, present, wanted: list[int],
                          linear: bool = False):
        """The `decode_basis(present, wanted)` files' rows -> the `wanted`
        files' rows, whole files in and out: the shell's decode over their
        sub-rows, [len(wanted) * alpha, k * alpha] (`linear`: 1-D in and
        out, split and merged in the program; else [k, W] -> [w, W])."""
        a = self.alpha
        rows = [f * a + j for f in self.decode_basis(present, wanted)
                for j in range(a)]
        want = [w * a + j for w in wanted for j in range(a)]
        if linear:
            return self.inner.reconstruct_stack(stack, rows, want, True, a)
        return interleave_merge(
            self.inner.reconstruct_stack(interleave_split(stack, self.k, a),
                                         rows, want), len(wanted), a)

    def encode_parity(self, data):
        """[k, L] data files -> [m, L] parity files (L % alpha == 0)."""
        virt = interleave_split(data, self.k, self.alpha)
        return interleave_merge(self.inner.encode_parity(virt),
                                self.m, self.alpha)

    def encode_parity_batch(self, units):
        """[U, k, L] -> [U, m, L]: a batch of `dispatch_parity_batch`'s
        host-array form, one stripe row of k file blocks a unit.  On a device shell with a
        linear apply each unit is one program that splits its rows into
        sub-rows and merges the product (a stripe row is
        `codec_base.stacked`'s third form; the eager [L / alpha, alpha]
        turn is the one a TPU's compiler takes minutes over); else the
        reshapes below round the inner batch kernel."""
        U, kk, L = units.shape
        a = self.alpha
        assert kk == self.k and L % a == 0, units.shape
        if not isinstance(units, np.ndarray) and \
                hasattr(self.inner, "encode_parity_linear"):
            import jax.numpy as jnp
            return jnp.stack([jnp.stack(self.inner.encode_parity_linear(
                units[u].reshape(-1), 1, a)) for u in range(U)])
        virt = units.reshape(U, self.k, L // a, a).swapaxes(2, 3).reshape(
            U, self.k * a, L // a)
        enc = getattr(self.inner, "encode_parity_batch", None)
        if enc is not None:
            pv = enc(virt)
        else:
            pv = np.stack([self.inner.encode_parity(virt[u])
                           for u in range(U)], axis=0)
        return pv.reshape(U, self.m, a, L // a).swapaxes(2, 3).reshape(
            U, self.m, L)

    def encode(self, data):
        parity = self.encode_parity(data)
        if isinstance(parity, np.ndarray):
            return np.concatenate([np.asarray(data), parity], axis=0)
        import jax.numpy as jnp
        return jnp.concatenate([jnp.asarray(data), parity], axis=0)

    def decode_select(self, available: list[int],
                      wanted: list[int]) -> list[int]:
        """File-level survivor choice: any k files decode (node-MDS)."""
        avail = sorted(set(available))
        if len(avail) < self.k:
            raise ValueError(f"msr: {len(avail)} survivors, need {self.k}")
        return avail[: self.k]

    def reconstruct(self, shards: dict, wanted: list[int] | None = None
                    ) -> dict:
        """File-level reconstruct: de-interleave survivors into virtual
        rows, run the inner shell, re-interleave the wanted files."""
        present = sorted(shards)
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        a = self.alpha
        use = self.decode_select(present, list(wanted))
        virt: dict = {}
        for sid in use:
            row = shards[sid]
            rows = interleave_split(row.reshape(1, -1), 1, a)
            for j in range(a):
                virt[sid * a + j] = rows[j]
        want_rows = [w * a + j for w in wanted for j in range(a)]
        out = self.inner.reconstruct(virt, want_rows)
        result = {}
        for w in wanted:
            stacked = np.stack(
                [np.asarray(out[w * a + j]) for j in range(a)], axis=0)
            result[w] = interleave_merge(stacked, 1, a)[0]
        return result

    # regen-facing passthroughs
    def repair_coeff(self, lost: int) -> np.ndarray:
        return self.code.repair_coeff(lost)

    def repair_matrix(self, lost: int, helpers: list[int]) -> np.ndarray:
        return self.code.repair_matrix(lost, helpers)


@functools.lru_cache(maxsize=8)
def get_code(k: int = DEFAULT_K, d: int = DEFAULT_D) -> PMMSRCode:
    return PMMSRCode(k, d)
