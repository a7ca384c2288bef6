"""CPU codec over the native C++ AVX2 GF(2^8) kernels (native.py).

The host-side twin of ops.gfmat_jax / ops.pallas_gf with the same
encode/reconstruct surface but numpy arrays in and out.  Fills the role
klauspost/reedsolomon's SIMD assembly plays in the reference (invoked from
weed/storage/erasure_coding/ec_encoder.go:214 enc.Encode and
weed/storage/store_ec.go:374 enc.ReconstructData): the fast path when no
TPU is attached.

Code-generic like codec_base: anything with k/m/n, `parity_matrix` and
`decode_matrix` plugs in; non-MDS codes steer survivor choice through
their `decode_select` hook.
"""

from __future__ import annotations

import collections

from seaweedfs_tpu import native
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import codec_base

import numpy as np


class NativeRSCodec:
    host_backend = True  # ops/dispatch computes it on the calling thread

    def __init__(self, code):
        self.code = code
        self.k, self.m, self.n = code.k, code.m, code.n
        self._decode_cache: collections.OrderedDict = collections.OrderedDict()

    def encode_parity(self, data: np.ndarray) -> np.ndarray:
        """[k, n] data -> [m, n] parity."""
        return native.gf_matmul(self.code.parity_matrix, np.asarray(data))

    def encode_parity_linear(self, spans, stripes: int,
                             alpha: int = 1) -> np.ndarray:
        """`stripes` stripe rows of a `.dat` as 1-D arrays that hold them
        one after the other, k blocks a row (`codec_base.stacked`'s third
        form) -> their parity as one `[m, W]` array, row i parity file i's
        contiguous run, W a row's bytes of one file: the device shells'
        `encode_parity_linear`, on the host.  The blocks are read by
        pointer where they lie (a `.dat`'s map), one `gf_matmul_ptrs` a
        stripe row, written straight into its slice of every run.
        `alpha` > 1 (a sub-packetised code: k and m files of alpha
        byte-interleaved sub-rows each) splits a row's blocks into their
        sub-rows and merges the product, copies on the host as in the
        device program."""
        k, m = self.k // alpha, self.m // alpha
        width = sum(s.nbytes for s in spans) // (stripes * k)
        blocks = [s[o:o + width] for s in spans
                  for o in range(0, len(s), width)]
        if len(blocks) != stripes * k:  # the kernel reads `width` a block
            raise ValueError(f"spans of {[len(s) for s in spans]} bytes: "
                             f"not {stripes} rows of {k} equal blocks")
        out = np.empty((m, stripes * width), dtype=np.uint8)
        mat = self.code.parity_matrix
        for r in range(stripes):
            row = blocks[r * k:(r + 1) * k]
            cut = slice(r * width, (r + 1) * width)
            if alpha == 1:
                native.gf_matmul_ptrs(mat, row, [o[cut] for o in out],
                                      width)
                continue
            sub = np.empty((k, alpha, width // alpha), dtype=np.uint8)
            for j, block in enumerate(row):
                sub[j] = block.reshape(-1, alpha).T
            virt = native.gf_matmul(mat, sub.reshape(k * alpha, -1))
            out[:, cut] = virt.reshape(m, alpha, -1).swapaxes(1, 2).reshape(
                m, width)
        return out

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.asarray(data)
        return np.concatenate([data, self.encode_parity(data)], axis=0)

    def reconstruct(self, shards: dict[int, np.ndarray],
                    wanted: list[int] | None = None) -> dict[int, np.ndarray]:
        """The `wanted` rows from survivor rows of n bytes each, read by
        pointer as they are given (a rebuild's views of the maps): one
        `gf_matmul_ptrs` over the basis rows, nothing stacked."""
        present = tuple(sorted(shards))
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        basis = codec_base.select_survivors(self.code, present, list(wanted))
        key = (basis, tuple(wanted))
        mat = self._decode_cache.get(key)
        if mat is None:
            mat = self.code.decode_matrix(list(present), list(wanted))
            self._decode_cache[key] = mat
            while len(self._decode_cache) > codec_base.decode_cache_cap():
                self._decode_cache.popitem(last=False)
        else:
            self._decode_cache.move_to_end(key)
        rows = [np.ascontiguousarray(shards[i], dtype=np.uint8)
                for i in basis]
        if len({r.shape for r in rows}) != 1 or rows[0].ndim != 1:
            raise ValueError(f"survivor rows of unequal or 2-D shapes: "
                             f"{sorted({r.shape for r in rows})}")
        out = np.empty((len(wanted), len(rows[0])), dtype=np.uint8)
        native.gf_matmul_ptrs(mat, rows, list(out), out.shape[1])
        return {w: out[i] for i, w in enumerate(wanted)}

_CODECS: dict = {}


def get_codec(k: int, m: int, construction: str = "vandermonde") -> NativeRSCodec:
    key = (k, m, construction)
    c = _CODECS.get(key)
    if c is None:
        c = NativeRSCodec(rs.get_code(k, m, construction))
        _CODECS[key] = c
    return c
