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
    host_backend = True  # dispatch.py routes through native.gf_matmul

    def __init__(self, code):
        self.code = code
        self.k, self.m, self.n = code.k, code.m, code.n
        self._decode_cache: collections.OrderedDict = collections.OrderedDict()

    def encode_parity(self, data: np.ndarray) -> np.ndarray:
        """[k, n] data -> [m, n] parity."""
        return native.gf_matmul(self.code.parity_matrix, np.asarray(data))

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.asarray(data)
        return np.concatenate([data, self.encode_parity(data)], axis=0)

    def reconstruct(self, shards: dict[int, np.ndarray],
                    wanted: list[int] | None = None) -> dict[int, np.ndarray]:
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
        stack = np.stack([np.asarray(shards[i]) for i in basis])
        out = native.gf_matmul(mat, stack)
        return {w: out[i] for i, w in enumerate(wanted)}


_CODECS: dict = {}


def get_codec(k: int, m: int, construction: str = "vandermonde") -> NativeRSCodec:
    key = (k, m, construction)
    c = _CODECS.get(key)
    if c is None:
        c = NativeRSCodec(rs.get_code(k, m, construction))
        _CODECS[key] = c
    return c
