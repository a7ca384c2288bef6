"""TPU-native GF(2^8) matrix codec via bit-slicing (XLA path).

Design (TPU-first, not a port): the reference crunches GF(2^8) with per-byte
SIMD table lookups (klauspost/reedsolomon AVX2, driven from
weed/storage/erasure_coding/ec_encoder.go:120-196). TPUs have no byte-LUT
unit, but they have an MXU. GF(2^8) is an 8-dim vector space over GF(2) and
multiplication by a constant is GF(2)-linear, so an RS coding matrix
C in GF(2^8)^{m x k} lifts to a 0/1 matrix B in {0,1}^{8m x 8k} with

    bits(C @ X) = (B @ bits(X)) mod 2.

Encode/decode/rebuild all become: unpack bytes to bit-planes, one int8
matmul on the MXU (values bounded by 8k <= 255, exact in int32/bf16-f32),
parity mask, repack. XLA fuses the unpack/mask/pack element-wise chains into
the matmul's prologue/epilogue; `ops.pallas_gf` does the same fully fused in
VMEM for the cases XLA schedules poorly.

Data layout: shards-major [k, n] uint8 — a stripe row of the EC layout
(weed/storage/erasure_coding/ec_locate.go block math) is exactly one such
matrix with n = block bytes. Batching stripes is vmap/reshape on n.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.ops import codec_base, gf

_SHIFTS = tuple(range(8))


def unpack_bits(x: jax.Array) -> jax.Array:
    """[k, n] uint8 -> [8k, n] int8 bit-planes; row 8j+s holds bit s of shard j."""
    k, n = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    bits = (x[:, None, :] >> shifts) & jnp.uint8(1)
    return bits.reshape(8 * k, n).astype(jnp.int8)


def pack_bits(y: jax.Array) -> jax.Array:
    """[8m, n] {0,1} -> [m, n] uint8; inverse of unpack_bits' layout."""
    m8, n = y.shape
    m = m8 // 8
    y = y.reshape(m, 8, n).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    return jnp.sum(y * weights, axis=1, dtype=jnp.uint8)


def bitsliced_apply_body(bitmat: jax.Array, data: jax.Array) -> jax.Array:
    """y[m, n] = (C @ data) over GF(2^8), with bitmat the [8m, 8k] int8 lift
    of C. Un-jitted body, shared by the single-device codec and the
    shard_map per-device functions in parallel/mesh.py."""
    xbits = unpack_bits(data)
    # int8 x int8 -> int32 rides the MXU's integer path on v5e; values are
    # 0/1 so the popcount-parity sum is exact.
    acc = jax.lax.dot_general(
        bitmat, xbits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    ybits = jax.lax.bitwise_and(acc, 1)
    return pack_bits(ybits)


@functools.partial(jax.jit, static_argnames=("linear", "stripes", "alpha"))
def _bitsliced_apply(bitmat: jax.Array, data, linear: bool = False,
                     stripes: int = 0, alpha: int = 1):
    """`linear`: 1-D in and out, laid out in this program
    (codec_base.stacked and unstacked; `stripes` rows of a `.dat`,
    `alpha` sub-rows a file)."""
    if linear:
        data = codec_base.stacked(data, bitmat.shape[1] // 8, stripes, alpha)
    out = bitsliced_apply_body(bitmat, data)
    return codec_base.unstacked(out, stripes, alpha) if linear else out


def bitsliced_apply_batch_body(bitmat: jax.Array, data: jax.Array
                               ) -> jax.Array:
    """[U, k, n] unit batch -> [U, m, n]: units are independent stripes,
    so the batch is one vmap of the bit-sliced apply (XLA batches the
    MXU dot over the leading dim).  Un-jitted, shared with the per-device
    shard_map bodies in parallel/mesh.py."""
    return jax.vmap(bitsliced_apply_body, in_axes=(None, 0))(bitmat, data)


_bitsliced_apply_batch = jax.jit(bitsliced_apply_batch_body)


class JaxGFMatrix:
    """A fixed GF(2^8) matrix, pre-lifted to its bit-matrix, applied on TPU."""

    def __init__(self, C: np.ndarray):
        self.C = np.asarray(C, dtype=np.uint8)
        self.m, self.k = self.C.shape
        # cast on the host: `jnp.asarray(..., dtype=)` builds a program
        # per matrix shape, on the first degraded read of each pattern
        self.bitmat = jnp.asarray(
            gf.gf_matrix_to_bitmatrix(self.C).astype(np.int8))

    def __call__(self, data, linear: bool = False,
                 stripes: int = 0, alpha: int = 1) -> jax.Array:
        """data [k, n] uint8 -> [m, n] uint8 product over GF(2^8)."""
        return _bitsliced_apply(self.bitmat, data, linear, stripes, alpha)

    def apply_batch(self, data: jax.Array) -> jax.Array:
        """data [U, k, n] -> [U, m, n] in one dispatch."""
        return _bitsliced_apply_batch(self.bitmat, data)


class JaxRSCodec(codec_base.RSCodecBase):
    """XLA bit-sliced RS codec: `RSCodecBase` over `JaxGFMatrix` applies."""

    # XLA takes any width; this only sets how coarse the reconstruct
    # seam's width buckets are (codec_base.bucket)
    tile = 32768

    def __init__(self, code):
        super().__init__(code, JaxGFMatrix)


@functools.lru_cache(maxsize=16)
def get_codec(k: int, m: int, construction: str = "vandermonde") -> JaxRSCodec:
    from seaweedfs_tpu.models import rs
    return JaxRSCodec(rs.get_code(k, m, construction))
