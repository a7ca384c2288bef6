"""Fused Pallas TPU kernel for the bit-sliced GF(2^8) matmul.

The XLA path (`ops.gfmat_jax`) materialises the 8x bit-plane expansion in
HBM; this kernel keeps it in VMEM. Each grid step DMAs a [k, TN] byte tile,
unpacks bit-planes in VMEM, runs one int8 MXU dot against the pre-lifted
coding matrix, folds parity-mask + repack into the epilogue, and writes only
the [m, TN] output bytes — HBM traffic is the information-theoretic minimum.

Throughput: not measured on current code (PERF.md keeps what the chip
has shown).  The CPU comparison point is the klauspost/reedsolomon AVX2
scheme driven by weed/storage/erasure_coding/ec_encoder.go.

Kernel-shape notes (why it looks the way it does):
- Bit extraction is `(x & (1<<s)) != 0`: Mosaic has no 8-bit shifts
  (`arith.shrui` on i8 fails to legalize) but and/cmp/select are native and
  uint8 lanes are 4x-packed, so this is the cheapest unpack.
- Bit-planes are *plane-major* (all of bit s for every shard, then bit s+1)
  and each plane is padded to PLANE_PAD=16 sublanes — half of int8's
  native (32, 128) tile.  Mosaic (libtpu 0.0.34) accepts the 8-way
  concatenation of those blocks; tests/test_tpu_aot.py compiles it for a
  v5e on every tier-1 run.  The coding bit-matrix gets matching zero
  columns (extra MXU work on zeros).
- The dot is int8 x int8 -> int32: 0/1 operands, sums bounded by 8k <= 128,
  exact. preferred_element_type=int8 trips a Mosaic verifier bug; int32 also
  keeps the <<r repack shifts legal (no 8-bit shifts, see above).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seaweedfs_tpu.ops import codec_base, gf

DEFAULT_TILE = 32768  # interpreter/CPU default: small pads for small inputs
TPU_TILE = 131072  # the served tile on a chip; which candidate is fastest
#                    is not measured on current code
PLANE_PAD = 16  # sublane alignment for each bit-plane block
# What one grid step of the body may hold in VMEM: its bit planes,
# [8 * kpad, tile] int8, and its accumulator, [8 * m, tile] int32.  32 MiB
# is what the widest matrix the kernel served before PM-MSR, [4, 10] (and
# [4, 12]: kpad 16 either way), holds at TPU_TILE, 16 MiB of each, so
# every RS and LRC matrix keeps the platform's tile; [72, 72] (kpad 80)
# would hold 80 + 288 MiB there, more than the chip has, and takes 8192,
# which costs it nothing: the MXU bounds that kernel, and on a v5e it runs
# the same 4.7-4.9 ms a 144 MiB unit at 4096 and at 8192 (PERF.md, PR 32).
VMEM_BUDGET = 32 << 20


def resolved_tile(tile: int | None = None) -> int:
    """The widest tile a codec will use: the explicit argument, else one
    constant a platform (`matrix_tile` narrows it for a matrix whose body
    would not fit at that width)."""
    if tile is not None:
        return tile
    return TPU_TILE if jax.default_backend() == "tpu" else DEFAULT_TILE


def matrix_tile(m: int, kpad: int, widest: int) -> int:
    """The tile of an [m, k] matrix's kernel: `widest` halved until the
    body's planes and accumulator (8 * kpad + 32 * m bytes a column) fit
    VMEM_BUDGET.  A rule of the matrix alone, so that one code's matrices
    can differ: under PM-MSR(9,16) on a TPU the [72, 72] parity takes
    8192, a one-lost [8, 72] decode 32768, the repair's [8, 16] 65536 and
    its [1, 8] the platform's 131072."""
    tile = widest
    while tile > 128 and tile * (8 * kpad + 32 * m) > VMEM_BUDGET:
        tile //= 2
    return tile


def gf_matrix_to_bitmatrix_planemajor(C: np.ndarray, kpad: int | None = None) -> np.ndarray:
    """[m,k] GF(2^8) matrix -> [8m, 8*kpad] 0/1 matrix, plane-major:
    out[r*m + i, s*kpad + j] = bit r of (C[i,j] * 2^s); columns j >= k are 0.
    """
    C = np.asarray(C, dtype=np.uint8)
    m, k = C.shape
    if kpad is None:
        kpad = k
    assert kpad >= k
    out = np.zeros((8 * m, 8 * kpad), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            M = gf.gf_mul_bitmatrix(int(C[i, j]))  # [bit r, plane s]
            for r in range(8):
                for s in range(8):
                    out[r * m + i, s * kpad + j] = M[r, s]
    return out


def _gf_body(bitmat, x, *, k: int, m: int, kpad: int):
    """The fused unpack -> MXU dot -> repack body on VMEM-resident arrays:
    x is one [k, TN] uint8 tile, bitmat the [8m, 8*kpad] plane-major lift."""
    zpad = jnp.zeros((kpad - k, x.shape[1]), jnp.int8)
    planes = []
    for s in range(8):
        p = ((x & jnp.uint8(1 << s)) != 0).astype(jnp.int8)
        planes.append(p if kpad == k else jnp.concatenate([p, zpad], axis=0))
    xbits = jnp.concatenate(planes, axis=0)  # [8*kpad, TN] int8 0/1
    acc = jnp.dot(bitmat, xbits, preferred_element_type=jnp.int32)
    acc = acc & 1  # [8m, TN] parity bits, plane-major
    byte = acc[0:m]
    for r in range(1, 8):
        byte = byte | (acc[r * m : (r + 1) * m] << r)
    return byte.astype(jnp.uint8)


def _gf_apply_kernel(bitmat_ref, x_ref, o_ref, *, k: int, m: int, kpad: int):
    o_ref[:] = _gf_body(bitmat_ref[:], x_ref[:], k=k, m=m, kpad=kpad)


# What a device trace calls the two kernels.  XLA names a Mosaic custom
# call after the innermost scope round its pallas_call (`%_gf_apply.1 =
# ... custom-call(...)` on the `XLA Ops` line) and a program after the
# jitted function (`jit__gf_apply(<hash>)` on `XLA Modules`).  Until
# these were pinned both followed from what a Python function happened to
# be called; now `name=` on the pallas_call and `codec_base.named_jit`
# (here and round the mesh encoders' `batch_body`) say it, and
# renaming a function here renames nothing on the trace.  The benchmark
# sums kernel time by these names (benchmark/kernels.json).
GF_APPLY = "_gf_apply"
GF_APPLY_BATCH = "_gf_apply_batch"


@codec_base.named_jit(GF_APPLY, static_argnames=("k", "m", "kpad", "tile",
                                                 "interpret", "linear",
                                                 "stripes", "alpha"))
def _gf_apply(bitmat: jax.Array, data, k: int, m: int, kpad: int,
              tile: int, interpret: bool, linear: bool = False,
              stripes: int = 0, alpha: int = 1):
    """`linear`: 1-D in and out, laid out in this program
    (codec_base.stacked and unstacked; `stripes` rows of a `.dat`, whose
    width need be no tile multiple: the pad and the cut are in this
    program too; `alpha` > 1: the k and m rows are the byte-interleaved
    sub-rows of k / alpha and m / alpha files, split and merged here)."""
    cut = None
    if linear:
        data = codec_base.stacked(data, k, stripes, alpha)
        if data.shape[1] % tile:
            cut = data.shape[1]
            data = jnp.pad(data, ((0, 0), (0, -cut % tile)))
    _, n = data.shape
    assert n % tile == 0, (n, tile)
    kernel = functools.partial(_gf_apply_kernel, k=k, m=m, kpad=kpad)
    out = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((8 * m, 8 * kpad), lambda i: (0, 0)),  # VMEM-resident
            pl.BlockSpec((k, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, tile), lambda i: (0, i)),
        # vma: inside the mesh encoders' shard_map the output varies over
        # the same mesh axes as the data block (empty outside one)
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8,
                                       vma=jax.typeof(data).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=GF_APPLY,
    )(bitmat, data)
    if cut is not None:
        out = out[:, :cut]
    return codec_base.unstacked(out, stripes, alpha) if linear else out


def _gf_apply_batch_kernel(bitmat_ref, x_ref, o_ref, *, k: int, m: int,
                           kpad: int):
    # block shapes carry a leading unit-batch dim of 1; squeeze it through
    # the same fused body
    o_ref[0] = _gf_body(bitmat_ref[:], x_ref[0], k=k, m=m, kpad=kpad)


@codec_base.named_jit(GF_APPLY_BATCH,
                      static_argnames=("k", "m", "kpad", "tile", "interpret"))
def _gf_apply_batch(bitmat: jax.Array, data: jax.Array, k: int, m: int,
                    kpad: int, tile: int, interpret: bool) -> jax.Array:
    """Unit-batch geometry: [U, k, n] -> [U, m, n] in ONE pallas_call with
    a (U, n//tile) grid — the fleet-conversion stream encodes a whole
    interleaved multi-volume unit batch per dispatch instead of paying a
    kernel launch (and a host round-trip through the dispatch seam) per
    unit.  Both grid axes are parallel: units are independent stripes and
    the GF matmul is column-local."""
    U, _, n = data.shape
    assert n % tile == 0, (n, tile)
    kernel = functools.partial(_gf_apply_batch_kernel, k=k, m=m, kpad=kpad)
    return pl.pallas_call(
        kernel,
        grid=(U, n // tile),
        in_specs=[
            pl.BlockSpec((8 * m, 8 * kpad), lambda u, i: (0, 0)),
            pl.BlockSpec((1, k, tile), lambda u, i: (u, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, m, tile), lambda u, i: (u, 0, i)),
        out_shape=jax.ShapeDtypeStruct((U, m, n), jnp.uint8,
                                       vma=jax.typeof(data).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=GF_APPLY_BATCH,
    )(bitmat, data)


class PallasGFMatrix:
    """Fixed GF(2^8) matrix applied via the fused kernel.

    Pads the byte-column count up to the tile size internally, at the
    price of a pad and a slice program per width: served callers feed
    tile-aligned spans (the EC block sizes — 1GB/1MB, reference
    weed/storage/erasure_coding/ec_encoder.go:21-22 — are all
    tile-multiples, and the reconstruct seam pads on the host to one of
    a few widths, codec_base.bucket); regen's small applies and tests
    come unaligned.

    The kernel is compiled for the chip.  `interpret=True` runs it under
    the Pallas interpreter instead and is for tests only: off-TPU nothing
    selects it implicitly, so a host with no chip raises here rather than
    serving from the emulator.
    """

    def __init__(self, C: np.ndarray, tile: int | None = None,
                 interpret: bool = False):
        if not interpret and jax.default_backend() != "tpu":
            raise RuntimeError(
                "the Pallas GF(2^8) kernel compiles only for a TPU and the "
                f"JAX backend found is {jax.default_backend()!r}; use "
                "WEEDTPU_EC_CODEC=auto|cpp|jax on this host")
        self.C = np.asarray(C, dtype=np.uint8)
        self.m, self.k = self.C.shape
        self.kpad = max(PLANE_PAD, -(-self.k // PLANE_PAD) * PLANE_PAD)
        self.tile = matrix_tile(self.m, self.kpad, resolved_tile(tile))
        self.interpret = bool(interpret)
        # cast on the host: `jnp.asarray(..., dtype=)` builds a program
        # per matrix shape, on the first degraded read of each pattern
        self.bitmat = jnp.asarray(gf_matrix_to_bitmatrix_planemajor(
            self.C, self.kpad).astype(np.int8))

    def __call__(self, data, linear: bool = False,
                 stripes: int = 0, alpha: int = 1) -> jax.Array:
        if linear:  # the seams': laid out, and padded where need be, inside
            return _gf_apply(self.bitmat, data, self.k, self.m, self.kpad,
                             self.tile, self.interpret, True, stripes, alpha)
        k, n = data.shape
        assert k == self.k, (k, self.k)
        pad = (-n) % self.tile
        if pad:
            data = jnp.pad(data, ((0, 0), (0, pad)))
        out = _gf_apply(self.bitmat, data, self.k, self.m, self.kpad,
                        self.tile, self.interpret)
        return out[:, :n] if pad else out

    def apply_batch(self, data: jax.Array) -> jax.Array:
        """[U, k, n] unit batch -> [U, m, n] parity in one kernel launch
        (grid over units x column tiles)."""
        U, k, n = data.shape
        assert k == self.k, (k, self.k)
        pad = (-n) % self.tile
        if pad:
            data = jnp.pad(data, ((0, 0), (0, 0), (0, pad)))
        out = _gf_apply_batch(self.bitmat, data, self.k, self.m, self.kpad,
                              self.tile, self.interpret)
        return out[:, :, :n] if pad else out


class PallasRSCodec(codec_base.RSCodecBase):
    """Fused-kernel RS codec: `RSCodecBase` over `PallasGFMatrix` applies."""

    def __init__(self, code, tile: int | None = None,
                 interpret: bool = False):
        super().__init__(
            code, lambda C: PallasGFMatrix(C, tile, interpret))
        self.tile = self._parity.tile
        self.interpret = self._parity.interpret


@functools.lru_cache(maxsize=16)
def _get_codec_cached(k: int, m: int, construction: str,
                      tile: int) -> PallasRSCodec:
    from seaweedfs_tpu.models import rs
    return PallasRSCodec(rs.get_code(k, m, construction), tile)


def get_codec(k: int, m: int, construction: str = "vandermonde",
              tile: int | None = None) -> PallasRSCodec:
    """tile=None resolves per backend: the big TPU tile for real chips,
    the small default under the (CPU) interpreter where column padding to
    the tile width is pure waste."""
    return _get_codec_cached(k, m, construction, resolved_tile(tile))
