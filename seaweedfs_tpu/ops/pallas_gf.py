"""Fused Pallas TPU kernel for the bit-sliced GF(2^8) matmul.

The XLA path (`ops.gfmat_jax`) materialises the 8x bit-plane expansion in
HBM; this kernel keeps it in VMEM. Each grid step has a [k, TN] byte block
in VMEM, unpacks bit-planes there, runs one int8 MXU dot against the
pre-lifted coding matrix, folds parity-mask + repack into the epilogue, and
writes only the [m, TN] output bytes — HBM traffic is the
information-theoretic minimum.  Two programs feed that body (`_gf_body`):
a 2-D [k, n] array, or a linear input that `codec_base.stacked` lays out
as one, is read a [k, TN] tile a step by the BlockSpec (`_gf_apply`); an
encode unit's or a rebuild batch's 1-D pieces, as the seams put them, are
read where they lie, each step DMAing the k shards' TN-byte tiles from
their pieces and assembling the block in VMEM, and the parity is written
as the seam wants it, m runs or one array (`_gf_apply_in_place`, PR 39).

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
    """`linear`: 1-D in and out.  Rows put one by one (a tuple of 1-D
    pieces: an encode unit, a rebuild batch) whose block is a tile
    multiple (`in_place_block`) are read where they lie by the kernel
    itself (`_gf_apply_in_place`); any other linear input is laid out in
    this program (codec_base.stacked and unstacked; `stripes` rows of a
    `.dat`, whose width need be no tile multiple: the pad and the cut are
    in this program too; `alpha` > 1: the k and m rows are the
    byte-interleaved sub-rows of k / alpha and m / alpha files, split and
    merged here)."""
    cut = None
    if linear:
        block = None
        if alpha == 1 and isinstance(data, (tuple, list)):
            block = in_place_block(tuple(p.shape[0] for p in data), k,
                                   stripes, tile)
        if block is not None:
            return _gf_apply_in_place(bitmat, tuple(data), k, m, kpad, tile,
                                      block, stripes, interpret)
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


# The in-place path (PR 39).  A linear input that comes as pieces (an
# encode unit's stripe rows or column cut, a rebuild batch's survivor
# rows) is one stream of max(stripes, 1) rows of k blocks, the pieces
# cutting it in order (codec_base.stacked's first and third forms).
# Laid out [k, W] in HBM first, it cost 13 of a unit program's 15-17 ms
# on a v5e: a u8 [k, W] is tiled with four rows a 32-bit word, so
# writing one shard's row into it is a byte-strided scatter (PERF.md,
# PR 38).  Instead each grid step DMAs tile c of every shard from where
# it lies into VMEM (`_fetch_plan`), and the [k, tile] block `_gf_body`
# reads is assembled there: a tile's bytes as [tile / 512, 128] 32-bit
# words, shard j's run of them at rows j * (tile / 512) of a scratch,
# so one strided load takes a word row of every shard, [k, 128]; its
# four bytes go to four 128-column runs of the block (byte b of word row
# r to columns b * tile / 4 + r * 128).  The parity's columns come back
# in the same order into words, and each parity shard's tile is written
# where its run wants it.  The matrix apply is column-local, so columns
# in any order that is the same for every row give the same bytes.
# 4096: a tile of one shard fills [8, 128] words a whole number of times.
IN_PLACE_QUANTUM = 4096
LINE = 128  # bytes a row of a piece's [n / 128, 128] view: free on a TPU
# word rows a loop step of the assembly: its loop is unrolled by hand
# (Mosaic takes a fori_loop unrolled by 1 or whole)
IN_PLACE_UNROLL = 8


def in_place_block(lengths: tuple, k: int, stripes: int,
                   tile: int) -> int | None:
    """The block of a linear input put as pieces of `lengths` bytes, if
    `_gf_apply` reads it where it lies: max(stripes, 1) rows of k blocks
    in all, every piece whole blocks, the block a multiple of the tile
    and the tile of IN_PLACE_QUANTUM.  None: the input is laid out by
    codec_base.stacked."""
    rows = max(stripes, 1)
    total = sum(lengths)
    if tile % IN_PLACE_QUANTUM or not total or total % (k * rows):
        return None
    block = total // (k * rows)
    if block % tile or any(n % block for n in lengths):
        return None
    return block


def _fetch_plan(lengths: tuple, k: int, rows: int) -> tuple:
    """-> ((piece, row, first shard, shards, first block of the piece's
    that they are), ...): for each stripe row, the pieces its k blocks
    lie in, a run of consecutive shards in each (one piece a shard in a
    column cut or a rebuild batch, one a row in a unit of rows)."""
    plan, first = [], 0
    for q, n in enumerate(lengths):
        blocks = range(first, first + n * rows * k // sum(lengths))
        for r in range(rows):
            js = [j for j in range(k) if r * k + j in blocks]
            if js:
                plan.append((q, r, js[0], len(js), r * k + js[0] - first))
        first = blocks.stop
    return tuple(plan)


def _in_place_kernel(bitmat_ref, *refs, k: int, m: int, kpad: int,
                     tile: int, per_row: int, stripe_rows: int, plan: tuple,
                     pieces_n: int, runs: bool):
    pieces = refs[:pieces_n]
    outs = refs[pieces_n:pieces_n + (m if runs else 1)]
    fetched, sem, words, x, y, ywords = refs[pieces_n + len(outs):]
    lines, rows = tile // LINE, tile // (4 * LINE)  # a tile of one shard
    quarter = tile // 4
    step, steps = pl.program_id(0), pl.num_programs(0)

    def copies(c, slot, act):
        """`act` on the DMAs of tile c of every shard into `slot`: a run
        of shards' tiles from a piece is one DMA, strided a block apart."""
        for q, r, j, n, b in plan:
            def each(q=q, j=j, n=n, b=b):
                at = pl.multiple_of((c % per_row) * lines, lines)
                act(pltpu.make_async_copy(
                    pieces[q].at[pl.ds(b, n), pl.ds(at, lines)],
                    fetched.at[slot, pl.ds(j, n)], sem.at[slot]))
            if stripe_rows == 1:
                each()
            else:
                pl.when(c // per_row == r)(each)

    slot = step % 2

    @pl.when(step == 0)
    def _():
        copies(step, 0, lambda d: d.start())

    @pl.when(step + 1 < steps)
    def _():
        copies(step + 1, 1 - slot, lambda d: d.start())

    copies(step, slot, lambda d: d.wait())
    for j in range(k):
        words[pl.ds(j * rows, rows), :] = pltpu.bitcast(fetched[slot, j],
                                                        jnp.uint32)

    def columns(b, r):
        return pl.ds(pl.multiple_of(b * quarter + r * LINE, LINE), LINE)

    def gather(r0, carry):
        for u in range(IN_PLACE_UNROLL):
            r = r0 * IN_PLACE_UNROLL + u
            row = words[pl.ds(r, k, stride=rows), :]  # [k, 128] words
            for b in range(4):
                x[:, columns(b, r)] = ((row >> (8 * b)) & 0xFF).astype(
                    jnp.uint8)
        return carry

    def scatter(r0, carry):
        for u in range(IN_PLACE_UNROLL):
            r = r0 * IN_PLACE_UNROLL + u
            row = y[:, columns(0, r)].astype(jnp.uint32)
            for b in range(1, 4):
                row |= y[:, columns(b, r)].astype(jnp.uint32) << (8 * b)
            ywords[pl.ds(r, m, stride=rows), :] = row
        return carry

    jax.lax.fori_loop(0, rows // IN_PLACE_UNROLL, gather, 0)
    y[...] = _gf_body(bitmat_ref[...], x[...], k=k, m=m, kpad=kpad)
    jax.lax.fori_loop(0, rows // IN_PLACE_UNROLL, scatter, 0)
    for i in range(m):
        tile_i = pltpu.bitcast(ywords[pl.ds(i * rows, rows), :], jnp.uint8)
        if runs:
            outs[i][...] = tile_i
        else:
            outs[0][i] = tile_i


def _gf_apply_in_place(bitmat, pieces: tuple, k: int, m: int, kpad: int,
                       tile: int, block: int, stripes: int,
                       interpret: bool):
    """The kernel of a linear input that `in_place_block` takes, as the
    one program: the pieces go in as they were put (a 1-D u8 array's
    [blocks, block / 128, 128] view is the same bytes on a TPU), each grid
    step fetches its k tiles (`_in_place_kernel`), and the parity goes out
    as `unstacked` gives it, written by the kernel where it lies: m runs
    of [W] for an encode unit (`stripes` >= 1), one [m * W] for a
    decode."""
    rows = max(stripes, 1)
    width, lines = rows * block, tile // LINE
    runs = stripes > 0
    vma = jax.typeof(pieces[0]).vma
    if runs:
        out_specs = [pl.BlockSpec((lines, LINE), lambda c: (c, 0))] * m
        out_shape = [jax.ShapeDtypeStruct((width // LINE, LINE), jnp.uint8,
                                          vma=vma)] * m
    else:
        out_specs = pl.BlockSpec((m, lines, LINE), lambda c: (0, c, 0))
        out_shape = jax.ShapeDtypeStruct((m, width // LINE, LINE), jnp.uint8,
                                         vma=vma)
    lengths = tuple(p.shape[0] for p in pieces)
    kernel = functools.partial(
        _in_place_kernel, k=k, m=m, kpad=kpad, tile=tile,
        per_row=block // tile, stripe_rows=rows,
        plan=_fetch_plan(lengths, k, rows),
        pieces_n=len(pieces), runs=runs)
    words = tile // (4 * LINE)
    out = pl.pallas_call(
        kernel,
        grid=(width // tile,),
        in_specs=[pl.BlockSpec((8 * m, 8 * kpad), lambda c: (0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pieces),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, k, lines, LINE), jnp.uint8),  # fetched tiles
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((k * words, LINE), jnp.uint32),  # ... as words
            pltpu.VMEM((k, tile), jnp.uint8),  # the block _gf_body reads
            pltpu.VMEM((m, tile), jnp.uint8),  # what it gives
            pltpu.VMEM((m * words, LINE), jnp.uint32),  # ... as words
        ],
        # a step starts the next one's fetch: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=GF_APPLY,
    )(bitmat, *(p.reshape(-1, block // LINE, LINE) for p in pieces))
    if runs:
        return tuple(o.reshape(-1) for o in out)
    return out.reshape(-1)


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

    def in_place(self, lengths, stripes: int = 0) -> bool:
        """Whether a linear call (`alpha` 1) on pieces of `lengths` bytes
        reads them where they lie (`_gf_apply`'s own test): what the
        dispatch seam counts as `in_place`."""
        return in_place_block(tuple(lengths), self.k, stripes,
                              self.tile) is not None

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
