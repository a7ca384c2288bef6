"""Shared codec shell: encode/reconstruct orchestration over a
matrix-apply backend (XLA bit-sliced or fused Pallas).

Survivor selection and decode-matrix caching live here once so the two
device backends cannot diverge. The TPU analogue of the reference's
enc.Encode / enc.Reconstruct pair (weed/storage/erasure_coding/
ec_encoder.go:214,267-277; weed/storage/store_ec.go:374-393).

Codec-generic: any code object exposing k/m/n, `parity_matrix` and
`decode_matrix(available, wanted)` plugs in — RS, LRC and the MSR
inner code all ride the same shell.  Non-MDS codes additionally expose
`decode_select(available, wanted)`, which names the survivor basis the
decode matrix's columns follow (RS semantics — first k sorted
survivors — are the default when the hook is absent).
"""

from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np


def named_jit(name: str, **jit_kwargs):
    """`jax.jit` of a function under an explicit name: the XLA module —
    and its event on a device trace's `XLA Modules` line — is
    `jit_<name>` whatever the Python function is called."""
    def deco(fn):
        @functools.wraps(fn)
        def named(*args, **kwargs):
            return fn(*args, **kwargs)
        named.__name__ = named.__qualname__ = name
        return jax.jit(named, **jit_kwargs)
    return deco


def decode_cache_cap() -> int:
    """LRU bound for per-(survivors, wanted) decode matrices.  Churny
    failure patterns multiplied by the codec family's larger key space
    (LRC bases vary per loss pattern, MSR keys are virtual-row tuples)
    would otherwise grow the cache without limit."""
    try:
        return max(1, int(os.environ.get("WEEDTPU_CODEC_DECODE_CACHE", "64")))
    except ValueError:
        return 64


# A reconstruct runs at one of a few widths, not at its row length: XLA and
# Mosaic build a program per shape, and a degraded read's length is its
# needle's.  The widths are the tile times 1, 2, 4 ... 2^(BUCKETS-1), and
# past the largest its multiples (a rebuild batch is one, so it is put up
# as it is).  The columns added are zero and cost nothing but the bytes:
# a GF(2^8) matrix apply is column-local.
BUCKETS = 8


def bucket(n: int, tile: int) -> int:
    """The width a reconstruct of `n` byte columns runs at under a codec
    of this `tile`: >= n, a tile multiple, under 2 x max(n, tile), and one
    of BUCKETS values up to tile << (BUCKETS - 1)."""
    top = tile << (BUCKETS - 1)
    if n > top:
        return -(-n // top) * top
    return tile << max(0, -(-n // tile) - 1).bit_length()


def select_survivors(code, present: tuple, wanted: list[int]) -> tuple:
    """The survivor basis a decode matrix is built against: the code's
    `decode_select` when it has one, else the MDS default of the first
    k sorted survivors."""
    sel = getattr(code, "decode_select", None)
    if sel is not None:
        return tuple(sel(list(present), list(wanted)))
    return tuple(present[: code.k])


def stacked(data, k: int, stripes: int = 0, alpha: int = 1) -> jax.Array:
    """Inside a program: the [k, W] stack of an input in *linear* form.
    The runtime moves a 1-D array between host and device as it lies; a
    2-D uint8 one goes through a relayout on the host, in both directions,
    that costs more than the transfer (a `[10, 16 MiB]` put 48 ms against
    16 for its ten rows, a `[1, 16 MiB]` copy back 88 ms against 6;
    PERF.md, PR 26).  So the reconstruct seam and, since PR 31, the encode
    seam move only 1-D arrays, and the program that applies the matrix
    lays them out: `linear=True` on a matrix apply is this on the way in
    and `unstacked` on the way out.  Three forms come in:

      the k rows as a tuple of [W] arrays (a rebuild batch, row by row
      from the shard files' maps);
      the k rows one after the other in one [k * W] array (a degraded
      read's stack);
      `stripes` = R >= 1 stripe rows as they lie in a `.dat`, row-major:
      1-D pieces (one array, or a tuple put piece by piece) that hold,
      one after the other, R rows of k blocks of `small` bytes each.
      Block j of every row is shard j's, so [R, k, small] becomes
      [k, R * small] (an encode unit: W = R * small contiguous bytes of
      each shard file).

    `alpha` > 1 (a sub-packetised code: PM-MSR, ops/msr.py): the matrix
    works on k *virtual* rows, alpha a shard file, and what comes in, in
    any of the three forms, is the k / alpha files' rows.  Sub-row a of a
    file is its byte set {t * alpha + a}, so each [W] file row is split
    into its alpha sub-rows of [W / alpha] here (`_subrows`), a
    byte-granular transpose inside the program where the host would copy
    every byte.

    Since PR 39 the Pallas apply lays out only what its kernel cannot
    read where it lies (`pallas_gf.in_place_block`): a rebuild batch's
    rows and an encode unit's pieces whose block is a tile multiple go
    into the kernel as they were put, so this is what stacks a degraded
    read's one array, a sub-packetised code's rows (`alpha` > 1), a
    block that is no tile multiple (tests' tiny ones), the XLA shell's
    every linear input, and, in its third form, the fleet's mesh
    program's units (parallel/mesh.py).  On a v5e a u8 [k, W] is tiled
    four rows a 32-bit word, so laying one out costs a strided write of
    every byte (ten 16 MiB rows: 13 ms, PERF.md, PR 38)."""
    files = k // alpha
    if isinstance(data, (tuple, list)):
        data = jnp.concatenate(data) if stripes else jnp.stack(data, axis=0)
    if stripes:
        data = data.reshape(stripes, files, -1).transpose(1, 0, 2)
    data = data.reshape(files, -1)
    return data if alpha == 1 else _subrows(data, alpha)


def unstacked(out: jax.Array, stripes: int = 0, alpha: int = 1):
    """Inside a program: the [m, W] product of a *linear* apply as it goes
    back.  A decode's rows go one after the other in one [m * W] array (a
    rebuild batch loses one or two shards: 16 or 32 MiB).  An encode
    unit's (`stripes` >= 1, see `stacked`) go as m arrays of [W], one
    contiguous run of each parity shard's file: the copy back lands in
    memory the host's allocator hands out, which maps an array over 32 MiB
    afresh every time and pays its first touch (TPU v5e's host: one
    64 MiB array back in 71-79 ms, four of 16 MiB in 6.5; PERF.md,
    PR 31).  `alpha` > 1: the m virtual rows are merged back into the
    bytes of m / alpha files first (`stacked`'s split, undone).  Where
    the Pallas kernel read its input in place it writes these forms
    itself (PR 39); this cuts the [m, W] of every input `stacked` laid
    out."""
    if alpha > 1:
        out = _files(out, alpha)
    return tuple(out) if stripes else out.reshape(-1)


# Sub-row a of a file is its bytes {t * alpha + a}: [W / alpha, alpha]
# turned round, whose minor dimension of alpha = 8 bytes the TPU's compiler
# lays out 16 times as wide as it is and takes minutes over (v5e, compiled
# with no chip: an encode unit's split alone 227 s and 2.4 GB of
# temporaries).  A matrix apply is column-local, so the sub-rows' columns
# may stand in any order that `_files` undoes: the file's bytes as
# [LANES, W / LANES] turned round whole, a plain 2-D transpose that leaves
# the alpha sub-rows of a stretch of the file under one another in runs of
# LANES columns, then those runs gathered by sub-row with the minor
# dimension left alone (column q * LANES + p of a sub-row is the file's
# column p * (W / alpha / LANES) + q).  The barrier keeps the compiler from
# folding the two steps back into the one it cannot do.
LANES = 128


def _subrows(data: jax.Array, alpha: int) -> jax.Array:
    """[files, W] -> [files * alpha, W / alpha]: each file's alpha
    byte-interleaved sub-rows under one another, their columns in the
    order above where W allows it and in the file's own order else."""
    files, width = data.shape
    if width % (alpha * LANES):
        return data.reshape(files, -1, alpha).swapaxes(1, 2).reshape(
            files * alpha, -1)
    turned = jax.lax.optimization_barrier(
        data.reshape(files, LANES, -1).swapaxes(1, 2))
    return turned.reshape(files, -1, alpha, LANES).swapaxes(1, 2).reshape(
        files * alpha, -1)


def _files(rows: jax.Array, alpha: int) -> jax.Array:
    """`_subrows`, undone: [m, W / alpha] sub-rows -> [m / alpha, W]."""
    files, sub = rows.shape[0] // alpha, rows.shape[1]
    if sub % LANES:
        return rows.reshape(files, alpha, sub).swapaxes(1, 2).reshape(
            files, -1)
    turned = jax.lax.optimization_barrier(
        rows.reshape(files, alpha, -1, LANES).swapaxes(1, 2).reshape(
            files, -1, LANES))
    return turned.swapaxes(1, 2).reshape(files, -1)


class RSCodecBase:
    """Encode / reconstruct for one fixed-matrix GF(2^8) code.

    `matrix_apply_factory(C) -> callable([k, n] bytes, linear=False,
    stripes=0, alpha=1) -> [m, n] bytes` (`linear`: 1-D in and out,
    `stripes` rows of a `.dat` in and m runs out, `alpha` sub-rows a file
    split and merged, see `stacked` and `unstacked`) supplies the device
    kernel for a fixed GF(2^8) matrix C.
    """

    def __init__(self, code, matrix_apply_factory):
        self.code = code
        self.k, self.m, self.n = code.k, code.m, code.n
        self._factory = matrix_apply_factory
        self._parity = matrix_apply_factory(code.parity_matrix)
        self._decode_cache: collections.OrderedDict = collections.OrderedDict()

    def _cached_decode(self, present: tuple, wanted: tuple):
        """(basis, lifted matrix) for a survivor/wanted pattern, LRU-bounded
        by WEEDTPU_CODEC_DECODE_CACHE."""
        basis = select_survivors(self.code, present, list(wanted))
        key = (basis, wanted)
        hit = self._decode_cache.get(key)
        if hit is not None:
            self._decode_cache.move_to_end(key)
            return basis, hit
        mat = self._lift(self.code.decode_matrix(list(present), list(wanted)))
        self._decode_cache[key] = mat
        while len(self._decode_cache) > decode_cache_cap():
            self._decode_cache.popitem(last=False)
        return basis, mat

    def _lift(self, C):
        return self._factory(C)

    def encode_parity(self, data: jax.Array) -> jax.Array:
        """[k, n] data -> [m, n] parity (systematic: data shards unchanged)."""
        return self._parity(data)

    def encode_parity_linear(self, spans, stripes: int,
                             alpha: int = 1) -> tuple:
        """`stripes` stripe rows of a `.dat` as 1-D arrays (`stacked`'s
        third form) -> their parity as m / alpha arrays of [W], W the
        rows' bytes of one shard (`unstacked`): the layout, the parity
        apply and the split are one program, and only 1-D arrays cross."""
        return self._parity(spans, True, stripes, alpha)

    def in_place(self, lengths, stripes: int = 0, present=None,
                 wanted=None) -> bool:
        """Whether the linear apply of an encode unit (`stripes` >= 1) or
        of the decode of `wanted` from `present` reads the 1-D pieces it is
        handed, of `lengths` bytes, where they lie instead of through
        `stacked` (the matrix apply's `in_place`; False for a backend
        that has none)."""
        mat = self._parity if wanted is None else \
            self._cached_decode(tuple(sorted(present)), tuple(wanted))[1]
        test = getattr(mat, "in_place", None)
        return bool(test is not None and test(lengths, stripes))

    def encode_parity_batch(self, units: jax.Array) -> jax.Array:
        """[U, k, n] unit batch -> [U, m, n] parity in ONE device dispatch
        — the fleet-conversion fast path.  Backends whose matrix apply
        has a fused batch kernel (Pallas grid over units, XLA vmap) use
        it; anything else falls back to per-unit applies."""
        batched = getattr(self._parity, "apply_batch", None)
        if batched is not None:
            return batched(units)
        return jnp.stack([self._parity(units[u])
                          for u in range(units.shape[0])], axis=0)

    def encode(self, data: jax.Array) -> jax.Array:
        """[k, n] data -> [k+m, n] shards."""
        return jnp.concatenate([data, self.encode_parity(data)], axis=0)

    def decode_basis(self, present, wanted: list[int]) -> tuple:
        """The survivors whose rows `reconstruct_stack` takes, in the
        order it takes them (first k sorted for MDS codes, the
        decode_select choice otherwise)."""
        return select_survivors(self.code, tuple(sorted(present)),
                                list(wanted))

    def reconstruct_stack(self, stack, present, wanted: list[int],
                          linear: bool = False, alpha: int = 1) -> jax.Array:
        """[len(basis), W] survivor rows in `decode_basis(present, wanted)`
        order -> the [len(wanted), W] rebuilt rows: the cached decode
        matrix applied to the stack as it is, one program and nothing
        else on the device.  The matrix is cached per (basis, wanted)
        pattern since failure patterns are few in practice; W is the
        caller's to keep to a few values (`bucket`).

        `linear`: the stack comes, and the rows go back, as 1-D arrays
        (`stacked`; `alpha` > 1: as the rows of len(basis) / alpha and
        len(wanted) / alpha files, `present` and `wanted` naming their
        sub-rows)."""
        _, mat = self._cached_decode(tuple(sorted(present)), tuple(wanted))
        return mat(stack, linear, 0, alpha)

    def reconstruct(self, shards: dict[int, jax.Array],
                    wanted: list[int] | None = None) -> dict[int, jax.Array]:
        """Rebuild missing shards from a dict of sufficient survivor rows:
        `reconstruct_stack` for callers that hold rows one by one (the
        MSR file codec's virtual rows, tests).  The stack is built on the
        side the rows live on."""
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        rows = [shards[i] for i in self.decode_basis(shards, wanted)]
        xp = np if all(isinstance(r, np.ndarray) for r in rows) else jnp
        out = self.reconstruct_stack(xp.stack(rows, axis=0), shards, wanted)
        return {w: out[i] for i, w in enumerate(wanted)}
