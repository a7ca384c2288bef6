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


def select_survivors(code, present: tuple, wanted: list[int]) -> tuple:
    """The survivor basis a decode matrix is built against: the code's
    `decode_select` when it has one, else the MDS default of the first
    k sorted survivors."""
    sel = getattr(code, "decode_select", None)
    if sel is not None:
        return tuple(sel(list(present), list(wanted)))
    return tuple(present[: code.k])


class RSCodecBase:
    """Encode / reconstruct for one fixed-matrix GF(2^8) code.

    `matrix_apply_factory(C) -> callable([k, n] bytes) -> [m, n] bytes`
    supplies the device kernel for a fixed GF(2^8) matrix C.
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

    def reconstruct(self, shards: dict[int, jax.Array],
                    wanted: list[int] | None = None) -> dict[int, jax.Array]:
        """Rebuild missing shards from sufficient survivors.

        The code's survivor basis (first k sorted for MDS codes, the
        decode_select choice otherwise) feeds the decode matrix; the
        matrix is cached per (basis, wanted) pattern since failure
        patterns are few in practice."""
        present = tuple(sorted(shards))
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        basis, mat = self._cached_decode(present, tuple(wanted))
        stack = jnp.stack([shards[i] for i in basis], axis=0)
        out = mat(stack)
        return {w: out[i] for i, w in enumerate(wanted)}
