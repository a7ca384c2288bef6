"""Backend dispatch seam between the EC data path and the codec registry.

The storage layer (storage/ec/ec_files.py) needs exactly three
capabilities from whatever codec `_get_codec` hands it: dispatch a parity
encode, materialise the result on the host, and reconstruct missing rows.
The backends differ in a way that matters to the I/O engine — host codecs
(native C++ / numpy) compute eagerly and return numpy, while JAX device
codecs dispatch asynchronously and return an un-materialised device array
whose d2h transfer is the sync point.  Centralising the isinstance
fan-out here keeps the storage layer free of backend imports and gives
the overlapped pipeline one seam to time the sync point through.

Every call also feeds the per-kernel profile (stats/profile.KERNELS):
host wall, H2D conversion, `block_until_ready` device time, and D2H
transfer are recorded separately per entry point, so a 225 ms `encode`
span finally decomposes into matmul vs transfer vs host codec time at
/debug/pprof?format=table.
"""

from __future__ import annotations

import time

import numpy as np

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.profile import KERNELS


def _host_classes():
    from seaweedfs_tpu.models.rs import RSCode
    from seaweedfs_tpu.ops.native_codec import NativeRSCodec
    return NativeRSCodec, RSCode


def _is_host(codec) -> bool:
    """Eager host backend: computes synchronously, numpy in/out.  The
    native AVX2 shell plus anything flagged `host_backend` (the MSR
    file wrapper and the registry's numpy shell propagate the flag so
    wrapped codecs route like the shell they wrap)."""
    NativeRSCodec, _ = _host_classes()
    return isinstance(codec, NativeRSCodec) or getattr(
        codec, "host_backend", False)


def _is_numpy_ref(codec) -> bool:
    """Bare reference code object (RSCode / LRCCode): no backend shell,
    just encode_numpy / reconstruct_numpy."""
    return hasattr(codec, "encode_numpy") and not hasattr(codec, "_factory")


def backend_name(codec) -> str:
    """Class of the shell that runs a codec's matrix applies: the
    answer to "which backend ran" on metric labels and job stats
    (MSRFileCodec wraps the shell that does the work)."""
    return type(getattr(codec, "inner", codec)).__name__


def describe(codec, jax_live: bool = False) -> dict:
    """What a codec object runs on: its shell class and, for a device
    shell, the JAX backend its matrices already live on (platform,
    device_kind, device count), interpret flag and tile.  Asks JAX
    nothing for a host codec unless the caller says a backend already
    exists (`jax_live`) — reporting must never initialise one."""
    shell = getattr(codec, "inner", codec)
    info: dict = {"codec": backend_name(codec)}
    host = _is_host(codec) or _is_numpy_ref(codec)
    if host and not jax_live:
        return info
    import jax
    devs = jax.devices()
    info.update(platform=devs[0].platform, device_kind=devs[0].device_kind,
                device_count=len(devs))
    if host:
        return info
    kernel = getattr(shell, "kernel", None)  # mesh encoders: _ApplyKernel
    if kernel is not None:
        info.update(body=kernel.kind, mesh_devices=int(shell.mesh.size))
        if kernel.kind == "pallas":  # always compiled inside shard_map
            info.update(interpret=False, tile=kernel.tile)
    else:  # the Pallas shell carries both; the XLA shell has neither
        info.update({f: getattr(shell, f) for f in ("interpret", "tile")
                     if hasattr(shell, f)})
    return info


def dispatch_parity(codec, batch: np.ndarray):
    """Dispatch [k, B] -> [m, B] parity. JAX backends return the device
    array WITHOUT materialising it; host backends compute eagerly."""
    if _is_host(codec):
        with trace.span("codec.dispatch_parity", backend="host",
                        bytes=batch.nbytes), \
                KERNELS.timed("encode_parity", nbytes=batch.nbytes):
            return codec.encode_parity(batch)
    if _is_numpy_ref(codec):
        with trace.span("codec.dispatch_parity", backend="host",
                        bytes=batch.nbytes), \
                KERNELS.timed("encode_parity", nbytes=batch.nbytes):
            return codec.encode_numpy(batch)[codec.k:]
    import jax.numpy as jnp
    # a device dispatch returns un-materialised: this span times only the
    # h2d + async enqueue — the sync cost shows up under codec.d2h
    with trace.span("codec.dispatch_parity", backend="device",
                    bytes=batch.nbytes):
        t0 = time.perf_counter()
        dev = jnp.asarray(batch)
        t1 = time.perf_counter()
        out = codec.encode_parity(dev)
        KERNELS.record("encode_parity", "device",
                       wall_s=time.perf_counter() - t1,
                       h2d_s=t1 - t0, h2d_bytes=batch.nbytes,
                       nbytes=batch.nbytes)
        return out


def materialize(parity, kernel: str = "encode_parity") -> np.ndarray:
    """Sync point of an async dispatch: host backends already returned
    numpy; device arrays `block_until_ready` (device time, attributed to
    `kernel`) and then transfer d2h here."""
    if isinstance(parity, np.ndarray):
        return parity
    nbytes = getattr(parity, "nbytes", 0)
    with trace.span("codec.d2h", bytes=nbytes):
        t0 = time.perf_counter()
        if hasattr(parity, "block_until_ready"):
            parity.block_until_ready()
        t1 = time.perf_counter()
        out = np.asarray(parity)
        KERNELS.record(kernel, "device", calls=0,
                       device_s=t1 - t0,
                       d2h_s=time.perf_counter() - t1, d2h_bytes=nbytes)
        return out


def dispatch_parity_batch(codec, units, placed=None):
    """Dispatch a [U, k, B] unit batch -> [U, m, B] parity in ONE kernel
    launch — the fleet-conversion hot path (ops/fleet_convert.py).

    `placed`, when given, is the already-device-resident (and, on a mesh,
    unit-sharded) twin of the host batch `units`: the pipeline H2Ds
    through the encoder's matched in_sharding up front so the dispatch
    never reshards.  Host backends loop eagerly per unit (they have no
    batch geometry to win; the pipeline's value there is the interleaved
    I/O).  Device dispatches return un-materialised; `unit_parity_shards`
    is the streaming sync point."""
    nbytes = units.nbytes
    if _is_host(codec) or _is_numpy_ref(codec):
        with trace.span("codec.dispatch_parity_batch", backend="host",
                        bytes=nbytes), \
                KERNELS.timed("fleet_encode", nbytes=nbytes):
            if _is_numpy_ref(codec):
                return np.stack([codec.encode_numpy(units[u])[codec.k:]
                                 for u in range(units.shape[0])], axis=0)
            batched = getattr(codec, "encode_parity_batch", None)
            if batched is not None:
                return batched(units)
            return np.stack([codec.encode_parity(units[u])
                             for u in range(units.shape[0])], axis=0)
    import jax.numpy as jnp
    with trace.span("codec.dispatch_parity_batch", backend="device",
                    bytes=nbytes):
        t0 = time.perf_counter()
        # the H2D is booked exactly once: by the mesh place() seam when
        # one exists (whether the caller pre-placed or we place here),
        # else by this record — double-booking would inflate the
        # fleet_encode h2d roofline row 2x
        booked_by_place = placed is not None
        if placed is None:
            place = getattr(codec, "place", None)
            if place is not None:
                placed = place(units)
                booked_by_place = True
            else:
                placed = jnp.asarray(units)
        t1 = time.perf_counter()
        out = codec.encode_parity_batch(placed)
        KERNELS.record("fleet_encode", "device",
                       wall_s=time.perf_counter() - t1,
                       h2d_s=0.0 if booked_by_place else t1 - t0,
                       h2d_bytes=0.0 if booked_by_place else nbytes,
                       nbytes=nbytes)
        return out


def unit_parity_shards(parity, kernel: str = "fleet_encode"):
    """Streaming sync point of a batched dispatch: yield
    (unit_start, unit_stop, np.ndarray) per device-local block as each
    block's D2H completes — on a mesh the drain hands shards to their
    writers as they come off each chip instead of waiting for a full
    gather.  Host arrays yield one block immediately."""
    if isinstance(parity, np.ndarray):
        yield 0, parity.shape[0], parity
        return
    nbytes = getattr(parity, "nbytes", 0)
    with trace.span("codec.d2h", bytes=nbytes, streamed=True):
        t0 = time.perf_counter()
        if hasattr(parity, "block_until_ready"):
            parity.block_until_ready()
        t1 = time.perf_counter()
        KERNELS.record(kernel, "device", calls=0, device_s=t1 - t0)
        shards = getattr(parity, "addressable_shards", None)
        if not shards:
            out = np.asarray(parity)
            KERNELS.record(kernel, "device", calls=0,
                           d2h_s=time.perf_counter() - t1,
                           d2h_bytes=out.nbytes)
            yield 0, out.shape[0], out
            return
        for sh in sorted(shards, key=lambda s: s.index[0].start or 0):
            start = sh.index[0].start or 0
            t2 = time.perf_counter()
            data = np.asarray(sh.data)
            KERNELS.record(kernel, "device", calls=0,
                           d2h_s=time.perf_counter() - t2,
                           d2h_bytes=data.nbytes)
            yield int(start), int(start) + data.shape[0], data


def parity_mismatch(codec, data: np.ndarray,
                    parity_rows: dict[int, np.ndarray]
                    ) -> dict[int, np.ndarray]:
    """Scrub seam: recompute the parity of a [k, B] data-stripe window
    through the SAME backend dispatch the encoder uses and compare
    against the stored parity bytes.  Returns a boolean mismatch mask
    per supplied parity row (row index is parity-relative: 0..m-1).
    One dispatch verifies the whole window — RS(10,4) syndrome checking
    IS a batched GF(2^8) matmul, the workload this seam accelerates.
    (Profiled under `encode_parity` — it runs the encode kernel.)"""
    expect = materialize(dispatch_parity(codec, data))
    return {r: np.not_equal(expect[r],
                            np.frombuffer(stored, dtype=np.uint8)
                            if isinstance(stored, (bytes, bytearray))
                            else stored)
            for r, stored in parity_rows.items()}


# device-side matrix applies for the reduced-read repair plane
# (ops/regen.py): the coefficient matrices are tiny ([1, j] slices of a
# decode matrix) but arbitrary, so device backends pre-lift each one to
# its bit-matrix via the codec's matrix_apply factory and cache it —
# repair plans reuse the same few windows for a whole shard
_APPLY_CACHE: dict = {}
_APPLY_CACHE_MAX = 64


def apply_matrix(codec, C: np.ndarray, stack: np.ndarray) -> np.ndarray:
    """out[r, n] = C[r, j] @ stack[j, n] over GF(2^8) through the same
    backend seam as encode/reconstruct — the partial-sum kernel of the
    reduced-read repair path (profiled as `repair_partial`)."""
    C = np.ascontiguousarray(C, dtype=np.uint8)
    nbytes = stack.nbytes
    if _is_host(codec):
        from seaweedfs_tpu import native
        with trace.span("codec.apply_matrix", backend="host",
                        bytes=nbytes), \
                KERNELS.timed("repair_partial", nbytes=nbytes):
            if native.available():
                return native.gf_matmul(C, np.ascontiguousarray(stack))
            from seaweedfs_tpu.ops import gf
            return gf.gf_matmul(C, stack)
    factory = getattr(codec, "_factory", None)
    if _is_numpy_ref(codec) or factory is None:
        from seaweedfs_tpu.ops import gf
        with trace.span("codec.apply_matrix", backend="host",
                        bytes=nbytes), \
                KERNELS.timed("repair_partial", nbytes=nbytes):
            return gf.gf_matmul(C, stack)
    key = (id(codec), C.shape, C.tobytes())
    mat = _APPLY_CACHE.get(key)
    if mat is None:
        if len(_APPLY_CACHE) >= _APPLY_CACHE_MAX:
            _APPLY_CACHE.clear()
        mat = _APPLY_CACHE[key] = factory(C)
    import jax.numpy as jnp
    with trace.span("codec.apply_matrix", backend="device", bytes=nbytes):
        t0 = time.perf_counter()
        dev = jnp.asarray(stack)
        t1 = time.perf_counter()
        out = mat(dev)
        t2 = time.perf_counter()
        host = np.asarray(out)
        KERNELS.record("repair_partial", "device",
                       wall_s=t2 - t1, h2d_s=t1 - t0, h2d_bytes=nbytes,
                       d2h_s=time.perf_counter() - t2,
                       d2h_bytes=host.nbytes, nbytes=nbytes)
        return host


def reconstruct_batch(codec, shards: dict[int, np.ndarray],
                      wanted: list[int]) -> dict[int, np.ndarray]:
    """Rebuild `wanted` shard rows from >=k survivor rows (host bytes
    in/out)."""
    nbytes = sum(v.nbytes for v in shards.values())
    if _is_host(codec):
        with trace.span("codec.reconstruct", backend="host",
                        bytes=nbytes, wanted=len(wanted)), \
                KERNELS.timed("reconstruct", nbytes=nbytes):
            return codec.reconstruct(shards, wanted=wanted)
    if _is_numpy_ref(codec):
        with trace.span("codec.reconstruct", backend="host",
                        bytes=nbytes, wanted=len(wanted)), \
                KERNELS.timed("reconstruct", nbytes=nbytes):
            return codec.reconstruct_numpy(shards, wanted=wanted)
    import jax.numpy as jnp
    with trace.span("codec.reconstruct", backend="device",
                    bytes=nbytes, wanted=len(wanted)):
        t0 = time.perf_counter()
        dev = {i: jnp.asarray(v) for i, v in shards.items()}
        t1 = time.perf_counter()
        out = codec.reconstruct(dev, wanted=wanted)
        t2 = time.perf_counter()
        host = {i: np.asarray(v) for i, v in out.items()}
        KERNELS.record("reconstruct", "device",
                       wall_s=t2 - t1, h2d_s=t1 - t0, h2d_bytes=nbytes,
                       d2h_s=time.perf_counter() - t2,
                       d2h_bytes=sum(v.nbytes for v in host.values()),
                       nbytes=nbytes)
        return host
