"""Backend dispatch seam between the EC data path and the codec registry.

The storage layer (storage/ec/ec_files.py) needs exactly two
capabilities from whatever codec `_get_codec` hands it, each cut at its
sync point: dispatch a parity encode and materialise the result on the
host (`dispatch_parity`, `materialize`), dispatch the reconstruction of
missing rows and materialise those (`dispatch_reconstruct`,
`materialize_rows`; `reconstruct_batch` is the two in a row, for a caller
with one batch).  The backends differ in a way that matters to the I/O engine — host codecs
(native C++ / numpy) compute eagerly and return numpy, while JAX device
codecs dispatch asynchronously and return an un-materialised device array
whose d2h transfer is the sync point.  Centralising the isinstance
fan-out here keeps the storage layer free of backend imports and gives
the overlapped pipeline one seam to time the sync point through.

This is also the one place that knows H2D from enqueue from wait from
copy, so it is where the time is cut.  Round every device dispatch the
seam opens four stages (stats/pipeline.Stage), booked to the calling
engine's job and named `codec.<stage>` on spans and profiler annotations:

  h2d          the calling thread's time in `jnp.asarray` / the mesh
               `place`: staging and the put.  Not the transfer: the DMA
               runs on after the call returns and shows in a profiler
               trace inside the `codec.h2d` annotation
  dispatch     the enqueue (and, for a shape seen first, tracing and
               compilation); a host codec's whole computation
  device_wait  blocked in `block_until_ready`: the queued transfers and
               the kernel
  d2h_copy     `np.asarray` once the array is ready (what is left of the
               copy where it was asked for at the enqueue: an encode
               unit's parity runs, a reconstruct's rows)

No synchronisation is added for the sake of measurement: a
`block_until_ready` stands only directly before a copy of the same array,
which would block on it anyway.  The same four figures feed the
per-kernel profile (stats/profile.KERNELS: `h2d_s`, `wall_s`, `device_s`,
`d2h_s` per entry point, at /debug/pprof?format=table), and each entry
point marks itself on its thread so that compilations are counted against
it (stats/profile.codec_entry).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from seaweedfs_tpu.stats import pipeline as _pipeline
from seaweedfs_tpu.stats.profile import KERNELS, codec_entry


def _stage(job, name: str, unit=None, **attrs):
    """One seam stage, booked to the calling engine's `job` (to nothing
    for a caller that runs none)."""
    return (job or _pipeline.UNTRACKED).stage(
        name, unit=unit, span="codec." + name, **attrs)


def _host_call(job, unit, kernel: str, nbytes: int, fn, *args, **kwargs):
    """A host codec computes where a device codec enqueues: its whole
    call is the `dispatch` stage."""
    with _stage(job, "dispatch", unit, backend="host", kernel=kernel,
                bytes=nbytes) as st:
        out = fn(*args, **kwargs)
    KERNELS.record(kernel, "host", wall_s=st.seconds, nbytes=nbytes)
    return out


def _device_call(job, unit, kernel: str, nbytes: int, put, run,
                 h2d_bytes: int | None = None, **attrs):
    """The first two stages of a device dispatch: `put()` sends the inputs
    up (`h2d`), `run(placed)` enqueues the work on them (`dispatch`).  The
    result comes back un-materialised; `_to_host` holds the other two.
    `nbytes` are the caller's useful bytes, `h2d_bytes` what crosses where
    that is more (a padded put).  `attrs` ride both stages' events (an
    encode unit's `rows` and `block`)."""
    with _stage(job, "h2d", unit, bytes=nbytes, **attrs) as h2d:
        placed = put()
    with _stage(job, "dispatch", unit, backend="device", kernel=kernel,
                bytes=nbytes, **attrs) as disp:
        out = run(placed)
    KERNELS.record(kernel, "device", wall_s=disp.seconds,
                   h2d_s=h2d.seconds, nbytes=nbytes,
                   h2d_bytes=nbytes if h2d_bytes is None else h2d_bytes)
    return out


def _to_host(arrays: list, job, unit, kernel: str) -> list[np.ndarray]:
    """The sync point of a device dispatch: wait for `arrays`, then copy
    them back."""
    with _stage(job, "device_wait", unit, kernel=kernel) as wait:
        for a in arrays:
            a.block_until_ready()
    with _stage(job, "d2h_copy", unit, kernel=kernel) as copy:
        host = [np.asarray(a) for a in arrays]
    KERNELS.record(kernel, "device", calls=0, device_s=wait.seconds,
                   d2h_s=copy.seconds,
                   d2h_bytes=sum(h.nbytes for h in host))
    return host


def keep_freed_pages() -> None:
    """Have the host allocator (glibc) keep the pages of freed buffers of
    up to 32 MiB instead of handing each one out fresh.  The runtime
    allocates the destination of every copy back (no handle in the public
    API), 16 MiB at a time in the bulk engines, and by default whether
    that is memory touched before or a new mapping, whose 4,096 pages are
    then faulted in one by one while sixteen other threads map, unmap and
    write, is a state a process falls into for good: TPU v5e,
    `ecvol.encode`, 3.69-4.03 GB/s in nine runs and 2.21 / 2.28 in two,
    with every host stage two to five times as long; 1.82 with every
    buffer forced fresh (`MALLOC_MMAP_THRESHOLD_=1048576`), 3.62-4.01 in
    sixteen of sixteen runs with freed memory kept (PERF.md, PR 31).  Called once a
    device shell is built on a platform that copies back (ops/codecs); the
    price is that up to 1 GiB of freed heap stays resident in the main
    arena, and in a thread's arena what its busiest call held.

    The two thresholds keep the main arena's pages only.  The buffers are
    asked for on the thread that enqueues, a request's worker, whose arena
    is a chain of 64 MiB heaps, and glibc unmaps every heap of it that
    falls wholly free unless the room left in the heap before it is under
    M_TOP_PAD, whatever the trim threshold says.  Three 16 MiB runs fill a
    heap, so a call's buffers (nine a unit under PM-MSR(9,16), 432 MiB in
    flight) were unmapped and faulted in again as they went, more or fewer
    by what small and lasting allocation happened to pin which heap in
    that process.  A pad of one whole heap keeps them all (TPU v5e,
    `pmmsr.encode`, one seed, runs in turn: 3.53-3.74 GB/s in six of six
    with it, 3.40-3.54 in five and 2.86 in one without; PERF.md, PR 32).
    """
    import ctypes
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):  # another libc: nothing to set
        return
    mallopt(-3, 32 << 20)  # M_MMAP_THRESHOLD: its maximum, and no longer dynamic
    mallopt(-1, 1 << 30)   # M_TRIM_THRESHOLD
    mallopt(-2, 64 << 20)  # M_TOP_PAD: a thread arena's heap (HEAP_MAX_SIZE)


def _is_host(codec) -> bool:
    """Eager host backend: computes synchronously, numpy in/out.  Anything
    flagged `host_backend`: the native AVX2 shell, the registry's numpy
    shell, and the MSR file wrapper over either, which propagates the flag
    so that a wrapped codec routes like the shell it wraps."""
    return getattr(codec, "host_backend", False)


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
            info.update(interpret=False, tile=shell.tile)
    else:  # the Pallas shell carries both; the XLA shell its bucket tile
        info.update({f: getattr(shell, f) for f in ("interpret", "tile")
                     if hasattr(shell, f)})
    info.update(geometry(codec))
    return info


def geometry(codec) -> dict:
    """The rows the codec's parity matrix takes and gives, and how many of
    them are one shard file's: 10, 4 and 1 under rs_10_4; 72, 72 and 8
    under msr_9_16, whose matrix works on the files' byte-interleaved
    sub-rows.  /perf says it on the codec's block and, with the matrix's
    tile, on the `encode_parity` and `fleet_encode` rows."""
    shell = getattr(codec, "inner", codec)
    return {"rows_in": shell.k, "rows_out": shell.m,
            "alpha": getattr(codec, "alpha", 1)}


def _note_matrix(kernel: str, codec, stripes: int) -> None:
    """What a device entry point's parity matrix is, and how many stripe
    rows the units it ran held, each count once (1: a column cut of a
    large-block row; 0: a 2-D array), on its /perf row."""
    seen = set(KERNELS.notes(f"{kernel}[device]").get("stripes", ()))
    KERNELS.note(kernel, "device", **geometry(codec),
                 stripes=sorted(seen | {stripes}),
                 tile=getattr(getattr(codec, "inner", codec), "tile", None))


def _unstriped(spans, k: int, stripes: int) -> np.ndarray:
    """The [k, W] host array of a unit that came as spans of a `.dat`
    (`codec_base.stacked`'s third form, on the host): a copy, for a codec
    that has no linear apply to lay the unit out on the device."""
    flat = spans[0] if len(spans) == 1 else np.concatenate(spans)
    return np.ascontiguousarray(
        flat.reshape(stripes, k, -1).transpose(1, 0, 2)).reshape(k, -1)


@codec_entry("encode_parity")
def dispatch_parity(codec, batch, job=None, unit=None, stripes: int = 0,
                    block: int = 0):
    """Dispatch the parity of one unit, [k, B] -> [m, B].  JAX backends
    return device arrays WITHOUT materialising them (`materialize` is the
    sync point); host backends compute eagerly.

    `batch` is a `[k, B]` host array (the scrubber's window, regen, a
    test), which goes up and comes back as it is, 2-D; or the unit as the
    encode engine selects it in the `.dat`'s map: a sequence of 1-D spans
    that hold, one after the other, `stripes` >= 1 stripe rows of k blocks
    each (`codec_base.stacked`'s third form; B = `stripes` blocks).  For a
    codec with a linear apply (`encode_parity_linear`: the Pallas and XLA
    shells, and `MSRFileCodec` over one) the spans are put as 1-D arrays
    from where they lie, the program lays them out (and splits them into
    a sub-packetised code's sub-rows), and the parity comes back as m
    arrays of `[B]`, one contiguous run of each parity shard
    (`codec_base.unstacked`): no byte is copied on the host and nothing
    2-D crosses.  A span of several
    rows goes up row by row where a row is at least `ROW_PUTS_FROM` (TPU
    v5e, a 160 MiB unit: 27.7 ms in one array, 14.5 as its sixteen 10 MiB
    rows; PERF.md, PR 31), else as one array.  The runtime reads a span
    after its put returns, so the spans stay alive and unchanged until the
    result is materialised (a sealed `.dat`'s map, or the engine's staged
    last row, held by the unit's queue item).  `block` is the block size of
    the unit's rows, said on its `h2d` and `dispatch` stage events beside
    `rows` (a column cut of a large-block row: one row, k spans a block
    apart).  A host shell with a linear apply (the native one, and
    `MSRFileCodec` over it) takes the spans too and reads them by pointer
    where they lie: its `[m, B]` parity, computed here, is the m runs.
    Every other codec (the numpy shells, the column-sharded mesh encoder)
    gets its `[k, B]` array built from the spans on the host
    (`_unstriped`), which the job counts as `rows_staged`."""
    if not (isinstance(batch, np.ndarray) and batch.ndim == 2):
        spans = list(batch)
        if hasattr(codec, "encode_parity_linear") and not _is_host(codec):
            nbytes = sum(s.nbytes for s in spans)
            spans = unit_pieces(spans, stripes)
            import jax.numpy as jnp

            def run(placed):
                runs = codec.encode_parity_linear(placed, stripes)
                for a in runs:  # see materialize
                    a.copy_to_host_async()
                _count_in_place(job, codec, placed, stripes)
                return runs
            _note_matrix("encode_parity", codec, stripes)
            return _device_call(
                job, unit, "encode_parity", nbytes,
                lambda: tuple(jnp.asarray(s) for s in spans), run,
                rows=stripes, block=block)
        if _is_host(codec) and hasattr(getattr(codec, "inner", codec),
                                       "encode_parity_linear"):
            return _host_call(job, unit, "encode_parity",
                              sum(s.nbytes for s in spans),
                              codec.encode_parity_linear, spans, stripes)
        batch = _unstriped(spans, codec.k, stripes)
        if job is not None and (stripes > 1 or len(spans) > 1):
            job.count("rows_staged", stripes)
    nbytes = batch.nbytes
    if _is_host(codec):
        return _host_call(job, unit, "encode_parity", nbytes,
                          codec.encode_parity, batch)
    if _is_numpy_ref(codec):
        return _host_call(job, unit, "encode_parity", nbytes,
                          lambda: codec.encode_numpy(batch)[codec.k:])
    import jax.numpy as jnp
    # returns un-materialised: the sync cost shows up in materialize()
    return _device_call(job, unit, "encode_parity", nbytes,
                        lambda: jnp.asarray(batch), codec.encode_parity)


def materialize(parity, kernel: str = "encode_parity", job=None,
                unit=None):
    """Sync point of an async dispatch: host backends already returned
    numpy; device arrays are waited for and then copied back here, both
    attributed to `kernel`.  What comes back has the dispatch's form, and
    either way its i-th item is parity row i: the m `[B]` runs of a unit
    that went up as spans, an `[m, B]` array otherwise.  The runs' copies
    back were asked for when the unit was enqueued, all m at once, so they
    follow the program on the device's queue, ahead of the puts of the
    units behind it, and `d2h_copy` here is what is left of them (TPU v5e,
    `ecvol.encode`: 2.94 GB/s asked for here, before the wait, 3.75-4.03
    at the enqueue, 1.74 one by one after the wait; PERF.md, PR 31)."""
    if isinstance(parity, np.ndarray):
        return parity
    if isinstance(parity, tuple):
        return _to_host(list(parity), job, unit, kernel)
    return _to_host([parity], job, unit, kernel)[0]


def _count_in_place(job, codec, placed, stripes: int, **decode) -> None:
    """Count a unit or batch whose program reads its 1-D pieces where
    they lie (`in_place` on /admin/ec/progress `stages`, beside
    `rows_staged`): the codec's `in_place`, where it has one, asked about
    what was put."""
    test = getattr(codec, "in_place", None)
    if job is not None and test is not None and test(
            [p.shape[0] for p in placed], stripes, **decode):
        job.count("in_place", 1)


def unit_pieces(spans, stripes: int) -> list:
    """The 1-D arrays a unit of `stripes` stripe rows goes up as: its
    spans cut into their rows where a row is at least `ROW_PUTS_FROM`,
    else the spans as they are.  Views: no byte moves."""
    row = sum(s.nbytes for s in spans) // stripes
    if row < ROW_PUTS_FROM:
        return list(spans)
    return [s[o:o + row] for s in spans for o in range(0, len(s), row)]


class _EncodeUnits(list):
    """A fleet batch dispatched a unit at a time (`dispatch_parity`): its
    sync point books to that entry point's kernel, as a single volume's
    `materialize` does."""
    kernel = "encode_parity"


@codec_entry("fleet_encode")
def dispatch_parity_batch(codec, units, job=None, unit=None,
                          stripes: int = 0):
    """Dispatch one batch of the fleet-conversion stream
    (ops/fleet_convert.py) in ONE launch.  Device dispatches return
    un-materialised; `unit_parity_shards` is the streaming sync point.

    `units` is a list of the codec's `unit_slots` slots, for a codec that
    lays a unit out on the device (`encode_units_linear`: the mesh's
    FleetUnitEncoder): each slot holds a unit as the stream selects it in
    a `.dat`'s map, the 1-D pieces (`unit_pieces`) of `stripes` stripe
    rows, alike in length from slot to slot, or None.  Every piece is put
    1-D to its slot's device from where it lies (`place_units`; an empty
    slot costs no PCIe byte), the mesh program lays the units out and
    gives each one's parity as one run of `[stripes * block]` for each of
    the m parity files (k and m are the codec's files; under a
    sub-packetised code the program splits and merges their sub-rows),
    and the copies back of the occupied slots' runs are asked for here, at
    the enqueue (see `materialize`).  What comes back is a list, slot by
    slot, of m device arrays or None.  The runtime reads a piece after
    its put returns, so the pieces stay alive and unchanged until the
    parity is materialised.  For any other codec (a one-device shell, a
    host shell, the numpy reference) each occupied slot is one unit of
    `dispatch_parity`, by its rule and under its kernel: the list that
    comes back (`_EncodeUnits`) holds, slot by slot, what that gave (m
    device runs, or a host shell's `[m, B]`), or None.

    Or `units` is a `[U, k, B]` host array (a test's) -> `[U, m, B]`: a
    mesh encoder H2Ds it through its matched in_sharding (`place`: each
    chip pulls exactly its U/D units), host backends loop eagerly per
    unit."""
    if not isinstance(units, np.ndarray):
        if not hasattr(codec, "encode_units_linear"):
            return _EncodeUnits(None if u is None else dispatch_parity(
                codec, u, job=job, unit=unit, stripes=stripes)
                for u in units)

        def run(placed):
            parity = [runs if u is not None else None for runs, u in
                      zip(codec.encode_units_linear(placed, stripes),
                          units)]
            for runs in filter(None, parity):
                for a in runs:
                    a.copy_to_host_async()
            return parity
        _note_matrix("fleet_encode", codec, stripes)
        return _device_call(
            job, unit, "fleet_encode",
            sum(p.nbytes for u in filter(None, units) for p in u),
            lambda: codec.place_units(units), run, rows=stripes)
    nbytes = units.nbytes
    if _is_numpy_ref(codec):
        def batched(us):
            return np.stack([codec.encode_numpy(u)[codec.k:] for u in us])
    elif _is_host(codec):
        batched = getattr(codec, "encode_parity_batch", None) or (
            lambda us: np.stack([codec.encode_parity(u) for u in us]))
    else:
        import jax.numpy as jnp
        _note_matrix("fleet_encode", codec, stripes)
        return _device_call(job, unit, "fleet_encode", nbytes,
                            lambda: getattr(codec, "place",
                                            jnp.asarray)(units),
                            codec.encode_parity_batch)
    return _host_call(job, unit, "fleet_encode", nbytes, batched, units)


def parity_devices(parity) -> int:
    """How many devices a dispatched batch's parity lives on (0: a host
    codec returned numpy)."""
    if isinstance(parity, list):
        return len({d for runs in parity if isinstance(runs, (tuple, list))
                    for d in runs[0].devices()})
    sharding = getattr(parity, "sharding", None)
    return len(sharding.device_set) if sharding is not None else 0


def unit_parity_shards(parity, kernel: str = "fleet_encode", job=None,
                       unit=None):
    """Streaming sync point of a batched dispatch: yield
    (unit_start, unit_stop, block) per device-local block as each
    block's D2H completes, `block[u - unit_start][i]` parity row i of
    unit u: on a mesh the drain hands shards to their writers as they
    come off each chip instead of waiting for a full gather.  A batch
    that went up as spans yields one unit a block, its m contiguous runs
    (their copies were asked for at the enqueue: `d2h_copy` is what is
    left of them), and nothing for an empty slot; a `[U, m, B]` device
    array yields each device's `[U/D, m, B]`; host arrays yield at once,
    a list of them one unit a block.  No stage stays open across a yield:
    what the consumer does with a block is its own.  The wait and the
    copies book to `kernel`, or to the batch's own (`_EncodeUnits`)."""
    kernel = getattr(parity, "kernel", kernel)
    if isinstance(parity, np.ndarray):
        yield 0, parity.shape[0], parity
        return
    if isinstance(parity, list) and all(
            runs is None or isinstance(runs, np.ndarray) for runs in parity):
        for slot, runs in enumerate(parity):
            if runs is not None:
                yield slot, slot + 1, [runs]
        return
    if isinstance(parity, list):
        with _stage(job, "device_wait", unit, kernel=kernel) as wait:
            for runs in filter(None, parity):
                for a in runs:
                    a.block_until_ready()
        KERNELS.record(kernel, "device", calls=0, device_s=wait.seconds)
        for slot, runs in enumerate(parity):
            if runs is None:
                continue
            with _stage(job, "d2h_copy", unit, kernel=kernel) as copy:
                host = [np.asarray(a) for a in runs]
            KERNELS.record(kernel, "device", calls=0, d2h_s=copy.seconds,
                           d2h_bytes=sum(h.nbytes for h in host))
            yield slot, slot + 1, [host]
        return
    shards = getattr(parity, "addressable_shards", None)
    if not shards:
        out, = _to_host([parity], job, unit, kernel)
        yield 0, out.shape[0], out
        return
    with _stage(job, "device_wait", unit, kernel=kernel) as wait:
        parity.block_until_ready()
    KERNELS.record(kernel, "device", calls=0, device_s=wait.seconds)
    for sh in sorted(shards, key=lambda s: s.index[0].start or 0):
        start = int(sh.index[0].start or 0)
        with _stage(job, "d2h_copy", unit, kernel=kernel) as copy:
            data = np.asarray(sh.data)
        KERNELS.record(kernel, "device", calls=0, d2h_s=copy.seconds,
                       d2h_bytes=data.nbytes)
        yield start, start + data.shape[0], data


def parity_mismatch(codec, data: np.ndarray,
                    parity_rows: dict[int, np.ndarray], job=None
                    ) -> dict[int, np.ndarray]:
    """Scrub seam: recompute the parity of a [k, B] data-stripe window
    through the SAME backend dispatch the encoder uses and compare
    against the stored parity bytes.  Returns a boolean mismatch mask
    per supplied parity row (row index is parity-relative: 0..m-1).
    One dispatch verifies the whole window — RS(10,4) syndrome checking
    IS a batched GF(2^8) matmul, the workload this seam accelerates.
    (Profiled under `encode_parity` — it runs the encode kernel; the
    seam's four stages book to `job`, the scrubber's flow account.)"""
    expect = materialize(dispatch_parity(codec, data, job=job), job=job)
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


@codec_entry("repair_partial")
def apply_matrix(codec, C: np.ndarray, stack: np.ndarray, job=None,
                 unit=None) -> np.ndarray:
    """out[r, n] = C[r, j] @ stack[j, n] over GF(2^8) through the same
    backend seam as encode/reconstruct — the partial-sum kernel of the
    reduced-read repair path (profiled as `repair_partial`)."""
    C = np.ascontiguousarray(C, dtype=np.uint8)
    nbytes = stack.nbytes
    factory = getattr(codec, "_factory", None)
    if _is_host(codec):
        from seaweedfs_tpu import native
        if native.available():
            return _host_call(job, unit, "repair_partial", nbytes,
                              native.gf_matmul, C,
                              np.ascontiguousarray(stack))
    if _is_host(codec) or _is_numpy_ref(codec) or factory is None:
        from seaweedfs_tpu.ops import gf
        return _host_call(job, unit, "repair_partial", nbytes,
                          gf.gf_matmul, C, stack)
    key = (id(codec), C.shape, C.tobytes())
    mat = _APPLY_CACHE.get(key)
    if mat is None:
        if len(_APPLY_CACHE) >= _APPLY_CACHE_MAX:
            _APPLY_CACHE.clear()
        mat = _APPLY_CACHE[key] = factory(C)
    import jax.numpy as jnp
    out = _device_call(job, unit, "repair_partial", nbytes,
                       lambda: jnp.asarray(stack), mat)
    return _to_host([out], job, unit, "repair_partial")[0]


# A put costs its thread about 0.2 ms whatever it carries, and one array
# in flight moves at half the rate of ten (TPU v5e: a 160 MiB put 30 ms,
# its ten rows 16; at 1 MiB a row the two ways tie; PERF.md, PR 26): rows
# this wide and wider go up one by one, narrower ones in one array.  A row
# that goes up on its own needs no place in a stacked array: where it is
# as wide as its program runs it is put from where it lies, uncopied (a view
# of a shard file's map in a rebuild; PERF.md, PR 29).
# A program that reads such rows in place runs at their own width, which
# spares a volume's short last batch the copy (PERF.md, section 6).
ROW_PUTS_FROM = 2 << 20


def _staged(rows, order: list[int], width: int) -> np.ndarray:
    """Rows `order` of `rows`, one under the other and `width` wide with
    zeros past their end: `rows` itself where it is that already, else a
    copy (the one host copy of a degraded read)."""
    n = len(rows[0])
    if (width == n and isinstance(rows, np.ndarray)
            and order == list(range(len(rows)))):
        return rows
    buf = np.zeros((len(order), width), dtype=np.uint8)
    for r, src in enumerate(order):
        buf[r, :n] = rows[src]
    return buf


class _Enqueued(NamedTuple):
    """A reconstruct a device codec has enqueued and not yet given back:
    the program's un-materialised result, how to cut it, and the host
    memory its puts read (views of a rebuild's maps, or the stacked copy),
    alive as long as this is."""
    out: object
    wanted: list[int]
    width: int
    n: int
    held: list


@codec_entry("reconstruct")
def dispatch_reconstruct(codec, rows, ids: list[int], wanted: list[int],
                         job=None, unit=None):
    """Enqueue the rebuild of `wanted` shard rows from the survivors
    `ids`, whose rows of n bytes each `rows` holds in that order: a
    `[len(ids), n]` array or a sequence of rows.  What comes back goes to
    `materialize_rows`, the sync point, as `dispatch_parity`'s goes to
    `materialize`: a device codec's is un-materialised and no wait stands
    here, so the caller may enqueue the next batch before it asks for this
    one (`ec_files.rebuild_ec_files`); a host codec and the numpy reference
    compute here, and so does the column-sharded mesh encoder, which waits
    inside (it takes a dict of rows at their own length), and theirs is
    the `{shard: row}` dict already.

    A device codec is handed the rows its decode matrix wants, in its
    order and W wide, with nothing but 1-D arrays crossing
    (`codec_base.stacked`), and the cut back to n is a view on the host.
    W is n itself where n is at least `ROW_PUTS_FROM` and the codec's own
    `in_place` says its program reads rows of n bytes where they lie (the
    Pallas shell: whole tiles): a rebuild batch, the short last one of a
    volume too, which the job counts as `narrow` where n is below its
    bucket.  That costs a program per such n and count of rows wanted
    (under 1 MiB small blocks and 16 MiB batches at most 11 widths beyond
    the buckets: 3, 5-7 and 9-15 MiB), each built once and then served
    from the persistent compile cache.  Else W is the bucket of n (`codec_base.bucket`): one
    program per (rows wanted, W) and not per n, which keeps a degraded
    read's needle lengths, all under `ROW_PUTS_FROM`, to a few programs.
    How the rows go up rests on n and W, not on what `rows` is: rows W
    wide and at least `ROW_PUTS_FROM` are put one by one from where they
    lie; any others are stacked on the host first (`_staged`: the one host
    copy of a degraded read, or of a short rebuild batch whose rows the
    program would not read in place), which the job counts as
    `rows_staged`.  The runtime reads a row after its put returns: what
    comes back holds `rows` and the stacked copy, and whoever holds it
    keeps them alive and unchanged until `materialize_rows` has returned.
    The result's copy back is asked for here, at the enqueue, so that it
    follows the program on the device's queue ahead of the next batch's
    puts (see `materialize`)."""
    ids = list(ids)
    n = len(rows[0])
    nbytes = len(ids) * n
    if _is_host(codec) or _is_numpy_ref(codec):
        fn = codec.reconstruct if _is_host(codec) else \
            codec.reconstruct_numpy
        return _host_call(job, unit, "reconstruct", nbytes, fn,
                          dict(zip(ids, rows)), wanted=wanted)
    import jax.numpy as jnp
    if not hasattr(codec, "reconstruct_stack"):
        out = _device_call(
            job, unit, "reconstruct", nbytes,
            lambda: {i: jnp.asarray(r) for i, r in zip(ids, rows)},
            lambda dev: codec.reconstruct(dev, wanted=wanted),
            wanted=len(wanted))
        return dict(zip(out, _to_host(list(out.values()), job, unit,
                                      "reconstruct")))
    from seaweedfs_tpu.ops.codec_base import bucket
    order = [ids.index(i) for i in codec.decode_basis(ids, wanted)]
    width = bucket(n, codec.tile)
    test = getattr(codec, "in_place", None)
    if (n < width and n >= ROW_PUTS_FROM and test is not None
            and test([n] * len(order), 0, present=ids, wanted=wanted)):
        width = n
        if job is not None:
            job.count("narrow", 1)
    held = [rows]

    def put():
        if width == n and width >= ROW_PUTS_FROM:
            return tuple(jnp.asarray(rows[src]) for src in order)
        stack = _staged(rows, order, width)
        if stack is not rows:
            held.append(stack)
            if job is not None:
                job.count("rows_staged", len(order))
        if width >= ROW_PUTS_FROM:
            return tuple(jnp.asarray(row) for row in stack)
        return jnp.asarray(stack.reshape(-1))

    def run(placed):
        out = codec.reconstruct_stack(placed, ids, wanted, linear=True)
        out.copy_to_host_async()
        if isinstance(placed, tuple):
            _count_in_place(job, codec, placed, 0, present=ids,
                            wanted=wanted)
        return out

    out = _device_call(job, unit, "reconstruct", nbytes, put, run,
                       h2d_bytes=len(order) * width, wanted=len(wanted))
    return _Enqueued(out, list(wanted), width, n, held)


def materialize_rows(pending, job=None, unit=None) -> dict[int, np.ndarray]:
    """Sync point of `dispatch_reconstruct`: the `{shard: row}` dict of
    host rows, n bytes each.  A dict is handed through; a device codec's
    result is waited for (`device_wait`: the queued puts and the program)
    and copied back (`d2h_copy`: what is left of the copy asked for at
    the enqueue), `[rows wanted x W]` flat, and cut to n as views."""
    if isinstance(pending, dict):
        return pending
    host, = _to_host([pending.out], job, unit, "reconstruct")
    host = host.reshape(len(pending.wanted), pending.width)
    return {w: host[r, :pending.n] for r, w in enumerate(pending.wanted)}


def reconstruct_batch(codec, rows, ids: list[int], wanted: list[int],
                      job=None, unit=None) -> dict[int, np.ndarray]:
    """Rebuild `wanted` shard rows (host bytes in and out) from the
    survivors `ids`: `dispatch_reconstruct` and `materialize_rows` one
    after the other on the calling thread, which books the seam's four
    stages: what a degraded read, regen and the scrubber call.  It waits
    for the result, so `rows` need stay alive and unchanged only until it
    returns."""
    return materialize_rows(
        dispatch_reconstruct(codec, rows, ids, wanted, job=job, unit=unit),
        job=job, unit=unit)
