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
  d2h_copy     `np.asarray` once the array is ready

No synchronisation is added for the sake of measurement: a
`block_until_ready` stands only directly before a copy of the same array,
which would block on it anyway.  The same four figures feed the
per-kernel profile (stats/profile.KERNELS: `h2d_s`, `wall_s`, `device_s`,
`d2h_s` per entry point, at /debug/pprof?format=table), and each entry
point marks itself on its thread so that compilations are counted against
it (stats/profile.codec_entry).
"""

from __future__ import annotations

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
    that is more (a padded put)."""
    with _stage(job, "h2d", unit, bytes=nbytes) as h2d:
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
            if not isinstance(a, np.ndarray):  # MSRFileCodec re-interleaves
                a.block_until_ready()          # on the host already
    with _stage(job, "d2h_copy", unit, kernel=kernel) as copy:
        host = [np.asarray(a) for a in arrays]
    KERNELS.record(kernel, "device", calls=0, device_s=wait.seconds,
                   d2h_s=copy.seconds,
                   d2h_bytes=sum(h.nbytes for h in host))
    return host


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
    else:  # the Pallas shell carries both; the XLA shell its bucket tile
        info.update({f: getattr(shell, f) for f in ("interpret", "tile")
                     if hasattr(shell, f)})
    return info


@codec_entry("encode_parity")
def dispatch_parity(codec, batch: np.ndarray, job=None, unit=None):
    """Dispatch [k, B] -> [m, B] parity. JAX backends return the device
    array WITHOUT materialising it; host backends compute eagerly."""
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
                unit=None) -> np.ndarray:
    """Sync point of an async dispatch: host backends already returned
    numpy; device arrays are waited for and then copied back here, both
    attributed to `kernel`."""
    if isinstance(parity, np.ndarray):
        return parity
    return _to_host([parity], job, unit, kernel)[0]


@codec_entry("fleet_encode")
def dispatch_parity_batch(codec, units, job=None, unit=None):
    """Dispatch a [U, k, B] unit batch -> [U, m, B] parity in ONE kernel
    launch — the fleet-conversion hot path (ops/fleet_convert.py).

    A mesh encoder H2Ds through its matched in_sharding (`place`: each
    chip pulls exactly its U/D units) so the dispatch never reshards.
    Host backends loop eagerly per unit (they have no batch geometry to
    win; the pipeline's value there is the interleaved I/O).  Device
    dispatches return un-materialised; `unit_parity_shards` is the
    streaming sync point."""
    nbytes = units.nbytes
    if _is_numpy_ref(codec):
        def batched(us):
            return np.stack([codec.encode_numpy(u)[codec.k:] for u in us])
    elif _is_host(codec):
        batched = getattr(codec, "encode_parity_batch", None) or (
            lambda us: np.stack([codec.encode_parity(u) for u in us]))
    else:
        import jax.numpy as jnp
        return _device_call(job, unit, "fleet_encode", nbytes,
                            lambda: getattr(codec, "place",
                                            jnp.asarray)(units),
                            codec.encode_parity_batch)
    return _host_call(job, unit, "fleet_encode", nbytes, batched, units)


def unit_parity_shards(parity, kernel: str = "fleet_encode", job=None,
                       unit=None):
    """Streaming sync point of a batched dispatch: yield
    (unit_start, unit_stop, np.ndarray) per device-local block as each
    block's D2H completes — on a mesh the drain hands shards to their
    writers as they come off each chip instead of waiting for a full
    gather.  Host arrays yield one block immediately.  No stage stays
    open across a yield: what the consumer does with a block is its own."""
    if isinstance(parity, np.ndarray):
        yield 0, parity.shape[0], parity
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
# as wide as its bucket too it is put from where it lies, uncopied (a view
# of a shard file's map in a rebuild; PERF.md, PR 29).
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


@codec_entry("reconstruct")
def reconstruct_batch(codec, rows, ids: list[int], wanted: list[int],
                      job=None, unit=None) -> dict[int, np.ndarray]:
    """Rebuild `wanted` shard rows (host bytes in and out) from the
    survivors `ids`, whose rows of n bytes each `rows` holds in that
    order: a `[len(ids), n]` array or a sequence of rows.

    A device codec is handed the rows its decode matrix wants, in its
    order and W wide, W the bucket of n (`codec_base.bucket`): one program
    per (rows wanted, W) and not per n, with nothing but 1-D arrays
    crossing (`codec_base.stacked`), and the cut back to n is a view on
    the host.  How they go up rests on n and W, not on what `rows` is:
    rows as wide as their bucket and at least `ROW_PUTS_FROM` (a rebuild
    batch) are put one by one from where they lie, and the runtime reads
    them after the put returns, so they stay alive and unchanged until
    this does (it waits for the result); any others are stacked on the
    host first (`_staged`: the one host copy of a degraded read, or of a
    rebuild's short last batch), which the job counts as `rows_staged`."""
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
        # a dict of rows at their own length is all MSRFileCodec (which
        # interleaves whole files round its shell, and brings its rows
        # back itself) and the column-sharded mesh encoder take
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

    def put():
        if width == n and width >= ROW_PUTS_FROM:
            return tuple(jnp.asarray(rows[src]) for src in order)
        stack = _staged(rows, order, width)
        if job is not None and stack is not rows:
            job.count("rows_staged", len(order))
        if width >= ROW_PUTS_FROM:
            return tuple(jnp.asarray(row) for row in stack)
        return jnp.asarray(stack.reshape(-1))

    out = _device_call(
        job, unit, "reconstruct", nbytes, put,
        lambda dev: codec.reconstruct_stack(dev, ids, wanted, linear=True),
        h2d_bytes=len(order) * width, wanted=len(wanted))
    host, = _to_host([out], job, unit, "reconstruct")
    host = host.reshape(len(wanted), width)
    return {w: host[r, :n] for r, w in enumerate(wanted)}
