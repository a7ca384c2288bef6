"""EC shard file generation / rebuild / decode — the TPU data plane.

The reference streams 10x256KB buffers through a CPU SIMD encoder
(weed/storage/erasure_coding/ec_encoder.go:120-235). Here a unit is a span
of the .dat's map, B = 16MB per shard (160MB under RS(10,4): sixteen 1MB
stripe rows), put to the device from where it lies and erasure-coded by the
bit-sliced MXU codec, so the kernel runs deep in its throughput regime and
host<->device transfers amortise. Data shards are copied in the kernel
(copy_file_range) — only parity (four runs of [B], one a parity shard)
comes back from the device.

Functions mirror the reference's capability surface:
  write_ec_files      <- WriteEcFiles (ec_encoder.go:56)
  rebuild_ec_files    <- RebuildEcFiles (ec_encoder.go:91)
  write_sorted_ecx    <- WriteSortedFileFromIdx (ec_encoder.go:27)
  write_dat_file      <- WriteDatFile (ec_decoder.go:153)
  write_idx_from_ecx  <- WriteIdxFileFromEcIndex (ec_decoder.go:18)
  find_dat_file_size  <- FindDatFileSize (ec_decoder.go:48)
"""

from __future__ import annotations

import collections
import mmap
import os
import queue
import sys
import threading
import time

import numpy as np

from seaweedfs_tpu.storage import idx as idxf
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import layout

DEFAULT_BATCH = 16 * 1024 * 1024  # bytes per shard per device round-trip


def _get_codec(kind: str | None = None, tag: str | None = None):
    """The codec the EC file engines run a volume's code on: the `ec.codec`
    knob of this framework.  `tag` picks the CODE (ops/codecs grammar:
    rs_10_4 / lrc_12_2_2 / msr_9_16; none: the RS default), `kind` the
    backend (default: WEEDTPU_EC_CODEC).  The choice itself is
    ops/codecs.resolve's, the program's one resolution; a tag the backend
    does not carry raises codecs.CodecUnsupported."""
    from seaweedfs_tpu.ops import codecs as _codecs
    return _codecs.resolve(tag, kind)


# backend seam (ops/dispatch.py): parity dispatch, the d2h sync point,
# and reconstruction, without backend imports in this layer
from seaweedfs_tpu.stats import netflow as _netflow  # noqa: E402
from seaweedfs_tpu.stats import pipeline as _pipeline  # noqa: E402
from seaweedfs_tpu.stats import profile as _profile  # noqa: E402
from seaweedfs_tpu.stats import trace as _trace  # noqa: E402
from seaweedfs_tpu.ops.dispatch import (  # noqa: E402
    backend_name as _backend_name,
    dispatch_parity as _dispatch_parity,
    dispatch_reconstruct as _dispatch_reconstruct,
    materialize as _materialize,
    materialize_rows as _materialize_rows,
    reconstruct_batch as _reconstruct_batch,
)

# units (encode) or batches (rebuild) between selection and materialised
# result: read N+1 / encode N / drain N-1
PIPELINE_DEPTH = int(os.environ.get("WEEDTPU_EC_PIPELINE_DEPTH", "3"))
# queued writes per shard fd before submission backpressures
WRITER_DEPTH = int(os.environ.get("WEEDTPU_EC_WRITER_DEPTH", "4"))


def _writer_threads(nshards: int) -> int:
    """Writer threads for an nshards-wide writer pool.  Shard fds are
    striped over the workers (same shard -> same worker, so per-shard
    write order holds); WEEDTPU_EC_WRITERS pins the count.  The default
    is CPU-aware: one worker per shard maximises overlap on a wide
    storage host, but on a 2-core box 14 threads just thrash the
    scheduler and the page-cache locks — there, a couple of workers
    already saturate the copy bandwidth."""
    env = int(os.environ.get("WEEDTPU_EC_WRITERS", "0"))
    if env > 0:
        return max(1, min(nshards, env))
    return max(2, min(nshards, os.cpu_count() or 2))


def _map_lazy(fd: int):
    """Read-only map of a source file, the one form every bulk engine
    maps with (single-volume encode and rebuild, a rebuild's backlog, the
    fleet stream), with no page made ready: a batch or a unit reads only
    its own span of the map, and whoever reads a page first takes its
    fault, which for a device codec is the runtime's copy threads, beside
    the stream.  On the chip machine those faults cost a tenth of a
    batch's puts, where populating the files whole held the first put back
    by a quarter of a call, and a populated map a span, a page touched
    ahead and MADV_POPULATE_READ (refused there) each cost more than the
    faults they save (PERF.md section 6, PR 37).  So nothing is scheduled
    and no size rule or knob applies: MADV_SEQUENTIAL readahead for a file
    the page cache does not hold, at any file size."""
    mm = mmap.mmap(fd, 0, flags=mmap.MAP_SHARED, prot=mmap.PROT_READ)
    try:
        mm.madvise(mmap.MADV_SEQUENTIAL)
    except (AttributeError, OSError):  # advice only
        pass
    return mm


def write_ec_files(base: str, dat_path: str | None = None,
                   large_block: int = layout.LARGE_BLOCK_SIZE,
                   small_block: int = layout.SMALL_BLOCK_SIZE,
                   batch_size: int = DEFAULT_BATCH,
                   progress=None, cancel=None, stats=None,
                   codec_tag: str | None = None) -> None:
    """Encode `<base>.dat` (or dat_path) into the shard files of the
    volume's code (`codec_tag`: `.ec00` .. `.ec13` under rs_10_4, `.ec15`
    under lrc_12_2_2), plus a `<base>.vif` volume-info sidecar recording
    the codec tag, the encode-time dat size, the version and the two block
    sizes the layout was cut with (the reference's .vif,
    volume_info.go:16-40, as JSON): the layout was cut from the FILE size,
    which later lookups cannot reliably re-derive from the index once tail
    needles get deleted, and with `large_block` / `small_block`, which are
    the volume's from here on: given here (upstream's 1 GB / 1 MB where
    the caller gives none), recorded in the `.vif`, and read back from it
    by every reader of the set (`volume_blocks`: EcVolume, write_dat_file,
    a recode).  The servers hold a request's pair to `check_blocks`
    first.

    `progress(bytes_done)` is called per batch with ACTUAL volume bytes
    consumed and `cancel()` (returning True) aborts mid-stream — a 30GB
    encode must be observable and stoppable (the reference streams progress
    over its gRPC seam).  `stats`, when a dict, receives per-stage wall-time
    attribution (read/encode/write seconds): what /admin/ec/progress shows.

    Shards build under `.tmp` names and commit by rename only when the
    whole encode succeeds, so a cancelled/crashed encode leaves any
    previous valid shard set (and its .ecx/.vif) untouched.  Stale `.tmp`
    files from an earlier failed/cancelled attempt are recycled in place
    (opened without O_TRUNC): a retried encode overwrites the already-
    allocated pages instead of faulting in fresh ones, which matters both
    on hosts with lazy page allocation and for filesystems that would
    otherwise re-extend the files block by block."""
    dat_path = dat_path or base + ".dat"
    dat_size = os.path.getsize(dat_path)
    from seaweedfs_tpu.ops import codecs as _codecs
    spec = _codecs.parse_tag(codec_tag or _codecs.default_tag())
    codec = _get_codec(tag=spec.tag)

    # chaos hook: an armed shard_write_error fault (maintenance/faults)
    # fails the encode exactly like a dying disk would — before any tmp
    # shard file exists, so the previous valid shard set stays intact
    from seaweedfs_tpu.maintenance import faults as _faults
    _faults.check_shard_write(base)

    tmp_paths = [base + layout.to_ext(i) + ".tmp"
                 for i in range(spec.n)]
    # stage attribution always accumulates (even when the caller brought
    # no dict): the stats keys feed the pipeline job /debug/pipeline
    # renders, so every encode is observable.  The job is the call, from
    # the first tmp file opened to the last rename
    stats = stats if stats is not None else {}
    geometry = block_geometry([dat_size], large_block, small_block, spec.k)
    stats.update(geometry, units_column=0, units_rows=0)
    pjob = _pipeline.track("ec_encode", stats, dat_size,
                           meta={"mode": "pipelined", **geometry},
                           span="ec.encode", sums=ENCODE_SUMS)
    out_fds: list[int] = []
    ok = False
    try:
        with pjob.stage("open", files=spec.n):
            # O_RDWR without O_TRUNC: recycle pages of stale tmp files (see
            # above); _encode_stream ftruncates each fd to its exact size
            for p_ in tmp_paths:
                out_fds.append(os.open(p_, os.O_RDWR | os.O_CREAT, 0o644))
        _encode_stream(codec, dat_path, dat_size, large_block, small_block,
                       batch_size, out_fds, progress, cancel, pjob)
        ok = True
    finally:
        try:
            with pjob.stage("commit"):
                for fd in out_fds:
                    os.close(fd)
                if ok:
                    write_vif(base, dat_size, codec=spec.tag,
                              large_block=large_block,
                              small_block=small_block)
                    for i, p_ in enumerate(tmp_paths):
                        os.replace(p_, base + layout.to_ext(i))
                else:
                    for p_ in tmp_paths:
                        try:
                            os.remove(p_)
                        except OSError:
                            pass
        finally:
            _state_overlap(stats)
            pjob.finish(None if ok else
                        (sys.exc_info()[1] or "encode failed"))


def check_blocks(large_block, small_block,
                 batch_size: int = DEFAULT_BATCH) -> None:
    """Raise ValueError for a pair of block sizes a conversion request
    may not name: both a positive number of bytes, small <= large, and
    each a whole number of the column steps `_iter_units` cuts it in
    (`min(batch_size, block)`).  The servers hold a request's
    `large_block_bytes` / `small_block_bytes` to this before anything is
    opened."""
    for name, block in (("large", large_block), ("small", small_block)):
        if not isinstance(block, int) or isinstance(block, bool) \
                or block <= 0:
            raise ValueError(f"{name} block size {block!r}: not a "
                             f"positive number of bytes")
        if block % min(batch_size, block):
            raise ValueError(f"{name} block {block}: not a whole number "
                             f"of {batch_size}-byte columns")
    if small_block > large_block:
        raise ValueError(f"small block {small_block} > large block "
                         f"{large_block}")


def block_geometry(dat_sizes, large_block: int, small_block: int,
                   data_shards: int) -> dict:
    """What a bulk job says of the layout it cuts (/admin/ec/progress
    `stages`, the job's meta): the two block sizes, the rows of each kind
    over the job's volumes (`dat_sizes`: one, or a fleet's), and the share
    of their bytes that lie in large-block rows."""
    sizes = list(dat_sizes)
    total = sum(sizes)
    geometry = (large_block, small_block, data_shards)
    rows = sum(layout.n_large_rows(n, *geometry) for n in sizes)
    return {"large_block": large_block, "small_block": small_block,
            "large_rows": rows,
            "small_rows": sum(layout.n_small_rows(n, *geometry)
                              for n in sizes),
            "large_row_share": round(
                rows * large_block * data_shards / total, 4)
            if total else 0.0}


def _iter_units(dat_size: int, large_block: int, small_block: int,
                batch_size: int, data_shards: int = layout.DATA_SHARDS):
    """Yield (row_start, block, col, step, shard_off) column-batch work
    units in shard file order: N full rows of k large blocks, then
    small-block rows.  shard_off is the unit's byte offset inside every
    shard file (all n shard files are parallel arrays of blocks).
    `data_shards` is the codec's stripe width k (10 for rs_10_4, 12 for
    lrc_12_2_2, 9 for MSR volumes)."""
    k = data_shards
    processed = 0
    remaining = dat_size
    shard_base = 0
    while remaining > large_block * k:
        step = min(batch_size, large_block)
        assert large_block % step == 0, (large_block, step)
        for col in range(0, large_block, step):
            yield processed, large_block, col, step, shard_base + col
        processed += large_block * k
        remaining -= large_block * k
        shard_base += large_block
    while remaining > 0:
        step = min(batch_size, small_block)
        assert small_block % step == 0, (small_block, step)
        for col in range(0, small_block, step):
            yield processed, small_block, col, step, shard_base + col
        processed += small_block * k
        remaining -= small_block * k
        shard_base += small_block


def _iter_spans(dat_size: int, large_block: int, small_block: int,
                batch_size: int, data_shards: int = layout.DATA_SHARDS):
    """_iter_units' units as every codec is handed them: yield
    (row_start, block, col, step, shard_off, rows).  Consecutive whole
    rows of one block size (step == block) are contiguous in the .dat
    (striping is row-major) and in every shard file, so up to
    batch_size // block of them are one unit: the span
    dat[row_start : row_start + rows * k * block], rows * block bytes of
    each shard at shard_off.  A block wider than batch_size stays cut in
    columns, one unit each (rows == 1, k spans of step bytes)."""
    run = None
    for row_start, block, col, step, shard_off in _iter_units(
            dat_size, large_block, small_block, batch_size, data_shards):
        if (run is not None and step == block == run[1]
                and run[5] < batch_size // block):
            run[5] += 1
            continue
        if run is not None:
            yield tuple(run)
        run = [row_start, block, col, step, shard_off, 1]
    if run is not None:
        yield tuple(run)


def _unit_spans(dat_view: np.ndarray, dat_size: int, k: int, row_start: int,
                block: int, col: int, step: int, rows: int):
    """-> (spans, staged): one of _iter_spans' units as 1-D views of the
    .dat's map that hold, one after the other, `rows` stripe rows of k
    blocks of `step` bytes.  No byte moves but for a row that runs past
    the end of the .dat (the volume's last): that one is copied into a
    zeroed buffer of its own (`staged` 1, else 0), since the parity of a
    short row is the parity of the row with zeros after it.  A unit that
    holds no data at all gives no spans."""
    if step == block:  # whole rows: one run of the map
        row = k * block
        full = min(rows, (dat_size - row_start) // row)
        spans = [dat_view[row_start:row_start + full * row]] if full else []
        if full == rows:
            return spans, 0
        offs = [row_start + full * row]  # the one row past the end
        width = row
    else:  # a column cut of one row: k runs, `block` apart
        offs = [row_start + j * block + col for j in range(k)]
        if offs[-1] + step <= dat_size:
            return [dat_view[o:o + step] for o in offs], 0
        if offs[0] >= dat_size:
            return [], 0
        spans, width = [], step
    tail = np.zeros(len(offs) * width, dtype=np.uint8)
    for i, off in enumerate(offs):
        src = dat_view[off:max(off, min(off + width, dat_size))]
        tail[i * width:i * width + len(src)] = src
    return spans + [tail], 1


class EncodeCancelled(RuntimeError):
    pass


def _pwrite_all(fd: int, view, off: int) -> None:
    """pwrite may write short (RLIMIT_FSIZE edge, fs under pressure); a
    silent short write would commit a shard with a zero gap."""
    mv = memoryview(view)
    while len(mv) > 0:
        n = os.pwrite(fd, mv, off)
        if n <= 0:
            raise OSError("pwrite returned 0")
        mv = mv[n:]
        off += n


def _pwritev_all(fd: int, bufs: list, off: int) -> None:
    """Vectored pwrite of buffers destined for one contiguous file range:
    a run of per-unit parity blocks lands in a single syscall instead of
    one pwrite per unit.  Short writes (possibly mid-iovec) resume."""
    if not hasattr(os, "pwritev"):
        for b in bufs:
            _pwrite_all(fd, b, off)
            off += memoryview(b).nbytes
        return
    mvs = [memoryview(b) for b in bufs]
    while mvs:
        n = os.pwritev(fd, mvs, off)
        if n <= 0:
            raise OSError("pwritev returned 0")
        off += n
        while mvs and n >= len(mvs[0]):
            n -= len(mvs[0])
            mvs.pop(0)
        if mvs and n:
            mvs[0] = mvs[0][n:]


_CFR_OK = True  # copy_file_range support, latched off on first failure


def _copy_range(src_fd: int, dst_fd: int, src_off: int, dst_off: int,
                count: int, src_view: np.ndarray | None = None) -> None:
    """In-kernel copy of a .dat slice into a shard file (no user-space
    transit), falling back to pwrite from the mmap view where
    copy_file_range is unsupported (non-regular files, cross-fs, old
    kernels)."""
    global _CFR_OK
    if _CFR_OK and hasattr(os, "copy_file_range"):
        so, do, left = src_off, dst_off, count
        try:
            while left > 0:
                n = os.copy_file_range(src_fd, dst_fd, left, so, do)
                if n <= 0:
                    raise OSError("copy_file_range returned 0")
                so += n
                do += n
                left -= n
            return
        except OSError:
            _CFR_OK = False
            src_off, dst_off, count = so, do, left  # resume where CFR died
    if count > 0 and src_view is not None:
        _pwrite_all(dst_fd, src_view[src_off:src_off + count], dst_off)


# the lumps /admin/ec/progress has always shown, and the stages of the
# dispatch seam (ops/dispatch.py) and of the rebuild engine's two threads
# that add up to each (stats/pipeline.PipelineJob `sums`)
ENCODE_SUMS = {"encode": ("h2d", "dispatch"),
               "d2h": ("device_wait", "d2h_copy")}
REBUILD_SUMS = {"reconstruct": ("stage", "h2d", "dispatch", "device_wait",
                                "d2h_copy", "unstage")}
_PART_KEYS = {part + "_s" for sums in (ENCODE_SUMS, REBUILD_SUMS)
              for parts in sums.values() for part in parts}


def _finalize_shards(out_fds, highwater, shard_size: int) -> None:
    """Cut every shard file to exactly shard_size: truncate to the written
    high-water mark first (drops stale bytes of a recycled tmp file), then
    extend — the zero suffix becomes a filesystem hole, so fully-padded
    regions (e.g. a 40MB volume in a 16MB-block layout) cost no write I/O
    at all."""
    for fd, hw in zip(out_fds, highwater):
        os.ftruncate(fd, min(hw, shard_size))
        if hw < shard_size:
            os.ftruncate(fd, shard_size)


def _encode_stream(codec, dat_path: str, dat_size: int, large_block: int,
                   small_block: int, batch_size: int, out_fds,
                   progress, cancel, pjob) -> None:
    """Stream the .dat through the codec into the shard fds: one strategy,
    the overlapped reader -> dispatch -> drain -> writers pipeline
    (_encode_pipelined), every shard file written through the per-shard
    writer pool (_ShardWriterPool) so all of them land concurrently.  A
    unit is a span of the mmap, up to batch_size bytes of every shard
    (_iter_spans: sixteen 1 MiB stripe rows at the served sizes; in a
    large-block row, which a volume past ten large blocks has, a column
    cut: k spans of batch_size bytes a block apart), selected as views
    and handed to the codec through the dispatch seam, whatever the codec
    ops/codecs.resolve built for the platform: a device codec puts it
    from where it lies, the native host codec reads it there by row
    pointer (no staging copy either way: only the volume's last, short
    row is copied, `rows_staged`).  JAX dispatch is async so the device
    round-trip overlaps host I/O, and only parity rides the device, one
    contiguous run a shard a unit; data shards move by in-kernel
    copy_file_range on their writers.

    Rows wholly beyond the .dat are never read, encoded, or written: the
    parity of an all-zero row region is zero, so those regions become
    holes (_finalize_shards).  Partially-covered units encode only the
    rows that carry data, against a column-sliced parity matrix.

    The stages book to `pjob` (write_ec_files' job, whose `stats` this
    fills): `map` for the .dat's map here (_map_lazy: no page is made
    ready before the first put; `spans_mapped` counts the units selected
    from it), `commit` for the cut to size; `wall_s` runs from before the
    map to after the pipeline's last join.  The job says which layout it
    cut (`block_geometry`) and how many units of each kind carried data
    (`units_column`, `units_rows`)."""
    stats = pjob.stats
    stats["bytes"] = dat_size
    stats["rows_staged"] = 0  # stripe rows copied on the host (job.count)
    stats["in_place"] = 0  # units read where they were put (the seam counts)
    stats["spans_mapped"] = 0  # units selected in the .dat's map (job.count)
    shard_size = layout.shard_file_size(dat_size, large_block, small_block,
                                        data_shards=codec.k)
    highwater = [0] * (codec.k + codec.m)
    if dat_size:
        stats["mode"] = "pipelined"
        stats["backend"] = _backend_name(codec)
        t_wall = time.perf_counter()
        with open(dat_path, "rb") as datf:
            dat_fd = datf.fileno()
            with pjob.stage("map", files=1, bytes=dat_size):
                mm = _map_lazy(dat_fd)
            dat_view = np.frombuffer(mm, dtype=np.uint8)
            try:
                _encode_pipelined(codec, dat_fd, dat_view, dat_size,
                                  large_block, small_block, batch_size,
                                  out_fds, highwater, pjob, progress, cancel)
            finally:
                del dat_view
                with pjob.stage("commit"):
                    try:
                        mm.close()
                    except BufferError:
                        # an in-flight exception's traceback frames still
                        # hold views into the map; GC reaps the mapping
                        # with them
                        pass
            stats["wall_s"] = time.perf_counter() - t_wall
            # stage BYTES are analytic (the layout fixes them), booked
            # once: zero hot-path cost, and the bottleneck verdict gets
            # achieved GB/s per stage
            _book_stage_bytes(pjob, stats, dat_size, codec.m * shard_size)
    with pjob.stage("commit"):
        _finalize_shards(out_fds, highwater, shard_size)


def _book_stage_bytes(pjob, stats: dict, data_bytes: int,
                      parity_bytes: int) -> None:
    """Attribute the run's bytes to whichever stages actually ran (a
    host-codec encode has no d2h stage; booking bytes against a
    zero-second stage would invent infinite-GB/s rows)."""
    for key, nbytes in (("read_s", data_bytes), ("encode_s", data_bytes),
                        ("d2h_s", parity_bytes),
                        ("write_data_s", data_bytes),
                        ("write_parity_s", parity_bytes),
                        ("reconstruct_s", data_bytes),
                        ("write_s", parity_bytes)):
        if stats.get(key):
            pjob.add_bytes(key[:-2], nbytes)


def _min_step(dat_size: int, large_block: int, small_block: int,
              batch_size: int, data_shards: int = layout.DATA_SHARDS) -> int:
    """The narrowest column step _iter_units will cut for this volume:
    whether the encode's drain submits its parity runs directly or
    through a _ShardFlusher (_make_sink)."""
    row = large_block * data_shards
    n_large = (dat_size - 1) // row if dat_size > row else 0
    steps = [min(batch_size, large_block)] if n_large else []
    if dat_size - n_large * row > 0:
        steps.append(min(batch_size, small_block))
    return min(steps, default=batch_size)


def _unit_coverage(dat_size: int, row_start: int, block: int, col: int,
                   step: int,
                   data_shards: int = layout.DATA_SHARDS) -> tuple[int, int]:
    """-> (nz, tail): nz = number of leading rows carrying any data in this
    unit, tail = valid bytes in row nz-1 (== step when that row is full)."""
    nz = 0
    tail = step
    for j in range(data_shards):
        off = row_start + j * block + col
        n = min(step, dat_size - off)
        if n <= 0:
            break
        nz = j + 1
        tail = n
    return nz, tail


def _countdown(n: int, cb):
    """Return a thunk that invokes cb after being called n times — the
    release hook for a pooled buffer fanned out to n shard writers."""
    lock = threading.Lock()
    left = [n]

    def hit() -> None:
        with lock:
            left[0] -= 1
            if left[0] > 0:
                return
        cb()
    return hit


class _ShardWriterPool:
    """pwrite/copy_file_range workers servicing the shard fds behind
    bounded queues.

    Shards are striped over _writer_threads(n) workers with a FIXED
    shard -> worker mapping: writes to different shard files proceed
    concurrently — a stall on one file no longer serializes the other
    13 — while writes to the SAME shard stay in submission order on its
    designated thread (they target disjoint offsets, but ordering keeps
    the fd's high-water mark and the page cache walk sequential).  On a
    wide host the default is one worker per shard; on a small host a
    couple of workers carry all 14 fds instead of thrashing the
    scheduler.  Bounded queues make submission apply backpressure
    instead of buffering a whole volume in flight.

    Workers never die: after the first error they drain remaining items
    without touching the fds (still firing release hooks) so producers
    can never deadlock on a full queue; the first error surfaces via
    `.errors` after close().  Each batch a worker writes is one stage of
    the run's `job`, named by stage_of(shard_index) (`write_data`,
    `write_parity`, `write`): its seconds book to the job and its stats
    dict as the write happens, summed over the threads, and close() adds
    the thread capacity behind each stage (`<stage>_workers`).

    One write primitive: a run of buffers bound for contiguous offsets
    of one shard goes out as one synchronous `_pwritev_all` on the shard's
    worker (`_copy_range` for the data shards' in-kernel copies).  A
    release hook fires only after the batch it rode in has been written:
    a recycled parity buffer must not be handed out before its pwritev
    returned."""

    def __init__(self, fds, highwater=None, job=None, stage_of=None,
                 depth: int | None = None, workers: int | None = None):
        self._fds = list(fds)
        self._hw = highwater
        # a bare pool (tests) times its batches for no run
        self._job = job if job is not None else _pipeline.UNTRACKED
        self._stats = job.stats if job is not None else None
        self._stage_of = stage_of or (lambda i: "write")
        n = workers if workers else _writer_threads(len(self._fds))
        self._nworkers = max(1, min(len(self._fds), n))
        shards_per = -(-len(self._fds) // self._nworkers)
        self._queues: list[queue.Queue] = [
            queue.Queue(maxsize=(depth or WRITER_DEPTH) * shards_per)
            for _ in range(self._nworkers)]
        self._busy = [0.0] * len(self._fds)
        self._wbytes = [0] * len(self._fds)
        self.errors: list[BaseException] = []
        self._threads = [
            threading.Thread(target=self._run, args=(w,),
                             name=f"ec-writer-{w:02d}", daemon=True)
            for w in range(self._nworkers)]
        for t in self._threads:
            t.start()

    @property
    def failed(self) -> bool:
        return bool(self.errors)

    @property
    def idle(self) -> bool:
        """Every write queued so far has been written (asked without a
        wait: a rebuild commits a volume mid-call only once it is)."""
        return not any(q.unfinished_tasks for q in self._queues)

    def _q(self, shard: int) -> queue.Queue:
        return self._queues[shard % self._nworkers]

    def put(self, shard: int, data, off: int, release=None) -> None:
        """Queue a pwrite of a 1-D uint8 buffer at `off`; the caller must
        keep `data` valid until `release` (or the write) completes."""
        self._q(shard).put((shard, [(data, None, off, release)]))

    def copy(self, shard: int, src_fd: int, src_off: int, dst_off: int,
             count: int, src_view=None) -> None:
        """Queue an in-kernel copy_file_range into the shard file."""
        self._q(shard).put(
            (shard, [(None, (src_fd, src_off, count, src_view), dst_off,
                      None)]))

    def put_many(self, shard: int, jobs: list) -> None:
        """Queue a batch of jobs as ONE queue item — one worker wakeup per
        batch, not per job (see _ShardFlusher)."""
        self._q(shard).put((shard, jobs))

    _IOV_RUN = 512  # max buffers merged into one pwritev (< IOV_MAX)

    def _run(self, w: int) -> None:
        q = self._queues[w]
        while True:
            batch = q.get()
            if batch is None:
                return
            shard, item = batch
            # releases fire only after the whole batch is written: a
            # recycled parity buffer mid-write is silent corruption
            releases: list = []
            with self._job.stage(self._stage_of(shard)) as st:
                self._write_batch(shard, item, releases)
            if not self.errors:  # a failed run's seconds mean nothing
                self._busy[shard] += st.seconds
            for rel in releases:
                rel()
            q.task_done()

    def _write_batch(self, shard: int, item: list, releases: list) -> None:
        """Write one queue item's jobs; every error is kept for close(),
        none raised."""
        idx = 0
        while idx < len(item):
            data, cfr, off, release = item[idx]
            if release is not None:
                releases.append(release)
            idx += 1
            try:
                if self.errors:
                    continue  # drain without touching the fd
                # inside the try: a shard the pool has no file for (a
                # codec wider than the set) is an error of the run, not
                # the death of this worker with its queue still filling
                fd = self._fds[shard]
                if cfr is not None:
                    src_fd, src_off, count, src_view = cfr
                    _copy_range(src_fd, fd, src_off, off, count,
                                src_view=src_view)
                    end = off + count
                else:
                    # merge the run of pwrites targeting
                    # contiguous offsets into one pwritev
                    bufs = [np.ascontiguousarray(data)]
                    end = off + bufs[0].nbytes
                    while (idx < len(item)
                           and len(bufs) < self._IOV_RUN
                           and item[idx][1] is None
                           and item[idx][2] == end):
                        nxt = np.ascontiguousarray(item[idx][0])
                        bufs.append(nxt)
                        end += nxt.nbytes
                        if item[idx][3] is not None:
                            releases.append(item[idx][3])
                        idx += 1
                    _pwritev_all(fd, bufs, off)
                self._wbytes[shard] += end - off
                if self._hw is not None and end > self._hw[shard]:
                    self._hw[shard] = end
            except BaseException as e:  # surfaced after close
                self.errors.append(e)

    # a bare pool quacks like a _ShardFlusher so producers can submit
    # DIRECTLY when units are big enough that per-job queue hops are
    # cheap relative to the writes themselves (see _make_sink)
    def account(self, nbytes: int) -> None:
        pass

    def flush(self) -> None:
        pass

    def stop(self) -> None:
        """Tell the workers that nothing more will be queued: each ends
        once its queue is written.  Idempotent; close() joins them.  Many
        pools stopped first and closed after wait for their threads side
        by side, not one pool after the other."""
        if not getattr(self, "_stopped", False):
            self._stopped = True
            for q in self._queues:
                q.put(None)

    def close(self, unit: int | None = None) -> None:
        """Drain every queue, join the workers, fold the thread capacity
        behind each stage into stats.  Idempotent, and does not raise —
        callers inspect `.errors`, letting a producer-side exception win
        over a writer one.  The calling thread's wait for the queued
        writes and the join is the job's `join_writers`, blocked (the
        writers' own `write_*` stages run inside it)."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        with self._job.blocked("join_writers", unit=unit):
            self.stop()
            for t in self._threads:
                t.join()
        if self._stats is not None:
            stage_busy: dict[str, float] = {}
            for i, busy in enumerate(self._busy):
                stage = self._stage_of(i)
                stage_busy[stage] = stage_busy.get(stage, 0.0) + busy
            # the stages' seconds are summed across N parallel shard
            # slots: publish the capacity backing them so occupancy math
            # (stats/pipeline busy_frac) divides by it instead of
            # reading a 4-worker 30%-busy pool as a 120%-saturated
            # stage.  The pool's threads split across its stages IN
            # PROPORTION TO BUSY SECONDS — write_data and write_parity
            # share one thread set, and naming each stage the full
            # thread count would let a fully saturated pool read as two
            # half-saturated stages and hand the bottleneck verdict to
            # the wrong stage.  ACCUMULATED, not first-wins —
            # fleet_convert's per-volume pools all fold into one shared
            # stats dict, and their concurrent workers are all capacity
            total_busy = sum(stage_busy.values())
            for stage, busy in stage_busy.items():
                if total_busy > 0:
                    wkey = stage + "_workers"
                    self._stats[wkey] = self._stats.get(wkey, 0.0) + \
                        self._nworkers * (busy / total_busy)
        # the disk-side roofline row: shard writes vs the measured disk
        # ceiling (stats/profile.roofline_snapshot special-cases this
        # kernel onto the wall/bytes columns)
        busy_total, wrote = sum(self._busy), sum(self._wbytes)
        if busy_total > 0 and wrote > 0:
            _profile.KERNELS.record("shard_write", "host", calls=0,
                                    wall_s=busy_total, nbytes=wrote)


FLUSH_BYTES = int(os.environ.get("WEEDTPU_EC_FLUSH_BYTES",
                                 str(8 * 1024 * 1024)))
# units at or above this size skip the submission batcher entirely — a
# queue hop per ~256KB+ write is noise, and direct submission lets the
# writers start (and release pooled buffers) the moment a job exists
# instead of at the next flush-group boundary
DIRECT_MIN = int(os.environ.get("WEEDTPU_EC_DIRECT_MIN",
                                str(256 * 1024)))


def _make_sink(writers: "_ShardWriterPool", nshards: int, min_step: int):
    """Submission front for the writer pool: the pool itself (direct,
    per-job) when every unit is at least DIRECT_MIN bytes, else a
    _ShardFlusher that batches the tiny-unit churn."""
    if min_step >= DIRECT_MIN:
        return writers
    return _ShardFlusher(writers, nshards)


class _ShardFlusher:
    """Producer-side submission batcher for a _ShardWriterPool.

    With the production 16MB column batches each unit is worth a worker
    wakeup, but a small-block-only layout cuts 1MB units — paying a queue
    round-trip per unit per shard costs more scheduler churn than the
    writes themselves on a small host.  The flusher accumulates each
    shard's jobs locally and hands them over as one put_many batch per
    ~FLUSH_BYTES of volume data; the worker then merges the contiguous
    parity runs into single pwritev calls."""

    def __init__(self, writers: _ShardWriterPool, nshards: int,
                 flush_bytes: int = FLUSH_BYTES):
        self._writers = writers
        self._jobs: list[list] = [[] for _ in range(nshards)]
        self._acc = 0
        self._flush_bytes = flush_bytes

    def put(self, shard: int, data, off: int, release=None) -> None:
        self._jobs[shard].append((data, None, off, release))

    def copy(self, shard: int, src_fd: int, src_off: int, dst_off: int,
             count: int, src_view=None) -> None:
        self._jobs[shard].append(
            (None, (src_fd, src_off, count, src_view), dst_off, None))

    def account(self, nbytes: int) -> None:
        """Producers call this once per unit; crossing the flush target
        ships every shard's pending batch."""
        self._acc += nbytes
        if self._acc >= self._flush_bytes:
            self.flush()

    def flush(self) -> None:
        self._acc = 0
        for shard, jobs in enumerate(self._jobs):
            if jobs:
                self._writers.put_many(shard, jobs)
                self._jobs[shard] = []


# keys ending in `_s` that are no work of a stage: the two clocks, and the
# blocked stages (a thread waiting for a slot, a buffer, the next unit,
# the drain or the writers)
_NOT_WORK_KEYS = frozenset((
    "wall_s", "call_s", "stall_s", "await_unit_s", "await_parity_s",
    "await_batch_s", "join_drain_s", "join_writers_s"))


def overlap_fraction(stats: dict) -> float | None:
    """Achieved stage overlap of an encode/rebuild run: 1 - wall / (sum of
    per-stage seconds).  0.0 means fully serial (the wall clock IS the sum
    of its stages); the upper bound for a given stage mix is
    1 - max_stage/sum.  stall_s and the other blocked stages' keys
    (_NOT_WORK_KEYS) are a thread's IDLE time, not productive stages, so
    they are excluded — a fully backpressured run reads as ~0, not as
    overlapped.  None when the stats carry no wall clock or no stage time
    (e.g. an empty volume)."""
    wall = stats.get("wall_s")
    total = sum(v for key, v in stats.items()
                if key.endswith("_s")
                and key not in _NOT_WORK_KEYS
                and key not in _PART_KEYS  # their lumps carry them
                and isinstance(v, float))
    if not wall or total <= 0:
        return None
    return round(max(0.0, 1.0 - wall / total), 3)


def _state_overlap(stats: dict) -> None:
    """`overlap_frac` of a call, over every stage it booked: stated last,
    with the commit's seconds in."""
    frac = overlap_fraction(stats)
    if frac is not None:
        stats["overlap_frac"] = frac


def _encode_pipelined(codec, dat_fd: int, dat_view: np.ndarray,
                      dat_size: int, large_block: int, small_block: int,
                      batch_size: int, out_fds, highwater, pjob,
                      progress=None, cancel=None) -> None:
    """Overlapped reader -> dispatch -> drain -> shard-writer pipeline.

    Stages, each on its own thread(s), all behind bounded queues so a
    slow stage backpressures the ones before it instead of buffering the
    volume:

      reader   walks the unit iterator for unit N+1; data shards go to
               their shard writers by in-kernel copy_file_range on the
               way (they never round-trip the device).  For DEVICE
               codecs and host codecs alike a unit is a span of the
               .dat's map (_iter_spans:
               up to batch_size // block consecutive stripe rows, which
               lie one after the other in the .dat and in every shard
               file; of a block wider than the batch, a large-block
               row's, one column cut: k spans a block apart, rows == 1),
               and `read` is its selection as views
               (_unit_spans): no byte moves but the volume's last, short
               row, copied into a zeroed buffer and counted
               (`rows_staged`, 0 or 1 a call).  At most PIPELINE_DEPTH
               units are between selection and materialised parity;
               waiting for a slot is `stall`.
      dispatch (caller's thread) launches the parity matmul for unit N
               through the seam: asynchronous on JAX backends (`h2d`: the
               spans put as 1-D arrays from where they lie, and
               `dispatch`, which add up to `encode`), eager for a host
               codec (`dispatch`: the native shell reads the spans by
               pointer where they lie)
      drain    materialises unit N-1's parity (the seam's `device_wait`
               and `d2h_copy`, which add up to `d2h`: the device sync
               point; a host codec's is already here), m runs of [W], and
               hands each parity shard's writer its own at shard_off: m
               writes a unit
      writers  striped pwrite workers over the shard fds
               (_ShardWriterPool), so parity files land concurrently
               instead of serially

    A unit's slot frees as soon as its parity is materialised — until
    then the device may still be reading the (possibly
    zero-copy-aliased on CPU backends) host memory, so the unit's spans
    ride its queue item that long; the map itself outlives the call and
    a sealed .dat does not change.  Parity runs are the materialised
    arrays, kept alive by the writer queue items.  `progress` and
    `cancel` are called once a unit."""
    k, m = codec.k, codec.m
    # no buffer is held: `slots` bounds the units in flight
    slots = threading.BoundedSemaphore(PIPELINE_DEPTH)
    q_read: queue.Queue = queue.Queue(maxsize=PIPELINE_DEPTH)
    # q_disp is unbounded: it carries at most one entry per in-flight
    # unit (the slots are the real backpressure)
    q_disp: queue.Queue = queue.Queue()
    errors: list[BaseException] = []
    done = 0

    def reader() -> None:
        nonlocal done
        flusher = _ShardFlusher(writers, k)  # data shards only
        try:
            for unit, (row_start, block, col, step, shard_off,
                       rows) in enumerate(_iter_spans(
                           dat_size, large_block, small_block, batch_size,
                           k)):
                if errors or writers.failed:  # downstream died: stop
                    break
                if cancel is not None and cancel():
                    raise EncodeCancelled("ec encode cancelled")
                covered = 0
                with pjob.stage("ship_data", unit=unit):
                    for r in range(rows):
                        nz, tail = _unit_coverage(
                            dat_size, row_start + r * k * block, block, col,
                            step, data_shards=k)
                        for j in range(nz):
                            off = row_start + (r * k + j) * block + col
                            flusher.copy(j, dat_fd, off,
                                         shard_off + r * step,
                                         step if j < nz - 1 else tail,
                                         src_view=dat_view)
                        if nz:
                            covered += (nz - 1) * step + tail
                            flusher.account(step)
                if not covered:
                    continue
                pjob.count("spans_mapped", 1)
                # a column cut of one (large) block row, or whole rows
                pjob.count("units_column" if step != block else "units_rows",
                           1)
                with pjob.blocked("stall", unit=unit):
                    slots.acquire()
                with pjob.stage("read", unit=unit, rows=rows, block=block):
                    spans, staged = _unit_spans(
                        dat_view, dat_size, k, row_start, block, col, step,
                        rows)
                    if staged:
                        pjob.count("rows_staged", staged)
                q_read.put((unit, spans, rows * step, shard_off,
                            (rows, block)))
                done += covered
                if progress is not None:
                    progress(done)
            flusher.flush()
        except BaseException as e:  # surfaced by the caller's thread
            errors.append(e)
        finally:
            q_read.put(None)

    def drain() -> None:
        failed = False
        # production-size units submit DIRECTLY: each unit's parity is
        # on its writer the moment its d2h lands, so write_parity busy
        # time overlaps the next unit's d2h instead of queueing behind a
        # flush-group boundary.  Tiny units keep the batcher — per-unit
        # queue hops would cost more than the writes.
        flusher = _make_sink(writers, k + m, _min_step(
            dat_size, large_block, small_block, batch_size, k))
        while True:
            with pjob.blocked("await_parity"):
                item = q_disp.get()
            if item is None:
                flusher.flush()
                return
            unit, spans, step, shard_off, parity = item
            if failed or errors or writers.failed:
                pjob.occupancy("inflight", -1)
                slots.release()
                continue
            try:
                pnp = _materialize(parity, job=pjob, unit=unit)
            except BaseException as e:
                errors.append(e)
                failed = True  # keep draining so nothing deadlocks
                continue
            finally:
                del spans, item  # the device is done with the host memory
                pjob.occupancy("inflight", -1)
                slots.release()
            # parity row i: a device shell's run, or row i of an [m, step]
            for i, run in enumerate(pnp):
                flusher.put(k + i, run[:step], shard_off)
            flusher.account(step)

    t_r = threading.Thread(target=reader, name="ec-reader", daemon=True)
    t_d = threading.Thread(target=drain, name="ec-drain", daemon=True)
    with pjob.stage("open"):
        writers = _ShardWriterPool(
            out_fds, highwater, pjob,
            stage_of=lambda i: "write_data" if i < k else "write_parity")
        t_r.start()
        t_d.start()
    try:
        while True:
            with pjob.blocked("await_unit"):
                item = q_read.get()
            if item is None:
                break
            # stage-queue depth at the consume site
            pjob.queue("q_read", q_read.qsize(), PIPELINE_DEPTH)
            # geom: the unit's stripe rows and their block size
            unit, spans, step, shard_off, geom = item
            if errors or writers.failed:  # stop dispatching, surface below
                slots.release()
                continue
            try:
                parity = _dispatch_parity(codec, spans, job=pjob, unit=unit,
                                          stripes=geom[0], block=geom[1])
            except BaseException as e:
                errors.append(e)  # the reader stops at its next unit
                slots.release()
                raise
            # out on the device until the drain has its parity
            pjob.occupancy("inflight", +1)
            q_disp.put((unit, spans, step, shard_off, parity))
            del item, spans  # the queue item alone holds a unit's views
    finally:
        with pjob.blocked("join_drain"):
            q_disp.put(None)
            t_d.join()
            # unblock a reader stuck on q_read or a slot
            while t_r.is_alive():
                try:
                    item = q_read.get(timeout=0.05)
                except queue.Empty:
                    continue
                if item is not None:
                    slots.release()
            t_r.join()
        # after the producers: no submission can block now (`join_writers`)
        writers.close()
    if errors:
        raise errors[0]
    if writers.errors:
        raise writers.errors[0]


def _survivor_basis(codec, present: list[int],
                    wanted: list[int]) -> list[int]:
    """Which surviving shard files a rebuild must actually read.  RS/MDS:
    any k.  LRC: the code's decode_select picks a minimal span (one local
    group for a single loss).  MSR whole-file rebuild: the file codec's
    node-MDS selection (any k whole files)."""
    sel = getattr(codec, "decode_select", None)
    if sel is not None:  # file-surface hook (MSRFileCodec)
        return list(sel(sorted(present), list(wanted)))
    from seaweedfs_tpu.ops import codec_base as _cb
    code = getattr(codec, "code", codec)
    return list(_cb.select_survivors(code, tuple(sorted(present)),
                                     list(wanted)))


def basis_kind(codec, use: list[int]) -> str:
    """`local` when the survivors a decode reads all lie in one local
    group of the code (an LRC's one-lost repair: r files, not k), else
    `global` (any MDS code, and an LRC's fallback over the whole set)."""
    code = getattr(codec, "code", codec)
    group_of = getattr(code, "group_of", None)
    if group_of is None:
        return "global"
    groups = {group_of(i) for i in use}
    return "local" if len(groups) == 1 and None not in groups else "global"


def _write_rows(writers: "_ShardWriterPool", opool: queue.Queue,
                obuf: np.ndarray, rows: int, n: int, off: int) -> None:
    """Hand a rebuild batch's rows, `obuf[r, :n]` for the volume's r-th
    lost shard (r < rows), to their writers at `off`; the buffer goes back
    to the output ring once every writer is done with its row."""
    release = _countdown(rows, lambda: opool.put(obuf))
    for r in range(rows):
        writers.put(r, obuf[r, :n], off, release=release)


# why a listed volume is answered under `skipped` with its files untouched
NOTHING_MISSING = "no shard missing"


class _RebuildVolume:
    """One volume of a rebuild call: its code, the shards it lacks, the
    survivors its decode reads (`use`) and, once opened, the survivor files
    and their maps, its `.tmp` outputs and their writer pool.  A batch is
    `batch` bytes of each survivor file at one offset.  `enqueued` is
    moved by the calling thread alone and `drained` by the drain alone, so
    `enqueued > drained` says, without a lock, that a batch of the volume
    is out.  `state`: planned, open, committed or rolled back; opened,
    committed and rolled back on the calling thread, which also holds the
    volume's profiler annotation over that span."""

    def __init__(self, base: str, spec, codec, present: list[int],
                 missing: list[int], batch_size: int):
        self.base, self.spec, self.codec = base, spec, codec
        self.missing = missing
        self.use = _survivor_basis(codec, present, missing)
        self.shard_size = os.path.getsize(base + layout.to_ext(self.use[0]))
        # MSR sub-packetization: every chunk a codec's interleave must see
        # is an alpha multiple (shard files themselves are block-multiples)
        self.batch = batch_size
        if spec.alpha > 1:
            self.batch = max(spec.alpha, batch_size - batch_size % spec.alpha)
            if self.shard_size % spec.alpha:
                raise ValueError(f"shard size {self.shard_size} not "
                                 f"{spec.alpha}-aligned")
        self.tmp_paths = {i: base + layout.to_ext(i) + ".tmp"
                          for i in missing}
        self.ins: dict[int, object] = {}
        self.maps: dict = {}
        self.views: dict = {}
        self.out_fds: dict[int, int] = {}
        self.writers: _ShardWriterPool | None = None
        self.enqueued = self.drained = 0
        self.state = "planned"
        self._ann = None

    def offsets(self) -> range:
        return range(0, self.shard_size, self.batch)

    def open(self, pjob) -> None:
        """Survivors opened, tmp outputs created, the writer pool started
        (`open`), the survivors mapped with no page made ready (`map`)."""
        self.state = "open"
        name = os.path.basename(self.base)
        self._ann = pjob.annotate(
            "rebuild.volume", volume=name, vid=name.rpartition("_")[2],
            lost=",".join(map(str, self.missing)))
        with pjob.stage("open", files=len(self.use) + len(self.missing)):
            for i in self.use:
                self.ins[i] = open(self.base + layout.to_ext(i), "rb")
            for i, p_ in self.tmp_paths.items():
                self.out_fds[i] = os.open(p_, os.O_RDWR | os.O_CREAT, 0o644)
            self.writers = _ShardWriterPool(
                [self.out_fds[i] for i in self.missing], None, pjob)
        with pjob.stage("map", files=len(self.use),
                        bytes=self.shard_size * len(self.use)):
            for i, f in self.ins.items():
                if self.shard_size:
                    mm = _map_lazy(f.fileno())
                    self.maps[i] = mm
                    self.views[i] = np.frombuffer(mm, dtype=np.uint8)

    def commit(self, pjob) -> None:
        """The outputs cut to size and renamed into place (`commit`) once
        the writer pool has written everything queued: at once where it is
        `idle` (its threads are joined with the call's other pools at the
        end), else after its close (`join_writers`).  The pool's first
        error is raised instead, and the volume left for `roll_back`."""
        if not self.writers.idle:
            self.writers.close()
        if self.writers.errors:
            raise self.writers.errors[0]
        with pjob.stage("commit"):
            for fd in self.out_fds.values():
                os.ftruncate(fd, self.shard_size)
            self._release(commit=True)

    def roll_back(self, pjob) -> None:
        """Whatever was opened closed, the tmp outputs removed: the volume's
        shard files are as they were before the call."""
        if self.writers is not None:
            self.writers.close()  # idempotent; the fds must outlive it
        with pjob.stage("commit"):
            self._release(commit=False)

    def _release(self, commit: bool) -> None:
        """The runtime reads a row after its put returns: every batch's
        views died with its queue item before this is reached.  Where a
        reference outlives the walk all the same (a traceback, the CPU
        backend aliasing an aligned view of the read-only maps)
        `mm.close()` raises BufferError, let pass: the mapping goes with
        its last view."""
        for f in self.ins.values():
            f.close()
        self.views.clear()
        for mm in self.maps.values():
            try:
                mm.close()
            except BufferError:
                pass
        for fd in self.out_fds.values():
            os.close(fd)
        if commit:
            for i, p_ in self.tmp_paths.items():
                os.replace(p_, self.base + layout.to_ext(i))
        else:
            for p_ in self.tmp_paths.values():
                try:
                    os.remove(p_)
                except OSError:
                    pass
        self.state = "committed" if commit else "rolled back"
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


def _pool_failure(vols: list[_RebuildVolume]) -> BaseException | None:
    """The first writer error of the first volume the walk left open."""
    for vol in vols:
        if vol.state == "open":
            vol.writers.close()
            return vol.writers.errors[0] if vol.writers.errors else None
    return None


def _boundaries(pjob, crossed: list[int]) -> None:
    """`boundaries_in_flight`: of the call's volume boundaries the walk
    reached, the share at which the next volume's first batch was enqueued
    while a batch of the volume before was out (`crossed`: [those, all])."""
    if crossed[1]:
        pjob.stats["boundaries_in_flight"] = round(crossed[0] / crossed[1], 4)


def _rebuild_pipelined(vols: list[_RebuildVolume], opool: queue.Queue,
                       pjob, progress=None, cancel=None,
                       commit=None) -> None:
    """Rebuild's batches through the dispatch seam, encode's shape
    (`_encode_pipelined`) with the reader and the dispatcher one thread,
    and one pipeline over every volume of the call: no byte moves on the
    host before a put, so there is nothing to read ahead.

      caller   walks the volumes and their batches: opens and maps a
               volume, then for each batch waits for one of
               PIPELINE_DEPTH slots (`stall`), selects the batch's rows in
               the volume's maps (`stage`: no byte moves) and enqueues them
               with the volume's own survivors and lost shards (the seam's
               `h2d` and `dispatch`).  A device codec's result comes back
               un-materialised, so batch N+1's rows go up while batch N is
               out, and the next volume's first batch while the last
               batches of the one before are (no drain between volumes); a
               host codec computes here (`dispatch`: the native shell
               reads the rows by pointer where they lie).  Between batches
               it commits every volume whose rows the writers have all
               written (`_ShardWriterPool.idle`: no wait)
      drain    materialises batch N (the seam's `device_wait` and
               `d2h_copy`), frees its slot, waits for a buffer of the output
               ring (`stall`), copies the rebuilt rows into it (`unstage`)
               and hands each to its volume's writer for that shard; after a
               volume's last batch it hands the volume back to the caller
      writers  one `_ShardWriterPool` a volume; a buffer returns to the ring
               once every writer is done with its row

    The six stages book to the one job from two threads and add up to
    `reconstruct` (REBUILD_SUMS), which may so pass the wall.  A batch's
    views, its stacked copy if it had one and its device arrays ride its
    queue item and die when its result is materialised.  However the walk
    ends (the last batch, `cancel`, a failed writer, an exception from
    either half of the seam), every batch that went up is waited for and
    the drain thread joined before this returns: the caller closes the maps
    next.  Then every volume whose rows all reached its writers commits, in
    order, up to the first whose writers failed; the rest are the caller's
    to roll back.  The first error is raised here: what ended the walk,
    else the drain's, else a commit's, else the first failed writer pool's.
    The job's gauge `inflight` counts the batches between enqueue and
    materialised result (`stats["inflight_max"]`, `inflight_avg`,
    `inflight_ge2_frac`); the drain's wait for the next one is
    `await_batch`, the caller's for the drain to end `join_drain`."""
    slots = threading.BoundedSemaphore(PIPELINE_DEPTH)
    q_out: queue.Queue = queue.Queue()  # unbounded: `slots` is the bound
    # volumes whose every row is with their writers, in order (the drain
    # appends, the caller pops)
    whole: collections.deque = collections.deque()
    errors: list[BaseException] = []
    halted: list[bool] = []  # the drain hands no more rows or volumes on

    def drain() -> None:
        while True:
            with pjob.blocked("await_batch"):
                item = q_out.get()
            if item is None:
                return
            vol, unit, off, n, last, pending = item
            if pending is None:  # a volume of empty files: no batch
                del item
                if not halted:
                    whole.append(vol)
                continue
            try:
                # a batch of a run that failed is waited for like any other:
                # the device may be reading its rows in the maps
                rebuilt = _materialize_rows(pending, job=pjob, unit=unit)
            except BaseException as e:  # raised by the caller's thread
                errors.append(e)
                halted.append(True)
                continue
            finally:
                del item, pending  # the device is done with the host memory
                vol.drained += 1
                pjob.occupancy("inflight", -1)
                slots.release()
            if halted or vol.writers.failed:
                halted.append(True)
                continue
            with pjob.blocked("stall", unit=unit):
                obuf = opool.get()
            with pjob.stage("unstage", unit=unit):
                for r, i in enumerate(vol.missing):
                    np.copyto(obuf[r, :n], rebuilt[i])
            del rebuilt
            _write_rows(vol.writers, opool, obuf, len(vol.missing), n, off)
            if last:
                whole.append(vol)

    failed_commit: list[bool] = []  # no volume commits after one failed

    def commit_whole(wait: bool) -> None:
        """Commit the volumes whose rows are all with their writers, in
        order, until one fails; mid-walk (`wait` false) only those whose
        writes are done, so that the caller is not held from the next
        enqueue."""
        while whole and not failed_commit and (wait or whole[0].writers.idle):
            try:
                commit(whole.popleft())
            except Exception:
                failed_commit.append(True)
                raise

    crossed = [0, 0]

    def walk() -> None:
        done = unit = 0
        for j, vol in enumerate(vols):
            commit_whole(False)
            vol.open(pjob)
            offsets = vol.offsets()
            if not offsets:  # in order behind the batches before it
                q_out.put((vol, None, 0, 0, True, None))
            for b, off in enumerate(offsets):
                if cancel is not None and cancel():
                    raise EncodeCancelled("ec rebuild cancelled")
                if errors or any(v.writers.failed for v in vols
                                 if v.state == "open"):
                    return
                n = min(vol.batch, vol.shard_size - off)
                with pjob.blocked("stall", unit=unit):
                    slots.acquire()
                try:
                    with pjob.stage("stage", unit=unit):
                        rows = [vol.views[i][off:off + n] for i in vol.use]
                    pjob.count("spans_mapped", len(rows))
                    pending = _dispatch_reconstruct(
                        vol.codec, rows, vol.use, vol.missing, job=pjob,
                        unit=unit)
                except BaseException:
                    slots.release()
                    raise
                if b == 0 and j:
                    before = vols[j - 1]
                    crossed[0] += before.enqueued > before.drained
                    crossed[1] += 1
                vol.enqueued += 1
                pjob.occupancy("inflight", +1)
                q_out.put((vol, unit, off, n, b == len(offsets) - 1,
                           pending))
                del rows, pending  # the queue item alone holds the views
                unit += 1
                done += n * len(vol.use)
                if progress is not None:
                    progress(done)
                commit_whole(False)

    t_d = threading.Thread(target=drain, name="ec-rebuild-drain",
                           daemon=True)
    with pjob.stage("open"):
        t_d.start()
    failure = None
    try:
        walk()
    except BaseException as e:  # raised below, once the drain is joined
        failure = e
    finally:
        with pjob.blocked("join_drain"):
            q_out.put(None)
            t_d.join()
        _boundaries(pjob, crossed)
        for vol in vols:  # no row is handed on any more
            if vol.writers is not None:
                vol.writers.stop()
    failure = failure or (errors[0] if errors else None)
    try:
        commit_whole(True)
    except Exception as e:  # a failed writer pool's error, re-raised below
        failure = failure or e
    failure = failure or _pool_failure(vols)
    if failure is not None:
        raise failure


def rebuild_ec_volumes(bases: list[str], batch_size: int = DEFAULT_BATCH,
                       progress=None, cancel=None, stats=None,
                       codec_tags: list | None = None) -> dict:
    """Regenerate whichever `.ecXX` files are missing from each set of
    `bases` (a rebuilder's backlog: `ec.rebuild` after a server is lost),
    each under the code its `.vif` names (or `codec_tags[i]`), in one call
    and one pipeline.  -> the report: `rebuilt` {base: the shard ids
    rebuilt} for every volume committed, `skipped` {base: why} for every
    volume left as it was before anything was opened: nothing missing
    (`NOTHING_MISSING`), or fewer survivors than the code's k.  A call that
    raises hands the same report on as the exception's `report`.

    *What commits when.*  A volume commits by rename as soon as its last
    rows are written (the tmp outputs cut to size and renamed into place
    on the calling thread, between batches of the volumes after it), not
    at the end of the call: volumes already committed stay so whatever
    happens later.  A call that ends early (`cancel()`, a failed writer, an
    exception from either half of the dispatch seam, a vanished survivor)
    raises what ended it once every batch that went up is back; by then
    every volume whose rows had all reached its writers is committed, in
    order, up to the first whose writers failed, and the volume in flight
    and every one after it are untouched (their tmp outputs removed, no
    file renamed).  The report's `rebuilt` says which volumes committed, on
    either way out.

    Per volume, only the survivors of the code's basis are opened and
    mapped (`_survivor_basis`): any k for an MDS code, the r of one local
    group for an LRC's one-lost repair; `stats` says how many a volume
    reads and which kind (`survivors`, the most over the volumes; `basis`,
    `mixed` where the volumes differ), how many volumes the call walked
    (`volumes`) and how many rows a batch rebuilds (`lost_rows`, the most
    over the volumes).

    The encode path's observability (`progress(bytes_done)` per batch over
    survivor bytes of the whole call, `cancel()` aborts, `stats` gets
    per-stage seconds summed over the volumes + overlap_frac) and its
    zero-copy reads: survivor shards are mmap'd (`_map_lazy`, stage `map`:
    no page is made ready before the first put, a batch's faults are taken
    by whoever reads its rows) and a batch is one volume's rows where they
    lie in the maps (`stats["spans_mapped"]` counts them: batches x
    survivors), handed to the dispatch seam as views, which the native
    host codec reads by row pointer and a device codec puts up uncopied where
    they are a whole bucket wide or its program reads them in place at
    their own width (`ops/dispatch.ROW_PUTS_FROM`; `stats["narrow"]`
    counts the batches put below their bucket, a volume's short last
    one, and `stats["rows_staged"]` the rows copied first: such a batch
    under a codec whose program does not read it in place).  Rebuilt
    shards land in a countdown-released buffer ring, one for the call, and
    stream to each volume's per-shard writer workers into recycled `.tmp`
    inodes (reference: RebuildEcFiles,
    ec_encoder.go:237-291; the backlog is `ec.rebuild`'s loop,
    command_ec_rebuild.go).

    Every volume goes through encode's reader -> dispatch -> drain shape
    (`_rebuild_pipelined`, `stats["mode"]` `pipelined`), whatever its
    codec (a host codec computes at the enqueue): up to
    PIPELINE_DEPTH batches, of one volume or of two, are between their put
    and their materialised result, batch N+1's rows going up while batch
    N's program, copy back, `unstage` and writes run;
    `stats["inflight_max"]` says how many there were at most and
    `boundaries_in_flight` at what share of the volume boundaries a batch
    of the volume before was still out (1.0: no drain between volumes).
    Each volume is on the profiler's trace as `rebuild.volume` (its name,
    vid and lost shards) from its open to its commit."""
    report: dict = {"rebuilt": {}, "skipped": {}}
    try:
        _rebuild_volumes(bases, batch_size, progress, cancel, stats,
                         codec_tags, report)
    except Exception as e:
        e.report = report  # what committed before it, and what was skipped
        raise
    return report


def _rebuild_volumes(bases, batch_size, progress, cancel, stats, codec_tags,
                     report: dict) -> None:
    """`rebuild_ec_volumes`' body, filling its report's `rebuilt` and
    `skipped` as it goes."""
    from seaweedfs_tpu.ops import codecs as _codecs
    from seaweedfs_tpu.maintenance import faults as _faults
    rebuilt, skipped = report["rebuilt"], report["skipped"]
    vols: list[_RebuildVolume] = []
    for base, tag in zip(bases, codec_tags or [None] * len(bases)):
        spec = _codecs.parse_tag(tag or (read_vif(base) or {}).get("codec"))
        present = [i for i in range(spec.n)
                   if os.path.exists(base + layout.to_ext(i))]
        missing = [i for i in range(spec.n) if i not in present]
        if not missing:
            skipped[base] = NOTHING_MISSING
            continue
        if len(present) < spec.k:
            skipped[base] = (f"need >= {spec.k} shards to rebuild, "
                             f"have {len(present)}")
            continue
        # chaos hook: fail like a dying disk BEFORE tmp shard files exist
        _faults.check_shard_write(base)
        vols.append(_RebuildVolume(base, spec, _get_codec(tag=spec.tag),
                                   present, missing, batch_size))
    if not vols:
        return
    stats = stats if stats is not None else {}
    tags = sorted({v.spec.tag for v in vols})
    kinds = sorted({basis_kind(v.codec, v.use) for v in vols})
    lost_rows = max(len(v.missing) for v in vols)
    stats["bytes"] = sum(v.shard_size * len(v.use) for v in vols)
    stats["codec"] = ",".join(tags)
    stats["volumes"] = len(vols)
    stats["lost_rows"] = lost_rows
    # what the rebuild reads: how many survivor files a volume stages and
    # whether they are one local group (/admin/ec/progress `stages`)
    stats["survivors"] = max(len(v.use) for v in vols)
    stats["basis"] = kinds[0] if len(kinds) == 1 else "mixed"
    stats["rows_staged"] = 0  # the dispatch seam counts (PipelineJob.count)
    stats["in_place"] = 0  # batches read where they were put (the seam too)
    stats["narrow"] = 0  # batches put at their own width, below the bucket
    stats["spans_mapped"] = 0  # rows selected in the maps: batches x survivors
    stats["inflight_max"] = 0  # the job's gauge (_rebuild_pipelined) says
    stats["mode"] = "pipelined"

    # a rebuild IS repair work: unless a caller already declared a class
    # (the planner's header re-entered through the middleware), any
    # network hop made on this thread while we run — a remote
    # shard_reader for survivors not on local disk — books as repair
    _flow_token = _netflow.set_class(_netflow.current_class() or "repair")
    pjob = _pipeline.track("ec_rebuild", stats, stats["bytes"],
                           meta={"volumes": len(vols), "missing": lost_rows,
                                 "codec": stats["codec"]},
                           span="ec.rebuild", sums=REBUILD_SUMS)
    # the job's own span, round the stages named after it
    job_span = _trace.span("ec.rebuild", codec=stats["codec"],
                           volumes=len(vols), missing=lost_rows,
                           survivors=stats["survivors"],
                           basis=stats["basis"])
    job_span.__enter__()
    t_wall = time.perf_counter()
    ok = False

    def commit(vol: _RebuildVolume) -> None:
        vol.commit(pjob)
        rebuilt[vol.base] = list(vol.missing)

    # the walk runs under the same finally that seals the job: a survivor
    # deleted between the present-list and open (a racing repair), or
    # ENOSPC on the tmp outputs, must not leak a forever-"running"
    # ec_rebuild entry on /debug/pipeline
    try:
        with pjob.stage("open"):
            # the output ring, one for the call: rows of the most a batch
            # rebuilds, as wide as the widest batch
            width = max(min(v.batch, max(v.shard_size, 1)) for v in vols)
            opool: queue.Queue = queue.Queue()
            for _ in range(PIPELINE_DEPTH):
                opool.put(np.empty((lost_rows, width), dtype=np.uint8))
        _rebuild_pipelined(vols, opool, pjob, progress, cancel, commit)
        stats["wall_s"] = time.perf_counter() - t_wall
        _book_stage_bytes(pjob, stats, stats["bytes"],
                          sum(v.shard_size * len(v.missing) for v in vols))
        ok = True
    finally:
        _netflow.reset(_flow_token)
        error = None if ok else (sys.exc_info()[1] or "rebuild failed")
        try:
            for vol in vols:
                if vol.writers is not None:
                    vol.writers.stop()
            for vol in vols:
                if vol.state == "open":
                    vol.roll_back(pjob)
                elif vol.writers is not None:
                    vol.writers.close()  # a pool committed idle
        finally:
            # the job is the call: it is sealed after the last commit, and
            # after close() folded the writer-pool busy seconds into
            # stats — finish() exports the stage counters, and a failed
            # rebuild must not export zero write-stage occupancy.  The
            # in-flight exception (ENOSPC, vanished survivor) is the
            # error operators triage from /debug/pipeline, not a generic
            # tag
            _state_overlap(stats)
            pjob.finish(error)
            job_span.__exit__(*sys.exc_info())


def rebuild_ec_files(base: str, batch_size: int = DEFAULT_BATCH,
                     progress=None, cancel=None, stats=None,
                     codec_tag: str | None = None) -> list[int]:
    """Regenerate whichever `.ecXX` files are missing from the present
    ones, under the code the `.vif` names (or `codec_tag`): the one-volume
    case of `rebuild_ec_volumes`, which says how, what commits when, and
    what a cancel or a failure leaves.  Returns the rebuilt shard ids, []
    where none is missing; fewer survivors than the code's k is a
    ValueError, before anything is opened."""
    report = rebuild_ec_volumes([base], batch_size, progress, cancel, stats,
                                codec_tags=[codec_tag])
    why = report["skipped"].get(base)
    if why is not None and why != NOTHING_MISSING:
        raise ValueError(why)
    return report["rebuilt"].get(base, [])


def rebuild_ec_reduced(base: str, lost: list[int], groups: list[dict],
                       fetch_partial, d: int | None = None,
                       batch_size: int = DEFAULT_BATCH,
                       align: int | None = None,
                       progress=None, cancel=None,
                       stats: dict | None = None,
                       codec_tag: str | None = None) -> dict:
    """Reduced-read rebuild of `lost` shards: instead of copying k full
    survivor shards here, each remote helper node ships XOR-combinable
    partial products (ops/regen.py) — repair bandwidth per remote node
    drops to one shard-range per lost shard, byte-identical output.

    `groups` lists the REMOTE helper nodes: {"node": url,
    "shards": [ids], "locality": class}; the local survivor group is
    discovered from the files next to `base` (each rebuilt shard joins
    it for the next pass).  `fetch_partial(node, shards, coeff_rows,
    offset, size) -> bytes` is the server layer's HTTP hop; transport
    failures raise regen.HelperDied and trigger re-planning with a
    substitute survivor.  Lost shards build under `.tmp` names and
    commit by rename per shard, so a helper death / crash never leaves
    a partial shard visible.  Returns accounting: measured helper
    bytes per node + locality class, the plans' predictions, and the
    naive-baseline cost the savings are judged against."""
    from seaweedfs_tpu.ops import regen

    # chaos hook: fail like a dying disk BEFORE tmp shard files exist
    from seaweedfs_tpu.maintenance import faults as _faults
    _faults.check_shard_write(base)

    from seaweedfs_tpu.ops import codecs as _codecs
    spec = _codecs.parse_tag(codec_tag or
                             (read_vif(base) or {}).get("codec"))
    codec = _get_codec(tag=spec.tag)
    if spec.family == "msr":
        # plan coordinates are sub-rows: a batch of S sub-row bytes costs
        # each helper an S*alpha-byte file read — shrink so the helper-
        # side pread stays bounded by the plain path's batch
        batch_size = max(spec.alpha, batch_size // spec.alpha)
    code = getattr(codec, "code", codec)  # RSCode is its own metadata

    lost = sorted(set(lost))
    local_fds: dict[int, int] = {}
    stats = stats if stats is not None else {}
    stats.setdefault("mode", "reduced")
    # unregistered: the planner's own accounting (helper bytes, replans)
    # is what a reduced rebuild reports; the job times `reconstruct`
    pjob = _pipeline.PipelineJob("ec_rebuild", stats, register=False,
                                 span="ec.rebuild")
    _flow_token = _netflow.set_class(_netflow.current_class() or "repair")
    t_wall = time.perf_counter()
    try:
        shard_size = 0
        for i in range(spec.n):
            p_ = base + layout.to_ext(i)
            if i not in lost and os.path.exists(p_):
                local_fds[i] = os.open(p_, os.O_RDONLY)
                shard_size = max(shard_size, os.path.getsize(p_))
        if shard_size == 0:
            for g in groups:
                if g.get("shard_size"):
                    shard_size = int(g["shard_size"])
                    break
        if shard_size <= 0:
            raise ValueError(f"cannot size shards of {base}")
        stats["bytes"] = shard_size * len(lost)
        stats["codec"] = spec.tag
        alpha = spec.alpha
        if alpha > 1 and shard_size % alpha:
            raise ValueError(
                f"shard size {shard_size} not {alpha}-aligned for {spec.tag}")

        def read_local(sid: int, off: int, n: int) -> bytes | None:
            fd = local_fds.get(sid)
            if fd is None:
                return None
            try:
                return os.pread(fd, n, off)
            except OSError:
                return None

        # MSR plans address SUB-ROWS: virtual id = file_shard*alpha + row,
        # offsets/lengths in sub-row bytes.  A sub-row is the byte-
        # interleaved slice {t*alpha + row} of its shard file, so reading
        # one means one contiguous pread of [off*alpha, (off+n)*alpha)
        # de-interleaved on the fly; a one-slot cache serves the alpha
        # consecutive sub-row reads execute_plan makes per local file
        # from a single pread.
        _vblk: dict = {}

        def read_local_sub(vid: int, off: int, n: int) -> bytes | None:
            fsid = vid // alpha
            fd = local_fds.get(fsid)
            if fd is None:
                return None
            key = (fsid, off, n)
            blk = _vblk.get(key)
            if blk is None:
                try:
                    raw = os.pread(fd, n * alpha, off * alpha)
                except OSError:
                    return None
                if len(raw) != n * alpha:
                    return None
                blk = np.frombuffer(raw, np.uint8).reshape(n, alpha)
                _vblk.clear()
                _vblk[key] = blk
            return blk[:, vid % alpha].tobytes()

        remote_groups = [
            regen.HelperGroup(node=g["node"],
                              shards=tuple(int(s) for s in g["shards"]
                                           if int(s) not in lost),
                              locality=int(g.get("locality", 3)))
            for g in groups if g.get("shards")]
        done = 0
        predicted: dict = {"per_node": {}, "by_locality": {},
                           "remote": 0, "local": 0}
        for sid in lost:
            tmp = base + layout.to_ext(sid) + ".tmp"
            out_fd = os.open(tmp, os.O_RDWR | os.O_CREAT, 0o644)
            committed = False
            try:
                if spec.family == "msr":
                    # regenerating repair: [alpha, d] posts land as an
                    # [alpha, n] block per segment — re-interleave back
                    # into shard-file byte order on the way to disk
                    def sink(off: int, rows: np.ndarray,
                             fd: int = out_fd) -> None:
                        rows = np.asarray(rows)
                        if rows.ndim == 1:
                            rows = rows.reshape(1, -1)
                        _pwrite_all(
                            fd,
                            np.ascontiguousarray(rows.T.reshape(-1)),
                            off * alpha)

                    planner = regen.plan_msr_repair
                    plan_code = codec  # file codec carries the inner code
                    reader = read_local_sub
                else:
                    def sink(off: int, row: np.ndarray,
                             fd: int = out_fd) -> None:
                        _pwrite_all(fd, np.ascontiguousarray(row), off)

                    planner = None
                    plan_code = code
                    reader = read_local

                local_group = regen.HelperGroup(
                    node="", shards=tuple(sorted(local_fds)), locality=0)
                with pjob.stage("reconstruct", unit=sid):
                    plan = regen.repair_shard(
                        plan_code, codec, sid,
                        [local_group] + remote_groups, shard_size,
                        reader, fetch_partial, sink,
                        d=d, batch_size=batch_size,
                        align=align or regen.DEFAULT_SEG_ALIGN,
                        cancel=cancel, stats=stats,
                        planner=planner)
                os.ftruncate(out_fd, shard_size)
                os.close(out_fd)
                out_fd = -1
                os.replace(tmp, base + layout.to_ext(sid))
                committed = True
                pred = plan.predicted_bytes()
                for key in ("remote", "local"):
                    predicted[key] += pred[key]
                for dim in ("per_node", "by_locality"):
                    for k_, v in pred[dim].items():
                        predicted[dim][k_] = predicted[dim].get(k_, 0) + v
                predicted["naive_remote"] = \
                    predicted.get("naive_remote", 0) + \
                    plan.naive_remote_bytes(len(local_group.shards))
                # the rebuilt shard is a local survivor for the next pass
                local_fds[sid] = os.open(base + layout.to_ext(sid),
                                         os.O_RDONLY)
                done += shard_size
                if progress is not None:
                    progress(done)
            finally:
                if out_fd >= 0:
                    os.close(out_fd)
                if not committed:
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
        stats["wall_s"] = time.perf_counter() - t_wall
        return {"rebuilt": lost, "shard_size": shard_size,
                "helper_bytes": stats.get("helper_bytes", {}),
                "by_locality": stats.get("by_locality", {}),
                "predicted": predicted,
                "replans": stats.get("replans", 0),
                "dead_helpers": stats.get("dead_helpers", [])}
    finally:
        _netflow.reset(_flow_token)
        for fd in local_fds.values():
            try:
                os.close(fd)
            except OSError:
                pass


def write_dat_file(base: str, dat_size: int,
                   out_path: str | None = None,
                   data_shards: int | None = None) -> None:
    """Data shard files -> `<base>.dat` (row-major interleave copy).
    ``out_path`` redirects the output (the un-convert path decodes into
    a temp name and renames, so a crash mid-decode can never leave a
    half-written .dat a restart would mount as live data).  The stripe
    width k comes from the volume's .vif codec tag unless overridden, the
    block sizes from the .vif's record alone (`volume_blocks`): a set is
    never un-striped with other blocks than it was cut with."""
    vif = read_vif(base) or {}
    large_block, small_block = volume_blocks(base, vif)
    if data_shards is None:
        from seaweedfs_tpu.ops import codecs as _codecs
        data_shards = _codecs.parse_tag(vif.get("codec")).k
    rows = layout.n_large_rows(dat_size, large_block, small_block,
                               data_shards=data_shards)
    ins = [open(base + layout.to_ext(i), "rb")
           for i in range(data_shards)]
    written = 0
    try:
        with open(out_path or (base + ".dat"), "wb") as dat:
            for r in range(rows):
                for j in range(data_shards):
                    ins[j].seek(r * large_block)
                    n = min(large_block, dat_size - written)
                    if n <= 0:
                        return
                    dat.write(ins[j].read(n))
                    written += n
            small_base = rows * large_block
            r = 0
            while written < dat_size:
                for j in range(data_shards):
                    ins[j].seek(small_base + r * small_block)
                    n = min(small_block, dat_size - written)
                    if n <= 0:
                        return
                    dat.write(ins[j].read(n))
                    written += n
                r += 1
    finally:
        for f in ins:
            f.close()


def write_sorted_ecx(idx_path: str, ecx_path: str | None = None) -> None:
    """.idx -> .ecx: 16-byte entries sorted by needle id ascending, ONE entry
    per id. The .idx is a log, so the last occurrence of an id (re-write or
    tombstone) is its truth — keeping duplicates would make the binary
    search land on the oldest entry and resurrect stale data."""
    ecx_path = ecx_path or idx_path[: -len(".idx")] + ".ecx"
    with open(idx_path, "rb") as f:
        data = f.read()
    ids, offs, sizes = idxf.read_columns(data)
    latest: dict[int, tuple[int, int]] = {}
    for nid, off, size in zip(ids.tolist(), offs.tolist(), sizes.tolist()):
        latest[nid] = (off, size)
    with open(ecx_path, "wb") as out:
        for nid in sorted(latest):
            off, size = latest[nid]
            out.write(idxf.pack_entry(nid, off, size))


def write_idx_from_ecx(ecx_path: str, idx_path: str | None = None) -> None:
    """.ecx (+ replayed .ecj tombstones) -> .idx for decode-to-volume."""
    idx_path = idx_path or ecx_path[: -len(".ecx")] + ".idx"
    ecj_path = ecx_path[: -len(".ecx")] + ".ecj"
    deleted = read_ecj(ecj_path)
    with open(ecx_path, "rb") as f:
        data = f.read()
    ids, offs, sizes = idxf.read_columns(data)
    with open(idx_path, "wb") as out:
        for nid, off, size in zip(ids.tolist(), offs.tolist(), sizes.tolist()):
            out.write(idxf.pack_entry(nid, off, size))
        for nid in deleted:
            out.write(idxf.pack_entry(nid, 0, t.TOMBSTONE_FILE_SIZE))


def write_vif(base: str, dat_size: int,
              version: int = t.CURRENT_VERSION,
              codec: str | None = None,
              large_block: int = layout.LARGE_BLOCK_SIZE,
              small_block: int = layout.SMALL_BLOCK_SIZE) -> None:
    """The shard set's sidecar: what a reader cannot re-derive from the
    files — the encode-time .dat size, the code's tag, and the block sizes
    the layout was cut with (`large_block_bytes`, `small_block_bytes`)."""
    import json
    doc: dict = {"version": version, "dat_file_size": dat_size,
                 "large_block_bytes": large_block,
                 "small_block_bytes": small_block}
    if codec:
        doc["codec"] = codec
    with open(base + ".vif", "w") as f:
        json.dump(doc, f)


def read_vif(base: str) -> dict | None:
    import json
    try:
        with open(base + ".vif") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def volume_blocks(base: str, vif: dict | None = None) -> tuple[int, int]:
    """(large, small) block sizes the shard set at `base` was cut with:
    its .vif's record (`vif`, where the caller has read it already).  A
    set from before the record, or one whose .vif is missing, was cut with
    layout.py's defaults, upstream's 1 GB / 1 MB."""
    vif = (read_vif(base) if vif is None else vif) or {}
    return (int(vif.get("large_block_bytes", layout.LARGE_BLOCK_SIZE)),
            int(vif.get("small_block_bytes", layout.SMALL_BLOCK_SIZE)))


def volume_codec_tag(base: str) -> str:
    """Codec tag of an EC volume from its .vif sidecar.  Volumes written
    before codec tags existed (or whose .vif is missing) are RS — the
    no-flag-day default."""
    from seaweedfs_tpu.ops import codecs as _codecs
    return _codecs.parse_tag((read_vif(base) or {}).get("codec")).tag


def find_dat_file_size(base: str, version: int = t.CURRENT_VERSION) -> int:
    """Recover the original .dat size: the encode-time size from the .vif
    sidecar when present, else the max end offset of live .ecx entries
    (reference: ec_decoder.go:48-70 — index-derived only, which misroutes
    when the volume's tail needles were all deleted)."""
    vif = read_vif(base)
    if vif and "dat_file_size" in vif:
        return int(vif["dat_file_size"])
    with open(base + ".ecx", "rb") as f:
        data = f.read()
    ids, offs, sizes = idxf.read_columns(data)
    max_end = 0
    for off, size in zip(offs.tolist(), sizes.tolist()):
        if t.size_is_valid(size):
            end = t.from_offset_units(off) + t.actual_size(size, version)
            max_end = max(max_end, end)
    return max_end


def read_ecj(ecj_path: str) -> list[int]:
    """Deletion journal: 8-byte big-endian needle ids, appended per delete."""
    if not os.path.exists(ecj_path):
        return []
    with open(ecj_path, "rb") as f:
        data = f.read()
    n = len(data) // 8
    return [int.from_bytes(data[i * 8:(i + 1) * 8], "big") for i in range(n)]
