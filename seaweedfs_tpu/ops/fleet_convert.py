"""Batched multi-volume EC conversion: one device-resident stream.

`write_ec_files` converts ONE volume well: its pipeline overlaps read /
encode / write, but between volumes the device drains and the writers
idle — fleet-wide cold-volume conversion (the consumer the autopilot
demote path feeds) runs as N serial encodes.  This module interleaves N
volumes' column units into ONE stream of unit batches, under whatever
code the codec handed in is (its k data and m parity files a volume, its
stripe width k, its sub-rows a file: RS(10,4)'s 10 + 4, LRC(12,2,2)'s
12 + 4, PM-MSR(9,16)'s 9 + 9 with 8 sub-rows; `.vif` under its tag):

    reader      walks the volumes round-robin and fills batches of one
                unit a slot (data shards go straight to each volume's
                writer pool by in-kernel copy_file_range on the way — they
                never touch the device).  A unit is a span of the
                volume's `.dat` map, as a single volume's encode has it
                (ec_files._iter_spans: up to batch_size // block
                consecutive stripe rows, sixteen 1 MiB rows at the served
                sizes, k blocks wide), selected as views
                (ec_files._unit_spans): the
                only bytes the reader moves are a volume's last, short
                row, into a zeroed buffer of its own, counted as
                `rows_staged`.  A batch holds units of one shape (one
                program a shape); a slot left without one stays empty.
    dispatch    (ops/dispatch.dispatch_parity_batch) for a codec that lays
                a unit out on the device (`encode_units_linear`: the
                mesh's FleetUnitEncoder) puts every unit's pieces 1-D to
                its own device from where they lie and launches ONE mesh
                program a batch, which lays the units out (and splits a
                sub-packetised code's file rows into sub-rows) and runs
                the batched parity kernel; any other codec (a one-device
                or a host shell) gets each unit of the batch as one
                `dispatch_parity`, a single volume's encode unit
    drain       waits, then takes each unit's parity as it comes off its
                device (dispatch.unit_parity_shards: one contiguous run
                of each of the m parity files a unit, their copies asked
                for at the enqueue) and hands parity shard k + i of the
                owning volume one run at shard_off — no full gather
    writers     per-volume _ShardWriterPool; a volume whose last unit
                drains is finalized (truncate to shard size, .vif,
                tmp -> rename commit) while the stream keeps feeding the
                other volumes

Double buffering falls out of the bounded batches in flight: H2D + kernel
for batch N+1 runs while batch N is still draining D2H + writes; a unit's
spans stay alive and unchanged until its batch's parity is materialised
(the maps outlive the run, a staged row rides the batch's queue item).
Failure/cancel anywhere aborts the WHOLE run cleanly: uncommitted volumes
keep their previous valid shard set (same .tmp recycle + rename-on-success
contract as write_ec_files), committed volumes stay committed.

Knobs: WEEDTPU_CONVERT_UNITS (unit slots a batch, default 4; rounded up
to an even mesh split, so one unit a chip on four), WEEDTPU_CONVERT_DEPTH
(batches in flight, default 2 = double buffered).  The master-side pacing
of fleet runs lives in maintenance/convert.py; this module is the data
plane.
"""

from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

from seaweedfs_tpu.ops.dispatch import (backend_name,
                                        dispatch_parity_batch,
                                        parity_devices, unit_parity_shards,
                                        unit_pieces)
from seaweedfs_tpu.stats import netflow as _netflow
from seaweedfs_tpu.stats import pipeline as _pipeline
from seaweedfs_tpu.storage.ec import layout
from seaweedfs_tpu.storage.ec.ec_files import (
    DEFAULT_BATCH, ENCODE_SUMS, EncodeCancelled, _book_stage_bytes,
    _iter_spans, _map_lazy, _ShardFlusher, _ShardWriterPool, _state_overlap,
    _unit_coverage, _unit_spans, block_geometry, write_vif)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def fleet_codec(kind: str | None = None, tag: str | None = None):
    """The codec a fleet conversion rides: ops/codecs.resolve's fleet
    rule.  An explicit choice (WEEDTPU_CONVERT_CODEC, else
    WEEDTPU_EC_CODEC) is honoured before JAX is asked anything: a
    host-codec process must not initialise a backend on a machine whose
    chip belongs to the volume server.  Under `auto`, more than one
    attached device (a real slice, or the virtual CPU mesh in tests)
    selects the unit-sharded FleetUnitEncoder, one device whatever a
    single volume's encode resolves to — every backend takes
    `dispatch_parity_batch`.  `tag` is the code the volumes go under
    (None: the default, rs_10_4), any that `resolve` carries for a single
    volume: the stream takes its striping, its files and its sub-rows
    from the codec (`convert_volumes`).  A tag no backend carries raises
    codecs.CodecUnsupported; a backend that fails to initialise raises."""
    from seaweedfs_tpu.ops import codecs
    kind = kind or os.environ.get("WEEDTPU_CONVERT_CODEC") or \
        os.environ.get("WEEDTPU_EC_CODEC", "auto")
    return codecs.resolve(tag, kind, fleet=True)


class _VolumeJob:
    """One volume mid-conversion: source map, recycled .tmp shard fds,
    its writer pool, and completion accounting.  Construction opens the
    files and starts the pool; `map` maps the `.dat`."""

    def __init__(self, index: int, base: str, dat_path: str | None,
                 large_block: int, small_block: int, batch_size: int, pjob,
                 spec):
        """`spec` (codecs.CodecSpec) is the code the volume goes under:
        its k-wide striping, its n shard files, its tag in the `.vif`.
        `index` is the volume's place in the run: the `unit` of its
        `join_writers` and `commit` stages."""
        self.index = index
        self.base = base
        self.tag = spec.tag
        self.dat_path = dat_path or base + ".dat"
        self.dat_size = os.path.getsize(self.dat_path)
        self.large_block = large_block
        self.small_block = small_block
        k, n = spec.k, spec.n
        self.shard_size = layout.shard_file_size(
            self.dat_size, large_block, small_block, data_shards=k)
        self.dat_f = open(self.dat_path, "rb")
        self.tmp_paths = [base + layout.to_ext(i) + ".tmp"
                          for i in range(n)]
        self.out_fds = [os.open(p, os.O_RDWR | os.O_CREAT, 0o644)
                        for p in self.tmp_paths]
        self.highwater = [0] * n
        self.mm = None
        self.view: np.ndarray | None = None
        self.writers = _ShardWriterPool(
            self.out_fds, self.highwater, pjob,
            stage_of=lambda i: "write_data" if i < k else "write_parity")
        # two submission batchers, one per producer thread: the reader
        # ships data-shard copies, the drain ships parity rows — a
        # _ShardFlusher is single-producer (its per-shard job lists and
        # accumulator are unlocked)
        self.data_flusher = _ShardFlusher(self.writers, n)
        self.parity_flusher = _ShardFlusher(self.writers, n)
        # (row_start, block, col, step, shard_off, rows): spans of the map
        self.units = _iter_spans(self.dat_size, large_block, small_block,
                                 batch_size, k)
        self.held = None  # the next unit, selected and not yet in a batch
        self.units_read = 0
        self.units_total: int | None = None  # set when the iterator ends
        self.units_drained = 0   # written by the drain thread only
        self.units_skipped = 0   # written by the reader thread only
        self.done_bytes = 0
        self.committed = False
        self._pjob = pjob

    def map(self) -> None:
        """Map a non-empty `.dat` with no page made ready (_map_lazy): a
        unit's span is faulted in by whoever reads it first."""
        if self.dat_size:
            self.mm = _map_lazy(self.dat_f.fileno())
            self.view = np.frombuffer(self.mm, dtype=np.uint8)

    def next_unit(self):
        try:
            u = next(self.units)
            self.units_read += 1
            return u
        except StopIteration:
            self.units_total = self.units_read
            return None

    def drained_all(self) -> bool:
        # drained is drain-thread-owned, skipped reader-thread-owned: two
        # counters so the threads never race one += on the same field
        return self.units_total is not None and \
            self.units_drained + self.units_skipped >= self.units_total

    def finalize(self) -> None:
        """All units drained: barrier on the writers (`join_writers`),
        cut shards to size, commit by rename (`commit`).  Runs on the
        drain thread while the stream keeps feeding other volumes."""
        self.data_flusher.flush()
        self.parity_flusher.flush()
        self.writers.close(self.index)
        if self.writers.errors:
            raise self.writers.errors[0]
        with self._pjob.stage("commit", unit=self.index):
            for fd, hw in zip(self.out_fds, self.highwater):
                os.ftruncate(fd, min(hw, self.shard_size))
                if hw < self.shard_size:
                    os.ftruncate(fd, self.shard_size)
            for fd in self.out_fds:
                os.close(fd)
            self.out_fds = []
            write_vif(self.base, self.dat_size, codec=self.tag,
                      large_block=self.large_block,
                      small_block=self.small_block)
            for i, p in enumerate(self.tmp_paths):
                os.replace(p, self.base + layout.to_ext(i))
        self.committed = True
        # callers that must react per-volume (the volume server's freeze
        # bookkeeping) see commits even when a LATER volume fails the run
        self._pjob.stats.setdefault("committed_bases", []).append(self.base)

    def abort(self) -> None:
        """Failure path: drop fds and every .tmp so no partial shard set
        is ever visible; a previous valid shard set stays untouched."""
        try:
            self.writers.close(self.index)
        except Exception:
            pass
        with self._pjob.stage("commit", unit=self.index):
            for fd in self.out_fds:
                try:
                    os.close(fd)
                except OSError:
                    pass
            self.out_fds = []
            if not self.committed:
                for p in self.tmp_paths:
                    try:
                        os.remove(p)
                    except OSError:
                        pass

    def release(self) -> None:
        self.held = None
        if self.view is not None:
            self.view = None
        if self.mm is not None:
            try:
                self.mm.close()
            except BufferError:
                pass
            self.mm = None
        self.dat_f.close()


def convert_volumes(bases: list[str], *,
                    large_block: int = layout.LARGE_BLOCK_SIZE,
                    small_block: int = layout.SMALL_BLOCK_SIZE,
                    batch_size: int = DEFAULT_BATCH,
                    codec=None, unit_batch: int | None = None,
                    progress=None, cancel=None,
                    stats: dict | None = None,
                    codec_tag: str | None = None) -> dict:
    """Convert `bases` (.dat volumes) into EC shard sets through one
    interleaved device-resident stream.  Returns per-volume accounting.

    `progress(bytes_done)` sees TOTAL volume bytes consumed across the
    fleet; `cancel()` aborts the whole run (uncommitted volumes roll
    back).  `stats` receives the usual per-stage wall-second attribution
    plus units/volumes counters, the code's tag (`codec`), its files a
    volume (`shard_files`) and its sub-rows a file (`alpha`).  The code is
    the codec's: `codec_tag` names it where no `codec` is handed in
    (`fleet_codec`), and k, m and the stripe width follow from it.
    `large_block` / `small_block` are the block sizes every volume of the
    run is cut with, as `write_ec_files` takes them: each volume's `.vif`
    records them, `stats` says them with the run's rows of each kind
    (`large_rows`, `small_rows`, `large_row_share`, over all volumes) and
    its units of each (`units_column`, `units_rows`)."""
    if not bases:
        return {"volumes": {}, "bytes": 0}
    codec = codec if codec is not None else fleet_codec(tag=codec_tag)
    from seaweedfs_tpu.ops import codecs
    spec = codecs.spec_of(codec)

    # chaos hook: an armed shard_write_error fault fails the conversion
    # like a dying disk — before any tmp shard file exists
    from seaweedfs_tpu.maintenance import faults as _faults
    for base in bases:
        _faults.check_shard_write(base)

    k, m = spec.k, spec.m
    depth = max(1, _env_int("WEEDTPU_CONVERT_DEPTH", 2))
    U = max(1, _env_int("WEEDTPU_CONVERT_UNITS", 4))
    slots = getattr(codec, "unit_slots", None)
    if slots is not None:  # round to an even mesh split
        U = slots(U)

    stats = stats if stats is not None else {}
    stats["mode"] = "fleet"
    stats["backend"] = backend_name(codec)
    stats["unit_batch"] = U
    stats.update(codec=spec.tag, shard_files=spec.n, alpha=spec.alpha)
    stats["rows_staged"] = 0  # stripe rows copied on the host (pjob.count)
    stats["spans_mapped"] = 0  # units selected in the volumes' maps (ditto)
    stats.update(units_column=0, units_rows=0)  # units that carried data
    # class=convert on THIS thread and (contextvars are per-thread) re-
    # stamped inside each pipeline thread, so any hop made on the
    # conversion's behalf — wherever it runs — books as convert
    flow_cls = _netflow.current_class() or "convert"
    _flow_token = _netflow.set_class(flow_cls)
    t_wall = time.perf_counter()
    # stages as the single-volume encode names them: the caller's `open`,
    # `map`, `await_unit`, `join_drain` and `commit`, the reader's `read`,
    # `ship_data` and `stall`, the drain's `await_parity`, the writers'
    # `write_data` and `write_parity` and each volume's `join_writers` and
    # `commit` (its index as `unit`), and the dispatch seam's four, which
    # add up to `encode` and `d2h`; a unit batch's index rides the others
    # as `unit`
    pjob = _pipeline.track("fleet_convert", stats,
                           meta={"volumes": len(bases), "unit_batch": U},
                           span="ec.fleet", sums=ENCODE_SUMS)
    jobs: list[_VolumeJob] = []
    try:
        with pjob.stage("open", files=len(bases) * (spec.n + 1)) as st:
            for i, b in enumerate(bases):
                jobs.append(_VolumeJob(i, b, None, large_block, small_block,
                                       batch_size, pjob, spec))
            stats["bytes"] = sum(j.dat_size for j in jobs)
            st.set(bytes=stats["bytes"])
            stats.update(block_geometry((j.dat_size for j in jobs),
                                        large_block, small_block, k))
        with pjob.stage("map", files=sum(1 for j in jobs if j.dat_size),
                        bytes=stats["bytes"]):
            for job in jobs:
                job.map()
    except BaseException as e:  # a volume that cannot be opened or mapped
        for job in jobs:
            job.abort()
            job.release()
        pjob.finish(e)
        raise

    # depth+1 batches between selection and materialised parity, so the
    # H2D and kernel of batch N+1 overlap the D2H and writes of batch N:
    # the pool's items are tokens (spans hold no buffer)
    pool: queue.Queue = queue.Queue()
    for _ in range(depth + 1):
        pool.put(None)
    q_read: queue.Queue = queue.Queue(maxsize=depth)
    q_disp: queue.Queue = queue.Queue()
    errors: list[BaseException] = []
    done_total = 0

    def peek(job):
        """The job's next unit that holds data, selected once: (unit,
        pieces, rows staged, shape): the unit as 1-D views of the map (no
        byte moves but a volume's last, short row, into a zeroed buffer of
        its own) cut into the pieces it goes up as, whose lengths are the
        shape a batch shares.  None at the volume's end."""
        while job.held is None:
            unit = job.next_unit()
            if unit is None:
                return None
            row_start, block, col, step, _, rows = unit
            views, staged = _unit_spans(job.view, job.dat_size, k,
                                        row_start, block, col, step, rows)
            if views:
                pieces = unit_pieces(views, rows)
                job.held = (unit, pieces, staged, (rows, *map(len, pieces)))
            else:
                # a trailing column unit wholly beyond the .dat: nothing
                # to encode or write
                job.units_skipped += 1
        return job.held

    def take(job):
        """Move the job's selected unit into a batch."""
        unit, pieces, staged, _ = job.held
        job.held = None
        _, block, _, step, shard_off, rows = unit
        pjob.count("rows_staged", staged)
        pjob.count("spans_mapped", 1)
        pjob.count("units_column" if step != block else "units_rows", 1)
        return pieces, (job, shard_off, rows * step), unit

    def ship_data(job, unit):
        """A unit's data shards go to the volume's own writers by
        in-kernel copies, block by block: they never ride the device."""
        nonlocal done_total
        row_start, block, col, step, shard_off, rows = unit
        for r in range(rows):
            nz, tail = _unit_coverage(
                job.dat_size, row_start + r * k * block, block, col, step,
                k)
            for j in range(nz):
                job.data_flusher.copy(
                    j, job.dat_f.fileno(),
                    row_start + (r * k + j) * block + col,
                    shard_off + r * step, step if j < nz - 1 else tail,
                    src_view=job.view)
            if nz:
                done_total += (nz - 1) * step + tail
                job.done_bytes += (nz - 1) * step + tail
                job.data_flusher.account(step)

    def reader() -> None:
        """Round-robin units across volumes into batches of one shape."""
        active = list(jobs)
        _netflow.set_class(flow_cls)
        batch = 0
        try:
            while active and not errors:
                if cancel is not None and cancel():
                    raise EncodeCancelled("fleet conversion cancelled")
                with pjob.blocked("stall", unit=batch):
                    buf = pool.get()
                units, metas, taken, shape = [], [], [], None
                # `read` is what ec.encode.read is: the selection and the
                # bytes the reader itself moves
                with pjob.stage("read", unit=batch):
                    took = True
                    while len(metas) < U and took:
                        took = False
                        for job in list(active):
                            held = peek(job)
                            if held is None:
                                active.remove(job)
                            elif not metas or held[3] == shape:
                                shape = held[3]
                                pieces, meta, unit = take(job)
                                units.append(pieces)
                                metas.append(meta)
                                taken.append((job, unit))
                                took = True
                                if len(metas) == U:
                                    break
                with pjob.stage("ship_data", unit=batch):
                    for job, unit in taken:
                        ship_data(job, unit)
                if progress is not None:
                    progress(done_total)
                if metas:
                    # a slot with no unit is None: a short last batch, or
                    # one closed because the volumes' next units differ
                    # in shape
                    units += [None] * (U - len(units))
                    q_read.put((batch, buf, metas, units, shape[0]))
                    batch += 1
                else:
                    pool.put(buf)
                del units  # the queue item alone holds a unit's views
        except BaseException as e:
            errors.append(e)
        finally:
            q_read.put(None)

    def drain() -> None:
        """Materialise parity per device shard and fan rows out; finalize
        each volume the moment its last unit lands."""
        failed = False
        _netflow.set_class(flow_cls)
        while True:
            with pjob.blocked("await_parity"):
                item = q_disp.get()
            if item is None:
                return
            batch, buf, metas, parity = item[:4]
            try:
                if failed or errors:
                    pool.put(buf)
                    continue
                # stream: each block fans out (and its parity writes
                # submit) the moment its d2h lands, instead of waiting
                # for a full gather — write_parity overlaps the d2h of
                # the blocks still in flight
                released = False
                for a, b, block in unit_parity_shards(parity, job=pjob,
                                                      unit=batch):
                    if not released:
                        # the first yield implies block_until_ready has
                        # returned: the device is done with the host
                        # memory (the spans, which the queue item held
                        # until here) even though
                        # later shards are still transferring
                        pool.put(buf)
                        released = True
                        del item
                    touched = []
                    for u in range(a, min(b, len(metas))):
                        job, shard_off, width = metas[u]
                        # the m parity files' runs, or the rows of a host
                        # shell's [m, W]: one contiguous run of each
                        # parity shard's file either way
                        for i, run in enumerate(block[u - a]):
                            job.parity_flusher.put(k + i, run[:width],
                                                   shard_off)
                        job.parity_flusher.account(width)
                        job.units_drained += 1
                        if job.drained_all():
                            job.finalize()
                        elif job not in touched:
                            touched.append(job)
                    for job in touched:
                        job.parity_flusher.flush()
                if not released:
                    pool.put(buf)
            except BaseException as e:
                errors.append(e)
                failed = True
            finally:
                # every unit's parity is on the host (or the batch failed)
                pjob.occupancy("inflight", -1)

    t_r = threading.Thread(target=reader, name="fleet-reader", daemon=True)
    t_d = threading.Thread(target=drain, name="fleet-drain", daemon=True)
    with pjob.stage("open"):
        t_r.start()
        t_d.start()
    try:
        while True:
            with pjob.blocked("await_unit"):
                item = q_read.get()
            if item is None:
                break
            # stage-queue depths at the consume site: a persistently full
            # q_read means the dispatch (encode) stage is the bound, a
            # deep q_disp means the drain/writers are
            pjob.queue("q_read", q_read.qsize(), depth)
            pjob.queue("q_disp", q_disp.qsize())
            batch, buf, metas, units, stripes = item
            if errors:
                pool.put(buf)
                continue
            try:
                parity = dispatch_parity_batch(codec, units, job=pjob,
                                               unit=batch, stripes=stripes)
                # how many devices the unit batch's parity lives on (0:
                # a host codec returned numpy) — a mesh that silently ran
                # on its first chip must show
                stats["devices"] = max(stats.get("devices", 0),
                                       parity_devices(parity))
                # the item carries the units: their views and staged rows
                # live until the drain has the parity
                pjob.occupancy("inflight", +1)
                q_disp.put((batch, buf, metas, parity, units))
            except BaseException as e:
                errors.append(e)
                pool.put(buf)
            del item, units
    finally:
        with pjob.blocked("join_drain"):
            q_disp.put(None)
            t_d.join()
            # unblock a reader stuck on a full q_read
            while t_r.is_alive():
                try:
                    item = q_read.get(timeout=0.05)
                except queue.Empty:
                    continue
                if item is not None:
                    pool.put(item[1])
            t_r.join()
        # empty volumes never enter the stream; commit them here, and on
        # any error roll every uncommitted volume back
        for job in jobs:
            try:
                if not errors and not job.committed and job.drained_all():
                    job.finalize()
            except BaseException as e:
                errors.append(e)
        for job in jobs:
            if errors and not job.committed:
                job.abort()
        with pjob.stage("commit"):
            for job in jobs:
                job.release()
        _netflow.reset(_flow_token)
        stats["wall_s"] = time.perf_counter() - t_wall
        # analytic stage bytes (the layout fixes them; zero hot-path
        # cost): the occupancy timeline gets achieved GB/s per stage.
        # Only COMMITTED volumes' bytes count — an aborted half-run must
        # not credit the full planned bytes and report achieved GB/s
        # the hardware never moved
        done_jobs = [j for j in jobs if j.committed]
        _book_stage_bytes(pjob, stats,
                          sum(j.dat_size for j in done_jobs),
                          m * sum(j.shard_size for j in done_jobs))
        pjob.finish(errors[0] if errors else None)
    if errors:
        raise errors[0]
    for job in jobs:
        if job.writers.errors:
            raise job.writers.errors[0]
    stats["volumes"] = len(jobs)
    stats["units"] = sum(j.units_read for j in jobs)
    _state_overlap(stats)
    return {"volumes": {j.base: {"bytes": j.dat_size,
                                 "shard_size": j.shard_size}
                        for j in jobs},
            "bytes": stats["bytes"], "units": stats["units"],
            "devices": stats.get("devices", 0),
            "wall_s": round(stats["wall_s"], 4)}
