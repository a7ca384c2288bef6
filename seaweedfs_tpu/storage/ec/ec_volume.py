"""EC volume runtime: serve needle reads/deletes from shard files.

Mirrors the reference runtime (weed/storage/erasure_coding/ec_volume.go,
ec_shard.go, store_ec.go) with one structural change: the .ecx index is
loaded as numpy columns and binary-searched in memory (searchsorted) rather
than re-reading the file per lookup — the file stays the source of truth
and deletes are written through.

Reads go through a pluggable `shard_reader(shard_id, offset, size)` so the
volume-server layer can back missing local shards with remote RPCs; when a
shard can't be read at all, the interval is reconstructed on-device from any
k readable shards (reference: store_ec.go:339-393
recoverOneRemoteEcShardInterval -> enc.ReconstructData).

The needle read path is a batched engine rather than the reference's
per-interval loop: all intervals are planned up front, adjacent ranges of
the same shard file coalesce into single reads, every local+remote shard
read fans out through one long-lived executor, and ALL missing intervals
reconstruct in ONE codec dispatch (the survivor slices for every failed
range stack column-wise into a single GF(2^8) matmul — RS decodes
byte-position by byte-position, so concatenated ranges rebuild exactly as
they would one by one).  A small LRU keeps recently reconstructed ranges so
repeated degraded GETs of a hot needle cost no shard I/O and no matmul.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import (ThreadPoolExecutor,
                                TimeoutError as _FutTimeout,
                                as_completed, wait as _futures_wait)
from typing import Callable

import numpy as np

from seaweedfs_tpu.stats import heat, trace
from seaweedfs_tpu.stats import pipeline as _pipeline
from seaweedfs_tpu.utils import resilience
from seaweedfs_tpu.storage import idx as idxf
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import ec_files, layout

ShardReader = Callable[[int, int, int], "bytes | None"]


def _read_flow() -> _pipeline.FlowAccount:
    """The degraded-read engine's stage account: `ec_read` on /perf, its
    spans and annotations `ec.read.<stage>`."""
    return _pipeline.flow("ec_read", span="ec.read")

# bytes of reconstructed ranges kept per EcVolume so hot degraded needles
# don't re-reconstruct (0 disables)
RECONSTRUCT_CACHE_BYTES = int(os.environ.get(
    "WEEDTPU_EC_RECONSTRUCT_CACHE", str(8 * 1024 * 1024)))

# one long-lived pool for LOCAL degraded-read shard preads (the old engine
# built a fresh ThreadPoolExecutor per interval — pool construction cost
# per degraded GET, times one per interval).  Remote shard fetches must
# NOT ride this pool: a blackholed peer parks its reader thread for the
# full RPC timeout, and a handful of those would starve every degraded
# GET's fast local preads behind them — remote fan-outs get a throwaway
# per-call pool instead (abandoned stragglers die with it).
_READ_POOL: ThreadPoolExecutor | None = None
_READ_POOL_LOCK = threading.Lock()


def _read_pool() -> ThreadPoolExecutor:
    global _READ_POOL
    pool = _READ_POOL
    if pool is None:
        with _READ_POOL_LOCK:
            pool = _READ_POOL
            if pool is None:
                workers = int(os.environ.get("WEEDTPU_EC_READ_WORKERS",
                                             "16"))
                pool = _READ_POOL = ThreadPoolExecutor(
                    max_workers=max(1, workers),
                    thread_name_prefix="ec-read")
    return pool


class EcVolume:
    """One mounted shard set.  Everything the layout depends on is the
    set's own, read from its `.vif` at mount: the code (`codec`), the
    encode-time `.dat` size and the two block sizes the set was cut with
    (`ec_files.volume_blocks`: `large_block`, `small_block`; a `.vif` from
    before that record means upstream's 1 GB / 1 MB).  No caller hands
    block sizes in: a set is never located with other blocks than it was
    cut with."""

    def __init__(self, base: str, version: int = t.CURRENT_VERSION):
        self.base = base
        # the volume id this EC volume serves — the workload heat
        # tracker's key for degraded reads.  Base names are "<vid>" or
        # "<collection>_<vid>" (store.Location.base_path); take the
        # trailing id so a collection volume's reconstructions land on
        # the SAME heat key as its blob reads
        self.vid = os.path.basename(base).rsplit("_", 1)[-1]
        vif = ec_files.read_vif(base) or {}
        self.large_block, self.small_block = ec_files.volume_blocks(base, vif)
        self.version = vif.get("version", version)
        # the volume's erasure code, from its .vif tag (pre-tag volumes
        # and missing .vif mean RS — no flag-day): geometry (k/n/alpha)
        # and the degraded-read survivor policy both key off this
        from seaweedfs_tpu.ops import codecs as _codecs
        self.spec = _codecs.parse_tag(vif.get("codec"))
        self.codec_tag = self.spec.tag

        # replay any crash-left journal into the .ecx, as the reference
        # does at mount (RebuildEcxFile, ec_volume_delete.go:51-98)
        self._replay_ecj()

        self._ecx = open(base + ".ecx", "r+b")
        data = self._ecx.read()
        self.ids, self.offs, self.sizes = idxf.read_columns(data)

        self.shards: dict[int, object] = {}
        for i in range(self.spec.n):
            p = base + layout.to_ext(i)
            if os.path.exists(p):
                self.shards[i] = open(p, "rb")
        if self.shards:
            any_id = next(iter(self.shards))
            self.shard_size = os.path.getsize(base + layout.to_ext(any_id))
        else:
            self.shard_size = 0
        self.dat_size = ec_files.find_dat_file_size(base, self.version)

        # degraded-read engine state: per-stage counters for /metrics and
        # an LRU of reconstructed (shard, offset, size) ranges
        self.read_stats: dict[str, int] = {
            "local_shard_reads": 0, "remote_shard_reads": 0,
            "intervals_coalesced": 0, "reconstruct_batches": 0,
            "reconstruct_intervals": 0, "reconstruct_cache_hits": 0,
        }
        self._stats_lock = threading.Lock()
        self._recon_cache: OrderedDict[tuple[int, int, int], bytes] = \
            OrderedDict()
        self._recon_cache_bytes = 0
        self._recon_lock = threading.Lock()
        # scrub-verdicted corrupt byte ranges per shard: reads overlapping
        # a quarantined range treat the local shard as unreadable, so the
        # interval is served via reconstruction (never from the bad
        # bytes).  A rebuild + remount replaces the file AND this object,
        # which is what clears the quarantine.
        self._quarantine: dict[int, list[tuple[int, int]]] = {}
        self._quarantine_lock = threading.Lock()

    # -- index ---------------------------------------------------------

    def _replay_ecj(self) -> None:
        ecj = self.base + ".ecj"
        deleted = ec_files.read_ecj(ecj)
        if not deleted:
            return
        with open(self.base + ".ecx", "r+b") as f:
            data = f.read()
            ids, _, _ = idxf.read_columns(data)
            for nid in deleted:
                pos = int(np.searchsorted(ids, nid))
                if pos < len(ids) and ids[pos] == nid:
                    f.seek(pos * 16 + 12)
                    f.write(t.TOMBSTONE_FILE_SIZE.to_bytes(4, "big", signed=True))
        os.remove(ecj)

    def find_needle(self, needle_id: int) -> tuple[int, int]:
        """-> (dat_offset_bytes, size); raises KeyError if absent/deleted."""
        pos = int(np.searchsorted(self.ids, needle_id))
        if pos >= len(self.ids) or self.ids[pos] != needle_id:
            raise KeyError(f"needle {needle_id:x} not in ec volume")
        size = int(self.sizes[pos])
        if not t.size_is_valid(size):
            raise KeyError(f"needle {needle_id:x} deleted")
        return t.from_offset_units(int(self.offs[pos])), size

    def delete_needle(self, needle_id: int) -> None:
        """Tombstone in .ecx (in place) + append to the .ecj journal."""
        pos = int(np.searchsorted(self.ids, needle_id))
        if pos >= len(self.ids) or self.ids[pos] != needle_id:
            return
        self.sizes[pos] = t.TOMBSTONE_FILE_SIZE
        self._ecx.seek(pos * 16 + 12)
        self._ecx.write(t.TOMBSTONE_FILE_SIZE.to_bytes(4, "big", signed=True))
        self._ecx.flush()
        with open(self.base + ".ecj", "ab") as j:
            j.write(needle_id.to_bytes(8, "big"))

    # -- stats / cache --------------------------------------------------

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.read_stats[key] = self.read_stats.get(key, 0) + n

    def read_stats_snapshot(self) -> dict[str, int]:
        with self._stats_lock:
            return dict(self.read_stats)

    def _cache_get(self, key: tuple[int, int, int]) -> bytes | None:
        with self._recon_lock:
            data = self._recon_cache.get(key)
            if data is not None:
                self._recon_cache.move_to_end(key)
        return data

    def _cache_put(self, key: tuple[int, int, int], data: bytes) -> None:
        if RECONSTRUCT_CACHE_BYTES <= 0 or \
                len(data) > RECONSTRUCT_CACHE_BYTES:
            return
        with self._recon_lock:
            old = self._recon_cache.pop(key, None)
            if old is not None:
                self._recon_cache_bytes -= len(old)
            self._recon_cache[key] = data
            self._recon_cache_bytes += len(data)
            while self._recon_cache_bytes > RECONSTRUCT_CACHE_BYTES and \
                    self._recon_cache:
                _, ev = self._recon_cache.popitem(last=False)
                self._recon_cache_bytes -= len(ev)

    # -- quarantine ------------------------------------------------------

    def quarantine_range(self, shard_id: int, offset: int, size: int) -> None:
        """Mark [offset, offset+size) of one shard as corrupt: local reads
        of any overlapping range fail over to reconstruction.  Adjacent /
        overlapping ranges merge so the list stays small."""
        with self._quarantine_lock:
            ranges = self._quarantine.get(shard_id, [])
            ranges.append((offset, size))
            ranges.sort()
            merged: list[tuple[int, int]] = []
            for off, sz in ranges:
                if merged and off <= merged[-1][0] + merged[-1][1]:
                    lo, lsz = merged[-1]
                    merged[-1] = (lo, max(lsz, off + sz - lo))
                else:
                    merged.append((off, sz))
            self._quarantine[shard_id] = merged

    def _is_quarantined(self, shard_id: int, offset: int, size: int) -> bool:
        with self._quarantine_lock:
            ranges = self._quarantine.get(shard_id)
            if not ranges:
                return False
            return any(off < offset + size and offset < off + sz
                       for off, sz in ranges)

    def clear_quarantine(self, shard_id: int) -> None:
        """Forget a shard's quarantined ranges — called when the shard
        FILE is deleted (purged corrupt, or lost): the verdict named
        bytes in a file that no longer exists, and a freshly rebuilt or
        re-copied replacement must not inherit it."""
        with self._quarantine_lock:
            self._quarantine.pop(shard_id, None)

    def quarantine_snapshot(self) -> dict[str, list[list[int]]]:
        with self._quarantine_lock:
            return {str(sid): [[off, sz] for off, sz in ranges]
                    for sid, ranges in self._quarantine.items() if ranges}

    # -- reads ----------------------------------------------------------

    def _read_local(self, shard_id: int, offset: int, size: int) -> bytes | None:
        """Positional read on the shard fd: os.pread carries its own file
        offset, so concurrent interval reads of one EcVolume never race a
        shared seek position.  Quarantined (scrub-verdicted corrupt)
        ranges read as unreadable so every caller — the batched engine,
        survivor gathering, and peer shard_read — falls over to
        reconstruction instead of the bad bytes."""
        f = self.shards.get(shard_id)
        if f is None:
            return None
        if self._quarantine and self._is_quarantined(shard_id, offset, size):
            return None
        try:
            return os.pread(f.fileno(), size, offset)
        except OSError:
            return None

    def _read_segs_local(self, shard_id: int,
                         segs: list[tuple[int, int]]) -> bytes | None:
        """All (offset, size) segments of one shard, concatenated; None if
        the shard is absent or any segment reads short."""
        parts = []
        for off, size in segs:
            data = self._read_local(shard_id, off, size)
            if data is None or len(data) != size:
                return None
            parts.append(data)
        return b"".join(parts)

    def _gather_survivors(self, exclude: set[int],
                          segs: list[tuple[int, int]],
                          shard_reader: ShardReader | None,
                          want: list[int] | None = None,
                          need: int | None = None
                          ) -> dict[int, np.ndarray]:
        """Survivor rows covering every segment, local shards first, the
        remainder fanned out to peers in PARALLEL on the shared pool like
        the reference's recoverOneRemoteEcShardInterval
        (store_ec.go:349-382) — a serial walk would stack per-peer
        timeouts onto one degraded GET.

        `want` restricts reads to a codec-chosen basis (an LRC local
        group: the whole point of the code is touching <= r+1 shards on
        a single loss); `need` is how many rows suffice (defaults to
        len(want), else k).  Raises IOError when the floor is missed so
        the caller can retry unrestricted."""
        k = self.spec.k
        universe = want if want is not None else list(range(self.spec.n))
        need = need if need is not None else             (len(want) if want is not None else k)
        floor = min(need, k) if want is None else need
        pool = _read_pool()
        local = [i for i in universe
                 if i not in exclude and i in self.shards]
        results: dict[int, bytes] = {}
        if len(local) == 1:
            data = self._read_segs_local(local[0], segs)
            if data is not None:
                results[local[0]] = data
        elif local:
            futs = {pool.submit(self._read_segs_local, i, segs): i
                    for i in local}
            for fut in as_completed(futs):
                data = None if fut.exception() else fut.result()
                if data is not None:
                    results[futs[fut]] = data
                    if len(results) >= need:
                        break  # enough survivors: no wasted disk reads
            for fut in futs:
                fut.cancel()  # drop un-started stragglers
        self._bump("local_shard_reads", len(results) * len(segs))
        if len(results) < need and shard_reader is not None:
            short = need - len(results)
            remote = [i for i in universe
                      if i not in exclude and i not in results]
            # same-rack-first: when the reader exposes the planner's
            # locality ranking (volume_server._shard_reader), submission
            # order biases the first-k-responders race toward nearby
            # survivors — hedging and the k-early-exit stay untouched
            rank = getattr(shard_reader, "locality_rank", None)
            if rank is not None and len(remote) > 1:
                try:
                    remote.sort(key=lambda sid: (rank(sid), sid))
                except Exception:
                    pass  # ranking is advisory, never load-bearing

            def read_remote(sid: int) -> bytes | None:
                parts = []
                for off, size in segs:
                    data = shard_reader(sid, off, size)
                    if data is None or len(data) != size:
                        return None
                    parts.append(data)
                return b"".join(parts)

            # throwaway pool, like the reference's per-recover fan-out: a
            # stuck peer must stall THIS request at worst, never the
            # shared local-pread pool other degraded GETs ride
            rpool = ThreadPoolExecutor(
                max_workers=min(8, len(remote) or 1))
            try:
                futs = {rpool.submit(read_remote, i): i for i in remote}
                for fut in as_completed(futs):
                    data = None if fut.exception() else fut.result()
                    if data is not None:
                        results[futs[fut]] = data
                        self._bump("remote_shard_reads", len(segs))
                        short -= 1
                        if short <= 0:
                            break
            finally:
                # do NOT wait for stragglers: one blackholed peer must
                # not stall the degraded GET past the k fast responders
                rpool.shutdown(wait=False, cancel_futures=True)
        if len(results) < floor:
            raise IOError(
                f"ec volume {self.base}: only {len(results)} shards "
                f"readable, need {floor} to reconstruct "
                f"shard(s) {sorted(exclude)}")
        rows = {}
        for sid in sorted(results)[:need]:
            rows[sid] = np.frombuffer(results[sid], dtype=np.uint8)
        return rows

    def _reconstruct_ranges(self, ranges: list[tuple[int, int, int]],
                            shard_reader: ShardReader | None
                            ) -> list[bytes]:
        """Rebuild several (shard_id, offset, size) ranges in ONE batched
        codec dispatch: each survivor's slices concatenate into a single
        row, the decode matmul runs once over the whole concatenation, and
        the rebuilt rows split back per range."""
        out: list[bytes | None] = [None] * len(ranges)
        todo: list[int] = []
        for idx, key in enumerate(ranges):
            data = self._cache_get(key)
            if data is not None:
                out[idx] = data
                self._bump("reconstruct_cache_hits")
            else:
                todo.append(idx)
        if not todo:
            return out  # type: ignore[return-value]
        wanted = sorted({ranges[i][0] for i in todo})
        segs = [(ranges[i][1], ranges[i][2]) for i in todo]
        codec = ec_files._get_codec(tag=self.codec_tag)
        # MSR sub-packetization works on byte-interleaved alpha-blocks:
        # widen each segment to alpha boundaries, slice the lead back off
        # after the decode (alpha=1 for rs/lrc: no-op)
        a = self.spec.alpha
        leads = [0] * len(segs)
        gsegs = segs
        if a > 1:
            gsegs = []
            for i, (off, size) in enumerate(segs):
                leads[i] = off % a
                start = off - leads[i]
                end = off + size
                end += (-end) % a
                gsegs.append((start, end - start))
        # codec-chosen survivor basis: LRC single-loss repairs read one
        # local group (r+1 shards), MSR whole-file decode reads any k
        # whole files.  If a basis shard turns out unreadable, retry
        # unrestricted — non-MDS decodability is then re-judged by the
        # shell over whatever actually arrived.
        sel = getattr(codec, "decode_select", None) or \
            getattr(getattr(codec, "code", None), "decode_select", None)
        basis: list[int] | None = None
        if sel is not None:
            try:
                basis = list(sel(
                    sorted(set(range(self.spec.n)) - set(wanted)),
                    list(wanted)))
            except (ValueError, TypeError):
                basis = None
        with _read_flow().stage(
                "gather_survivors",
                nbytes=self.spec.k * sum(s for _, s in gsegs),
                shards_lost=len(wanted), segs=len(gsegs)) as gather:
            try:
                rows = self._gather_survivors(set(wanted), gsegs,
                                              shard_reader, want=basis)
            except IOError:
                if basis is None:
                    raise
                # one extra survivor beyond k keeps every <= tolerance-1
                # loss pattern decodable for LRC; harmless elsewhere
                extra = 1 if self.spec.family == "lrc" else 0
                rows = self._gather_survivors(
                    set(wanted), gsegs, shard_reader,
                    need=self.spec.k + extra)
            # what the read gathered: 6 of one local group under
            # lrc_12_2_2 with one shard of the group lost, k otherwise
            gather.set(survivors=len(rows),
                       basis=ec_files.basis_kind(codec, list(rows)))
        # one dispatch decodes every wanted shard over the WHOLE
        # concatenation even though each segment only consumes its own
        # shard's slice — deliberately: with f lost shards that wastes
        # (f-1)/f of the matmul OUTPUT (microseconds at KB batch sizes),
        # while splitting into per-shard dispatches multiplies the
        # per-call orchestration cost this engine exists to amortize
        flow = _read_flow()
        with flow.stage("reconstruct", nbytes=sum(s for _, s in gsegs),
                        intervals=len(todo), shards=len(wanted)):
            rebuilt = ec_files._reconstruct_batch(
                codec, list(rows.values()), list(rows), wanted, job=flow)
        self._bump("reconstruct_batches")
        self._bump("reconstruct_intervals", len(todo))
        if heat.ambient_is_data():
            # a read that actually reconstructed: the expensive event
            # the per-volume degraded-read fraction in /cluster/heat
            # measures (canary/scrub/repair classes stay out).  Weight
            # 0: this is the SAME request the serving path's op=read
            # record counts — annotate it, don't count it twice
            heat.record("volume", self.vid, 0, "degraded", weight=0.0)
        pos = 0
        for i, idx in enumerate(todo):
            sid, off, size = ranges[idx]
            lead = leads[i]
            data = np.asarray(
                rebuilt[sid][pos + lead:pos + lead + size]).tobytes()
            pos += gsegs[i][1]
            out[idx] = data
            self._cache_put((sid, off, size), data)
        return out  # type: ignore[return-value]

    def _read_ranges(self, plan: list[tuple[int, int, int]],
                     shard_reader: ShardReader | None) -> list[bytes]:
        """The batched read engine: coalesce adjacent per-shard ranges,
        read all coalesced ranges concurrently (local then remote), and
        repair everything still missing in one reconstruction dispatch."""
        # coalesce: group the plan per shard, merge contiguous shard-file
        # ranges (a needle spanning whole stripe rows lands contiguous
        # blocks in each shard file), remembering how each original
        # interval slices back out of its merged read
        with trace.span("ec.coalesce", intervals=len(plan)) as csp:
            per_shard: dict[int, list[tuple[int, int, int]]] = {}
            for i, (sid, off, size) in enumerate(plan):
                per_shard.setdefault(sid, []).append((off, size, i))
            reads: list[list] = []  # [sid, off, size, [(idx, rel_off, sz)..]]
            for sid, lst in per_shard.items():
                lst.sort()
                cur: list | None = None
                for off, size, idx in lst:
                    if cur is not None and cur[1] + cur[2] == off:
                        cur[3].append((idx, cur[2], size))
                        cur[2] += size
                    else:
                        cur = [sid, off, size, [(idx, 0, size)]]
                        reads.append(cur)
            csp.set(reads=len(reads))
        if len(plan) > len(reads):
            self._bump("intervals_coalesced", len(plan) - len(reads))

        blobs: dict[int, bytes] = {}  # read index -> bytes
        failed: list[int] = []
        # reconstructed-range LRU first: a hot degraded needle skips shard
        # I/O entirely
        probe: list[int] = []
        for ri, (sid, off, size, _) in enumerate(reads):
            data = self._cache_get((sid, off, size))
            if data is not None:
                blobs[ri] = data
                self._bump("reconstruct_cache_hits")
            else:
                probe.append(ri)
        # local reads, concurrent when there is anything to overlap
        with _read_flow().stage(
                "local_pread", nbytes=sum(reads[ri][2] for ri in probe),
                reads=len(probe)) as lsp:
            if len(probe) == 1:
                ri = probe[0]
                sid, off, size, _ = reads[ri]
                data = self._read_local(sid, off, size)
                if data is not None and len(data) == size:
                    blobs[ri] = data
                    self._bump("local_shard_reads")
                else:
                    failed.append(ri)
            elif probe:
                pool = _read_pool()
                futs = {pool.submit(self._read_local, *reads[ri][:3]): ri
                        for ri in probe}
                for fut in as_completed(futs):
                    ri = futs[fut]
                    data = None if fut.exception() else fut.result()
                    if data is not None and len(data) == reads[ri][2]:
                        blobs[ri] = data
                        self._bump("local_shard_reads")
                    else:
                        failed.append(ri)
            lsp.set(missed=len(failed))
        # remote fetch of whatever the local disks couldn't serve — on a
        # throwaway pool so a hung peer can't starve the shared pread
        # pool.  The wait is HEDGED (utils/resilience.py): after a
        # p99-informed delay, ranges still in flight are handed to
        # reconstruction from OTHER survivors — a slow-but-alive peer
        # then costs the hedge delay plus one decode, not its full
        # latency.  Completions that beat the cutoff feed the latency
        # tracker; abandoned fetches do not (they would teach the
        # tracker that slow is normal and quietly disable hedging).
        pending: dict = {}  # abandoned primary future -> read index
        if failed and shard_reader is not None:
            still: list[int] = []
            hedge_s = resilience.hedge_delay_s()

            def timed_fetch(sid: int, off: int, size: int):
                t0 = time.perf_counter()
                return shard_reader(sid, off, size), \
                    time.perf_counter() - t0

            def collect(fut, ri) -> None:
                res = None if fut.exception() else fut.result()
                data = res[0] if res else None
                if data is not None and len(data) == reads[ri][2]:
                    blobs[ri] = data
                    self._bump("remote_shard_reads")
                    if hedge_s is not None:
                        # only completions that BEAT a hedge cutoff may
                        # teach the tracker: with hedging off there is
                        # no cutoff, and feeding unfiltered (possibly
                        # slow-peer) latencies here would raise the
                        # hedge delay toward exactly the latency it
                        # exists to cut
                        resilience.SHARD_FETCH.observe(res[1])
                else:
                    still.append(ri)

            with _read_flow().stage(
                    "remote_fetch",
                    nbytes=sum(reads[ri][2] for ri in failed),
                    reads=len(failed),
                    hedge_ms=None if hedge_s is None else
                    round(hedge_s * 1000.0, 1)) as rsp:
                rpool = ThreadPoolExecutor(max_workers=min(8, len(failed)))
                futs = {rpool.submit(timed_fetch, *reads[ri][:3]): ri
                        for ri in failed}
                try:
                    if hedge_s is None:
                        for fut in as_completed(futs):
                            collect(fut, futs[fut])
                    else:
                        done, not_done = _futures_wait(set(futs),
                                                       timeout=hedge_s)
                        for fut in done:
                            collect(fut, futs[fut])
                        if not_done:
                            from seaweedfs_tpu.stats import metrics
                            metrics.HEDGE_TOTAL.labels("fired").inc()
                            for fut in not_done:
                                pending[fut] = futs[fut]
                                still.append(futs[fut])
                finally:
                    # when hedging left primaries in flight, do NOT
                    # cancel them: reconstruction may find too few
                    # survivors and need to fall back to whichever
                    # primary eventually answers
                    rpool.shutdown(wait=False,
                                   cancel_futures=not pending)
                rsp.set(missed=len(still))
            failed = still
        # one-shot batched reconstruction of every range still missing
        if failed:
            failed.sort()
            keys = [tuple(reads[ri][:3]) for ri in failed]
            try:
                rebuilt = self._reconstruct_ranges(keys, shard_reader)
            except IOError:
                if not pending:
                    raise
                # the hedge lost its bet — too few survivors to decode —
                # so the abandoned primary fetches are the only source
                # left: wait them out (deadline-bounded) and decode
                # whatever still misses afterwards
                from seaweedfs_tpu.stats import metrics
                metrics.HEDGE_TOTAL.labels("primary_rescued").inc()
                try:
                    for fut in as_completed(
                            list(pending),
                            timeout=resilience.clamp_timeout(30.0)):
                        ri = pending[fut]
                        res = None if fut.exception() else fut.result()
                        data = res[0] if res else None
                        if data is not None and len(data) == reads[ri][2]:
                            blobs[ri] = data
                            self._bump("remote_shard_reads")
                except (_FutTimeout, TimeoutError):
                    pass
                failed = [ri for ri in failed if ri not in blobs]
                rebuilt = self._reconstruct_ranges(
                    [tuple(reads[ri][:3]) for ri in failed],
                    shard_reader) if failed else []
            else:
                if pending:
                    from seaweedfs_tpu.stats import metrics
                    metrics.HEDGE_TOTAL.labels("hedge_won").inc()
            for ri, data in zip(failed, rebuilt):
                blobs[ri] = data
        parts: list[bytes | None] = [None] * len(plan)
        for ri, (_, _, _, members) in enumerate(reads):
            blob = blobs[ri]
            for idx, rel, size in members:
                parts[idx] = blob[rel:rel + size]
        return parts  # type: ignore[return-value]

    def read_needle(self, needle_id: int,
                    shard_reader: ShardReader | None = None,
                    skip_shards: frozenset | None = None) -> ndl.Needle:
        """Full needle read: locate -> plan all intervals -> batched shard
        reads + one-shot reconstruction -> parse.

        `skip_shards` withholds those shards from BOTH the local files
        and the remote reader, forcing the read through reconstruction —
        the canary prober's deliberate degraded read.  Implemented as a
        shallow view sharing fds/index/caches/stats with self (the
        reconstruction cache is keyed by range, so results are identical
        whichever survivors produced them), never mutating this volume."""
        if skip_shards:
            import copy as _copy
            skip = frozenset(skip_shards)
            view = _copy.copy(self)
            view.shards = {s: f for s, f in self.shards.items()
                           if s not in skip}
            # the view must NOT share the reconstruction-range LRU: a
            # cache hit would serve the probe without touching the
            # decode path (defeating a canary that exists to exercise
            # it), and probe results must not displace real entries
            view._recon_cache = OrderedDict()
            view._recon_cache_bytes = 0
            view._recon_lock = threading.Lock()
            inner = shard_reader

            def skipping_reader(sid: int, off: int, size: int):
                if sid in skip or inner is None:
                    return None
                return inner(sid, off, size)

            rank = getattr(inner, "locality_rank", None)
            if rank is not None:
                skipping_reader.locality_rank = rank
            return view.read_needle(needle_id, skipping_reader)
        with trace.span("ec.plan", needle=f"{needle_id:x}") as psp:
            dat_offset, size = self.find_needle(needle_id)
            length = t.actual_size(size, self.version)
            plan = self.locate(dat_offset, length)
            psp.set(intervals=len(plan), bytes=length)
        record = b"".join(self._read_ranges(plan, shard_reader))
        n = ndl.Needle.from_record(record, self.version)
        if n.id != needle_id:
            raise IOError(f"ec read returned needle {n.id:x}, wanted {needle_id:x}")
        return n

    def locate(self, dat_offset: int, length: int) -> list[tuple]:
        """[(shard id, offset in its file, size)] of the .dat's bytes
        [dat_offset, dat_offset + length), under the set's own code and
        blocks: the one place a mounted set's layout is asked."""
        return [(*iv.to_shard_id_and_offset(self.large_block,
                                            self.small_block), iv.size)
                for iv in layout.locate_data(
                    self.large_block, self.small_block, self.dat_size,
                    dat_offset, length, data_shards=self.spec.k)]

    def shard_ids(self) -> list[int]:
        return sorted(self.shards)

    def close(self) -> None:
        self._ecx.close()
        for f in self.shards.values():
            f.close()
