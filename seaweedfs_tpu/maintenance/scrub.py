"""Background scrubber: detect silent corruption before a client does.

One Scrubber runs on each volume server.  A full pass streams every store
volume and every EC volume at a bounded rate (WEEDTPU_SCRUB_MBPS):

- store volumes: each live needle is re-read and its CRC32C recomputed
  against the stored checksum (storage/needle.py crc32c), and the record's
  id is cross-checked against the index entry that routed us there — a
  bit flip in either the data or the header surfaces here instead of on a
  client read.

- EC volumes: RS(10,4) parity verification IS a batched GF(2^8) matmul,
  so each scrub window stacks the k data-shard stripes into one [k, W]
  matrix, recomputes parity through the SAME ops/dispatch backend seam
  the encoder uses (tpu / native / numpy all work), and compares against
  the stored parity shards — one codec dispatch per window.  A mismatch
  is localized to the single corrupt shard by a per-candidate consistency
  test on the mismatching byte columns (RS decodes column by column, so
  only those columns are re-derived, with the slow numpy reference code).

Corrupt EC ranges are quarantined on the owning EcVolume — reads of the
range reconstruct from the other shards instead of serving the bad bytes —
and every pass's verdicts are reported upstream to the master's repair
planner (maintenance/repair.py), which deletes the corrupt shard and
rebuilds it through the normal EC machinery.

The rate limit exists because scrub I/O competes with foreground reads on
the same spindles.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from seaweedfs_tpu.stats import metrics, netflow, trace
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import layout

log = logging.getLogger("scrub")

DEFAULT_MBPS = 8.0          # WEEDTPU_SCRUB_MBPS: sustained scrub rate
DEFAULT_INTERVAL = 300.0    # WEEDTPU_SCRUB_INTERVAL: seconds between passes
DEFAULT_WINDOW = 1024 * 1024  # WEEDTPU_SCRUB_WINDOW: syndrome window bytes
# columns fed to the corrupt-shard localizer: RS is column-independent, so
# a handful of mismatching columns identify the shard as well as all of them
LOCALIZE_COLS = 1024


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


class RateLimiter:
    """Byte-budget throttle: sustained `bytes_per_s` with a small burst
    allowance so per-needle accounting doesn't turn into thousands of
    sub-millisecond sleeps."""

    def __init__(self, bytes_per_s: float, burst_s: float = 0.25):
        self.rate = float(bytes_per_s)
        self.burst = burst_s
        self._next = time.monotonic()

    def set_rate(self, bytes_per_s: float) -> None:
        """Retarget the sustained rate live (a float store — atomic
        under the GIL; the scrub thread reads it per chunk, so a
        governor push takes effect mid-pass, not next pass)."""
        self.rate = float(bytes_per_s)

    def throttle(self, nbytes: int) -> None:
        # read the rate ONCE: set_rate() flips it from another thread,
        # and the zero-check must guard the same value we divide by
        rate = self.rate
        if rate <= 0 or nbytes <= 0:
            return
        now = time.monotonic()
        # credit at most `burst` seconds of idle time, then advance the
        # schedule by this chunk's transmit time at the target rate
        self._next = max(self._next, now - self.burst) + nbytes / rate
        delay = self._next - now
        if delay > 0:
            time.sleep(delay)


def localize_corrupt_shard(cols: np.ndarray, code=None) -> int | None:
    """Identify the single corrupt shard from the stored bytes at the
    mismatching byte columns.

    `cols` is [n, C] for the volume's code (RS by default; any alpha=1
    code with reconstruct_numpy + parity_matrix works — LRC does).  For
    each candidate shard, reconstruct it from the other n-1 and test
    whether the stripe becomes fully consistent (all m parity rows
    match a recompute from the data rows).  With one corrupt shard
    exactly one candidate passes: excluding the corrupt shard from the
    survivors yields a consistent stripe, while any other candidate
    either reconstructs from (or is checked against) the bad bytes.
    Returns None when zero or several candidates pass — more than one
    shard is corrupt in this window, or the stripe is degenerate."""
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.ops import gf
    if code is None:
        code = rs.get_code(layout.DATA_SHARDS, layout.PARITY_SHARDS)
    passing: list[int] = []
    for cand in range(code.n):
        others = {i: cols[i] for i in range(code.n)
                  if i != cand}
        rec = code.reconstruct_numpy(others, wanted=[cand])[cand]
        rows = dict(others)
        rows[cand] = rec
        data = np.stack([rows[i] for i in range(code.k)])
        parity = gf.gf_matmul(code.parity_matrix, data)
        if all(np.array_equal(parity[r], rows[code.k + r])
               for r in range(code.m)):
            passing.append(cand)
            if len(passing) > 1:
                return None
    return passing[0] if len(passing) == 1 else None


def syndrome_scan(ev, codec=None, window: int | None = None,
                  limiter: RateLimiter | None = None,
                  shard_reader=None, stop: threading.Event | None = None,
                  stats: dict | None = None) -> list[dict]:
    """Walk an EcVolume's shard files window by window and verify parity.

    Each window reads the same [off, off+W) slice of every readable shard,
    recomputes parity from the k data rows in ONE dispatch through the
    ops/dispatch seam, and compares against the stored parity rows.
    Windows where any data shard (or every parity shard) is unreadable are
    skipped and counted — on a spread cluster each server only verifies
    what it can assemble locally unless a `shard_reader` is provided.

    Returns corrupt-range dicts {shard, offset, size, columns}; shard is
    -1 when the corruption could not be localized to one shard."""
    from seaweedfs_tpu.ops import codecs as _codecs
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.stats import pipeline
    from seaweedfs_tpu.storage.ec import ec_files
    # where the seam's stages of every window book: `ec_scrub` on /perf
    flow = pipeline.flow("ec_scrub", span="ec.scrub")
    if codec is None:
        codec = ec_files._get_codec(tag=getattr(ev, "codec_tag", None))
    spec = getattr(ev, "spec", None) or _codecs.spec_of(codec)
    window = window or DEFAULT_WINDOW
    if spec.alpha > 1:
        # sub-packetized codewords are positionally blocked per alpha
        # bytes: parity only recomputes over alpha-aligned windows
        window = max(spec.alpha, window - window % spec.alpha)
    k, m = spec.k, spec.m
    out: list[dict] = []
    for off in range(0, ev.shard_size, window):
        if stop is not None and stop.is_set():
            break
        n = min(window, ev.shard_size - off)
        rows: dict[int, np.ndarray] = {}
        for sid in range(spec.n):
            data = ev._read_local(sid, off, n)
            if (data is None or len(data) != n) and shard_reader is not None:
                data = shard_reader(sid, off, n)
            if data is not None and len(data) == n:
                rows[sid] = np.frombuffer(data, dtype=np.uint8)
        got = sum(r.nbytes for r in rows.values())
        if stats is not None:
            stats["bytes"] = stats.get("bytes", 0) + got
        metrics.SCRUB_BYTES.labels("ec").inc(got)
        parity_have = {s - k: rows[s] for s in range(k, k + m) if s in rows}
        if any(i not in rows for i in range(k)) or not parity_have:
            if stats is not None:
                stats["windows_skipped"] = stats.get("windows_skipped", 0) + 1
            if limiter is not None:
                limiter.throttle(got)
            continue
        batch = np.stack([rows[i] for i in range(k)])
        with trace.span("scrub.syndrome", offset=off, bytes=batch.nbytes):
            masks = dispatch.parity_mismatch(codec, batch, parity_have,
                                             job=flow)
        if stats is not None:
            stats["windows"] = stats.get("windows", 0) + 1
        if limiter is not None:
            limiter.throttle(got)
        mism = np.zeros(n, dtype=bool)
        for mask in masks.values():
            mism |= mask
        bad_cols = np.nonzero(mism)[0]
        if bad_cols.size == 0:
            continue
        shard = -1
        # single-byte-column localization needs columns to be
        # independent codewords: true for alpha=1 families only
        if len(rows) == spec.n and spec.alpha == 1:
            sel = bad_cols[:LOCALIZE_COLS]
            cols = np.stack([rows[i][sel]
                             for i in range(spec.n)])
            loc = localize_corrupt_shard(
                cols, code=getattr(codec, "code", None))
            if loc is not None:
                shard = loc
        out.append({"shard": shard, "offset": off, "size": n,
                    "columns": int(bad_cols.size)})
    return out


class Scrubber:
    """Rate-limited background scrub loop over one Store.

    `report(summary)` is invoked (on the scrub thread) after each full
    pass — the volume server wires it to POST /maintenance/scrub_report on
    the master.  `shard_reader_factory(vid)` optionally supplies a remote
    shard reader so syndrome windows missing local shards can still be
    verified (WEEDTPU_SCRUB_REMOTE=1); by default only locally-assembled
    windows are checked."""

    def __init__(self, store, *, mbps: float | None = None,
                 interval: float | None = None, window: int | None = None,
                 report=None, shard_reader_factory=None):
        self.store = store
        self.mbps = mbps if mbps is not None else \
            _env_float("WEEDTPU_SCRUB_MBPS", DEFAULT_MBPS)
        self.interval = interval if interval is not None else \
            _env_float("WEEDTPU_SCRUB_INTERVAL", DEFAULT_INTERVAL)
        self.window = window or int(_env_float("WEEDTPU_SCRUB_WINDOW",
                                               DEFAULT_WINDOW))
        self.report = report
        self.shard_reader_factory = shard_reader_factory
        # this node's CONFIGURED rate: governor pushes arrive as a
        # fraction of it (apply_governed_scale), so a node deliberately
        # configured slower than the fleet default is scaled, never
        # overridden upward to someone else's ceiling
        self.configured_mbps = self.mbps
        self.last_scrub = 0.0
        self.last_summary: dict = {}
        self._stop = threading.Event()
        self._mu = threading.Lock()  # serializes concurrent scrub_once
        self._thread: threading.Thread | None = None
        # the pass currently in flight keeps its limiter here so a
        # governor retune (set_mbps) lands mid-pass, not next pass
        self._limiter: RateLimiter | None = None
        # operator pause latch: an explicit operator {"mbps": 0} sticks
        # until an explicit operator resume — the governor's periodic
        # governed=True re-pushes must never silently un-pause a node
        # someone stopped mid-incident
        self.operator_paused = False

    def set_mbps(self, mbps: float, governed: bool = False) -> float:
        """Retune the sustained scrub rate (pushed via
        /admin/scrub_rate).  Applies to the active pass immediately and
        to every later pass.  ``0`` PAUSES scrubbing (the
        construction-time semantic): future passes skip and the active
        pass stops at its next volume boundary — the live limiter keeps
        its previous rate rather than taking 0, because a zero-rate
        RateLimiter means *unthrottled*, the exact opposite of an
        operator posting {"mbps": 0} mid-incident.  ``governed`` marks
        the interference governor's pushes: they respect an operator
        pause (no-op while latched) and never flip the latch; operator
        calls (governed=False) set it — 0 latches, >0 releases.
        Returns the rate in effect."""
        mbps = max(0.0, float(mbps))
        if governed:
            if self.operator_paused:
                return self.mbps  # the operator's stop wins
        else:
            self.operator_paused = mbps <= 0
            self.configured_mbps = mbps  # new operator baseline
        self.mbps = mbps
        lim = self._limiter
        if lim is not None and self.mbps > 0:
            lim.set_rate(self.mbps * 1e6)
        return self.mbps

    def apply_governed_scale(self, scale: float) -> float:
        """Governor seam: scale THIS node's configured rate by the
        fleet backoff fraction (0..1].  A node started with
        WEEDTPU_SCRUB_MBPS=2 in an 8-default fleet governs to 2 x scale
        — its deliberate config is scaled, never raised to the master's
        ceiling.  Respects the operator pause latch like any governed
        push."""
        scale = max(0.0, min(1.0, float(scale)))
        return self.set_mbps(self.configured_mbps * scale, governed=True)

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "Scrubber":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run,
                                            name="scrubber", daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout)

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                summary = self.scrub_once()
            except Exception:
                log.warning("scrub pass failed", exc_info=True)
                continue
            if self.report is not None:
                try:
                    self.report(summary)
                except Exception:
                    log.warning("scrub report failed", exc_info=True)

    # -- one pass ------------------------------------------------------

    def scrub_once(self) -> dict:
        """One full pass over every mounted volume; returns the summary
        that also goes upstream: {ts, bytes, volumes: {vid: verdict}}."""
        # every remote byte this pass pulls (peer shard reads for the
        # syndrome checks) books as class=scrub — the shard_reader
        # factory captures the ambient class right here on this thread
        if self.mbps <= 0:
            # paused (set_mbps(0) or WEEDTPU_SCRUB_MBPS=0): no pass
            return {"ts": time.time(), "bytes": 0, "volumes": {},
                    "paused": True}
        with self._mu, netflow.flow("scrub"), \
                trace.span("scrub.pass", parent=trace.new_root()) \
                as pass_span:
            limiter = RateLimiter(self.mbps * 1e6)
            self._limiter = limiter
            vols: dict[str, dict] = {}
            total = 0
            for loc in self.store.locations:
                for vid, v in list(loc.volumes.items()):
                    if self._stop.is_set() or self.mbps <= 0:
                        break
                    if getattr(v, "backend_kind", "") == "remote" or \
                            getattr(v, "staging", False):
                        continue  # remote-tier reads cost money; staged
                    try:
                        res = self._scrub_volume(vid, v, limiter)
                    except Exception as e:
                        res = {"kind": "normal", "error": str(e)}
                    vols[str(vid)] = res
                    total += res.get("bytes", 0)
                for vid, ev in list(loc.ec_volumes.items()):
                    if self._stop.is_set() or self.mbps <= 0:
                        break
                    try:
                        res = self._scrub_ec(vid, ev, limiter)
                    except Exception as e:
                        res = {"kind": "ec", "error": str(e)}
                    vols[str(vid)] = res
                    total += res.get("bytes", 0)
            pass_span.set(volumes=len(vols), bytes=total)
            self._limiter = None
            summary = {"ts": time.time(), "bytes": total, "volumes": vols}
            self.last_scrub = summary["ts"]
            self.last_summary = summary
            return summary

    def _scrub_volume(self, vid: int, v, limiter: RateLimiter) -> dict:
        res: dict = {"kind": "normal", "needles": 0, "bytes": 0,
                     "crc_mismatches": 0, "corrupt": []}
        for nid, (off, size) in list(v.nm.items()):
            if self._stop.is_set():
                break
            if not t.size_is_valid(size):
                continue
            ok = True
            try:
                n = v._read_at(off, size, verify_checksum=False)
                c = ndl.crc32c(n.data)
                ok = n.id == nid and \
                    n.checksum in (c, ndl.crc_legacy_value(c))
            except (ValueError, EOFError, OSError):
                ok = False
            nbytes = t.actual_size(size, v.version)
            res["needles"] += 1
            res["bytes"] += nbytes
            metrics.SCRUB_BYTES.labels("volume").inc(nbytes)
            if not ok:
                res["crc_mismatches"] += 1
                res["corrupt"].append({"needle": f"{nid:x}"})
                metrics.SCRUB_CORRUPTIONS.labels("needle").inc()
                log.warning("scrub: volume %d needle %x failed CRC "
                            "verification", vid, nid)
            limiter.throttle(nbytes)
        res["last_scrub"] = time.time()
        return res

    def _scrub_ec(self, vid: int, ev, limiter: RateLimiter) -> dict:
        res: dict = {"kind": "ec", "windows": 0, "windows_skipped": 0,
                     "bytes": 0}
        reader = None
        if self.shard_reader_factory is not None and \
                os.environ.get("WEEDTPU_SCRUB_REMOTE") == "1":
            reader = self.shard_reader_factory(vid)
        corrupt = syndrome_scan(ev, window=self.window, limiter=limiter,
                                shard_reader=reader, stop=self._stop,
                                stats=res)
        for c in corrupt:
            metrics.SCRUB_CORRUPTIONS.labels("ec_shard").inc()
            if c["shard"] >= 0:
                # never serve the bad bytes again: reads of this range
                # reconstruct from the other shards until the repair
                # planner rebuilds the shard (remount clears it)
                ev.quarantine_range(c["shard"], c["offset"], c["size"])
            log.warning("scrub: ec volume %d parity mismatch at "
                        "[%d, +%d) -> shard %s", vid, c["offset"],
                        c["size"], c["shard"] if c["shard"] >= 0
                        else "unlocalized")
        res["corrupt"] = corrupt
        res["quarantined"] = ev.quarantine_snapshot()
        res["last_scrub"] = time.time()
        return res
