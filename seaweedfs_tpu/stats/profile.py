"""Continuous profiling: a signal-free sampling profiler + kernel profile.

Two complementary views of where time goes, both exposed through
``/debug/pprof`` on every server (loopback-gated like the rest of the
debug surface):

- **Host stacks** — a dedicated daemon thread walks
  ``sys._current_frames()`` at ``WEEDTPU_PROFILE_HZ`` and folds every
  thread's stack into a collapsed-stack table (the flamegraph.pl /
  speedscope input format: ``frame;frame;frame count``) plus a
  cumulative self/total per-frame table.  No signals, no sys.setprofile
  hooks: the sampled threads pay nothing, the sampler costs one frame
  walk per tick, and it works from any thread (asyncio loop, worker
  pools, the scrubber) unlike signal-based profilers which only ever see
  the main thread.

- **Kernel profile** — the device-side twin fed by ops/dispatch.py from
  the four stages it opens round every device dispatch: per codec entry
  point (encode_parity / reconstruct / fleet_encode / repair_partial)
  the host wall time of the enqueue (``wall_s``), the time blocked in
  ``block_until_ready`` (``device_s``: the device's work plus the
  transfers queued before it), what the calling thread spent in
  ``jnp.asarray`` / ``place`` (``h2d_s``: staging and put, not the DMA
  itself) and in ``np.asarray`` once ready (``d2h_s``), plus the bytes
  each way.

- **Compile counter** — backend compilations counted inside the program
  by a ``jax.monitoring`` listener (the events ``JAX_LOG_COMPILES``
  logs), booked to the codec entry point open on the compiling thread;
  ``/perf`` -> ``compiles``.

Default off: ``WEEDTPU_PROFILE_HZ`` unset/0 starts nothing, and
``/debug/pprof?seconds=N`` spins up an on-demand window sampler that is
stopped (thread joined) before the response is written — start/stop must
leave zero threads behind.
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time

_DEFAULT_HZ = 97  # prime: never phase-locks with 10ms/100ms periodic work


def profile_hz() -> float:
    """Continuous-profiler rate; 0 (the default) disables the background
    sampler and leaves only the on-demand /debug/pprof?seconds=N path."""
    try:
        return float(os.environ.get("WEEDTPU_PROFILE_HZ", "0"))
    except ValueError:
        return 0.0


def _clamp_hz(hz: float) -> float:
    return max(1.0, min(float(hz), 1000.0))


def _frame_label(frame) -> str:
    """``module.function`` — module from the file basename, so stacks read
    as ``volume_server.handle_blob;ec_volume.read_needle;...``."""
    code = frame.f_code
    mod = os.path.basename(code.co_filename)
    if mod.endswith(".py"):
        mod = mod[:-3]
    return f"{mod}.{code.co_name}"


class SamplingProfiler:
    """Walk every thread's stack `hz` times a second into a collapsed
    stack table.  start() spawns one daemon thread; stop() joins it —
    a stopped profiler owns no threads and can be read freely."""

    def __init__(self, hz: float = _DEFAULT_HZ):
        self.hz = _clamp_hz(hz)
        self.samples = 0
        self.started_at: float | None = None
        # collapsed stack (root;...;leaf) -> sample count
        self._stacks: dict[tuple[str, ...], int] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "SamplingProfiler":
        if self._thread is not None:
            return self
        self.started_at = time.time()
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="weedtpu-profiler",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout)
        self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- sampling -------------------------------------------------------

    def _run(self) -> None:
        period = 1.0 / self.hz
        me = threading.get_ident()
        while not self._stop.wait(period):
            self._sample_once(me)

    def _sample_once(self, skip_ident: int | None = None) -> None:
        if skip_ident is None:
            skip_ident = threading.get_ident()
        frames = sys._current_frames()
        with self._lock:
            self.samples += 1
            for ident, frame in frames.items():
                if ident == skip_ident:
                    continue  # the sampler observing itself is noise
                stack: list[str] = []
                f = frame
                while f is not None:
                    stack.append(_frame_label(f))
                    f = f.f_back
                if not stack:
                    continue
                key = tuple(reversed(stack))  # root -> leaf
                self._stacks[key] = self._stacks.get(key, 0) + 1

    # -- rendering ------------------------------------------------------

    def stacks_snapshot(self) -> dict[tuple[str, ...], int]:
        with self._lock:
            return dict(self._stacks)

    def collapsed(self, limit: int = 0) -> str:
        """flamegraph.pl input: one ``root;child;leaf count`` line per
        distinct stack, heaviest first."""
        items = sorted(self.stacks_snapshot().items(),
                       key=lambda kv: -kv[1])
        if limit > 0:
            items = items[:limit]
        return "\n".join(f"{';'.join(stack)} {n}" for stack, n in items)

    def table(self, limit: int = 40) -> str:
        """Cumulative per-frame table: self (leaf) and total (anywhere on
        the stack) counts, heaviest-total first.  Percentages are of all
        THREAD-samples (each tick samples every live thread), so an idle
        10-thread process shows ~100% in wait frames, not 1000%."""
        snap = self.stacks_snapshot()
        self_n: dict[str, int] = {}
        total_n: dict[str, int] = {}
        for stack, n in snap.items():
            self_n[stack[-1]] = self_n.get(stack[-1], 0) + n
            for fr in set(stack):  # count once even if recursive
                total_n[fr] = total_n.get(fr, 0) + n
        thread_samples = max(1, sum(snap.values()))
        rows = sorted(total_n.items(), key=lambda kv: -kv[1])[:limit]
        out = [f"samples={self.samples} hz={self.hz:g} "
               f"thread_samples={sum(snap.values())}",
               f"{'self':>8} {'self%':>7} {'total':>8} {'total%':>7}  frame"]
        for fr, tot in rows:
            s = self_n.get(fr, 0)
            out.append(f"{s:8d} {100.0 * s / thread_samples:6.1f}% "
                       f"{tot:8d} {100.0 * tot / thread_samples:6.1f}%  {fr}")
        return "\n".join(out)


# -- the process-wide continuous profiler --------------------------------

_global_lock = threading.Lock()
_global: SamplingProfiler | None = None


def global_profiler() -> SamplingProfiler | None:
    return _global


def ensure_started() -> SamplingProfiler | None:
    """Idempotently start the continuous profiler when WEEDTPU_PROFILE_HZ
    asks for one.  Every server calls this at start(); the profiler is
    process-wide, so co-hosted servers share it."""
    global _global
    hz = profile_hz()
    with _global_lock:
        if hz <= 0:
            return _global
        # compare CLAMPED rates: an out-of-range env value (hz=2000)
        # would otherwise never equal the running profiler's clamped hz
        # and every co-hosted server's start() would restart the
        # profiler, discarding the accumulated baseline
        if _global is None or not _global.running or \
                _global.hz != _clamp_hz(hz):
            if _global is not None:
                _global.stop()
            _global = SamplingProfiler(hz).start()
        return _global


def shutdown() -> None:
    """Stop the continuous profiler (tests; servers leave it running)."""
    global _global
    with _global_lock:
        if _global is not None:
            _global.stop()
            _global = None


# -- kernel profile (device-side twin, fed by ops/dispatch.py) -----------

class KernelProfile:
    """Per-kernel host/device time + transfer accounting.

    One row per codec entry point, accumulating: calls, host-side
    dispatch wall (`wall_s`), seconds blocked in `block_until_ready`
    (`device_s`), the calling thread's seconds in the H2D put and the
    D2H copy with their bytes, and payload bytes (module docstring)."""

    _FIELDS = ("calls", "wall_s", "device_s", "h2d_s", "d2h_s",
               "bytes", "h2d_bytes", "d2h_bytes")

    def __init__(self):
        self._rows: dict[str, dict[str, float]] = {}
        self._notes: dict[str, dict] = {}
        self._lock = threading.Lock()

    def record(self, kernel: str, backend: str = "host", *,
               calls: float = 1.0, wall_s: float = 0.0,
               device_s: float = 0.0, h2d_s: float = 0.0,
               d2h_s: float = 0.0, nbytes: float = 0.0,
               h2d_bytes: float = 0.0, d2h_bytes: float = 0.0) -> None:
        key = f"{kernel}[{backend}]"
        add = (calls, wall_s, device_s, h2d_s, d2h_s, nbytes, h2d_bytes,
               d2h_bytes)
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._rows[key] = dict.fromkeys(self._FIELDS, 0.0)
            for f, v in zip(self._FIELDS, add):
                if v:
                    row[f] += v

    def note(self, kernel: str, backend: str = "host", **what) -> None:
        """What the entry point last ran, said and not summed (the rows
        and tile of the encode matrix): /perf carries it on the kernel's
        rows."""
        self._notes[f"{kernel}[{backend}]"] = what

    def notes(self, key: str) -> dict:
        return dict(self._notes.get(key, ()))

    def snapshot(self) -> dict[str, dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._rows.items()}

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()
        self._notes.clear()

    def table(self) -> str:
        snap = sorted(self.snapshot().items(),
                      key=lambda kv: -(kv[1]["wall_s"] + kv[1]["device_s"]
                                       + kv[1]["d2h_s"]))
        out = [f"{'calls':>7} {'wall_ms':>9} {'device_ms':>9} "
               f"{'h2d_ms':>8} {'d2h_ms':>8} {'MB':>9}  kernel"]
        for key, r in snap:
            out.append(
                f"{int(r['calls']):7d} {r['wall_s'] * 1e3:9.1f} "
                f"{r['device_s'] * 1e3:9.1f} {r['h2d_s'] * 1e3:8.1f} "
                f"{r['d2h_s'] * 1e3:8.1f} "
                f"{r['bytes'] / 1e6:9.1f}  {key}")
        return "\n".join(out)


KERNELS = KernelProfile()


# -- what the codec selection resolved to ---------------------------------

_codecs_noted: dict[tuple, dict] = {}
_codecs_lock = threading.Lock()


def note_codec(key: tuple, make_info) -> None:
    """Record, and log the first time, what one codec selection resolved
    to in this process (ops/codecs.resolve builds the block with
    ops/dispatch.describe — lazily, `key` is checked first).  /perf
    carries the blocks, so "which backend is this volume server really
    encoding on" has an answer that does not depend on reading the
    environment.  The first block that names a JAX platform also starts
    the compile counter: from here on this process may compile."""
    if key in _codecs_noted:  # the per-read path: one dict probe
        return
    with _codecs_lock:
        if key in _codecs_noted:
            return
        info = _codecs_noted[key] = make_info()
    if "platform" in info:
        _count_compiles()
    import logging
    logging.getLogger("ec").info("ec codec resolved: %s", info)


def codecs_snapshot() -> list[dict]:
    with _codecs_lock:
        return [dict(v) for v in _codecs_noted.values()]


def jax_backend_noted() -> bool:
    """True once a codec selection in this process has initialised a JAX
    backend — the only processes allowed to ask JAX about its devices."""
    with _codecs_lock:
        return any("platform" in v for v in _codecs_noted.values())


# -- compilations, counted inside the program -----------------------------
#
# jax.monitoring hands every listener the duration events JAX records;
# "/jax/core/compile/backend_compile_duration" is recorded by the same
# context manager that writes JAX_LOG_COMPILES's "Finished XLA
# compilation of ..." line (jax 0.9.0: _src/dispatch.log_elapsed_time
# from pxla._cached_compilation), synchronously on the compiling thread,
# so this count and a grep of that log agree.  Always on once a codec
# resolved to a JAX backend: it costs nothing unless something compiles.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_entry = threading.local()
_compiles: dict[str, list[float]] = {}  # entry -> [count, seconds]
_compiles_lock = threading.Lock()
_compiles_counted = False


def codec_entry(name: str):
    """Decorator of a dispatch-seam entry point: compilations on the
    calling thread book to `name` while the call is open."""
    def deco(fn):
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            prev = getattr(_compile_entry, "name", None)
            _compile_entry.name = name
            try:
                return fn(*args, **kwargs)
            finally:
                _compile_entry.name = prev
        return entry
    return deco


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    entry = getattr(_compile_entry, "name", None) or "other"
    with _compiles_lock:
        row = _compiles.setdefault(entry, [0, 0.0])
        row[0] += 1
        row[1] += duration


def _count_compiles() -> None:
    global _compiles_counted
    with _compiles_lock:
        if _compiles_counted:
            return
        _compiles_counted = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def compiles_snapshot() -> dict[str, dict]:
    """{entry point: {count, seconds}} of the backend compilations so
    far; entry `other` is whatever compiled outside the dispatch seam."""
    with _compiles_lock:
        return {k: {"count": int(c), "seconds": round(secs, 6)}
                for k, (c, secs) in _compiles.items()}


# which (resource, seconds-field, bytes-field) pairs a kernel row feeds:
# compute uses the device seconds on device rows and host wall on host
# rows; the transfer resources read their dedicated columns
_ROOFLINE_TRANSFERS = (("h2d", "h2d_s", "h2d_bytes"),
                       ("d2h", "d2h_s", "d2h_bytes"))


def roofline_snapshot() -> dict:
    """Per-kernel calls, bytes, busy seconds and achieved GB/s per
    resource (`rows`).  Rows without meaningful time (<1ms accumulated)
    are skipped: a rate computed over noise says nothing."""
    rows: list[dict] = []
    for key, r in KERNELS.snapshot().items():
        kernel, _, backend = key.partition("[")
        backend = backend.rstrip("]")
        compute_s = r["device_s"] if backend == "device" else r["wall_s"]
        candidates = [("device" if backend == "device" else "host",
                       compute_s, r["bytes"])]
        for resource, sfield, bfield in _ROOFLINE_TRANSFERS:
            candidates.append((resource, r[sfield], r[bfield]))
        if kernel == "shard_write":
            # the writer pool's disk seconds ride the wall/bytes columns
            candidates = [("disk", r["wall_s"], r["bytes"])]
        for resource, secs, nbytes in candidates:
            if secs < 1e-3 or nbytes <= 0:
                continue
            gbps = nbytes / 1e9 / secs
            row = {"kernel": kernel, "backend": backend,
                   "resource": resource, "calls": int(r["calls"]),
                   "busy_s": round(secs, 4),
                   "gbytes": round(nbytes / 1e9, 4),
                   "achieved_gbps": round(gbps, 3)}
            row.update(KERNELS.notes(key))
            rows.append(row)
    rows.sort(key=lambda r: -r["busy_s"])
    return {"rows": rows}


# -- /debug/pprof --------------------------------------------------------

async def handle_debug_pprof(req):
    """On-demand profile window: ``?seconds=N`` samples for N seconds at
    ``?hz=`` (default WEEDTPU_PROFILE_HZ or 97) and returns collapsed
    stacks; without ``seconds`` the continuous profiler's cumulative view
    is served (400 when none is running).  ``?format=table`` renders the
    self/total table + the kernel profile instead; ``?format=json``
    returns all three views machine-readably."""
    import asyncio

    from aiohttp import web

    try:
        seconds = float(req.query.get("seconds", "0"))
    except ValueError:
        seconds = 0.0
    seconds = min(seconds, 120.0)
    try:
        hz = float(req.query.get("hz", str(profile_hz() or _DEFAULT_HZ)))
    except ValueError:
        hz = _DEFAULT_HZ
    fmt = req.query.get("format", "collapsed")

    if seconds > 0:
        prof = SamplingProfiler(hz).start()
        try:
            await asyncio.sleep(seconds)
        finally:
            prof.stop()
    else:
        prof = global_profiler()
        if prof is None:
            return web.json_response(
                {"error": "no continuous profiler running; pass "
                          "?seconds=N or set WEEDTPU_PROFILE_HZ"},
                status=400)

    if fmt == "json":
        stacks = [{"stack": list(s), "count": n}
                  for s, n in sorted(prof.stacks_snapshot().items(),
                                     key=lambda kv: -kv[1])]
        return web.json_response({"samples": prof.samples, "hz": prof.hz,
                                  "stacks": stacks,
                                  "kernels": KERNELS.snapshot()})
    if fmt == "table":
        text = (prof.table() + "\n\n-- kernel profile (ops/dispatch) --\n"
                + KERNELS.table() + "\n")
    else:
        text = prof.collapsed() + "\n"
    return web.Response(text=text, content_type="text/plain")


async def handle_debug_jax_profile(req):
    """``/debug/jax_profile?seconds=N[&dir=D]``: open the JAX profiler
    on this running server for N seconds (default 5, at most 120) and
    answer when the trace is written — an operator's window, where
    ``--jax-profile`` covers a whole process life.  The EC plane's
    stages are in it as ``ec.*`` / ``codec.*`` annotations beside the
    device's operations.  One session at a time (utils/grace.py holds
    it): 400 while one is open, and 400 in a process whose codecs run on
    no JAX backend, because a profiler session would initialise one."""
    import asyncio
    import glob
    import tempfile

    from aiohttp import web

    from seaweedfs_tpu.utils import grace

    if not jax_backend_noted():
        return web.json_response(
            {"error": "no codec of this process runs on a JAX backend; "
                      "a profiler session would initialise one"},
            status=400)
    try:
        seconds = float(req.query.get("seconds", "5"))
    except ValueError:
        seconds = 5.0
    seconds = max(0.0, min(seconds, 120.0))
    trace_dir = req.query.get("dir") or tempfile.mkdtemp(
        prefix="weedtpu-jax-profile-")
    # a SIGTERM inside the window still writes the trace (registered
    # here, on the server's loop thread: signal handlers install only
    # from the main thread); start and stop run on a worker thread so
    # that handler never finds the session's lock held by its own thread
    grace.on_interrupt(grace.stop_jax_profile)
    if not await asyncio.to_thread(grace.start_jax_profile, trace_dir):
        return web.json_response(
            {"error": "a JAX profiler session is open already"},
            status=400)
    try:
        await asyncio.sleep(seconds)
    finally:
        await asyncio.to_thread(grace.stop_jax_profile)
    return web.json_response({
        "dir": trace_dir, "seconds": seconds,
        "xplane": sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))})
