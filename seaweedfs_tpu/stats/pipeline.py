"""Pipeline occupancy accounting: the data plane's performance
observatory.

Every overlapped pipeline in the data path (the EC encode/rebuild
engines in storage/ec/ec_files.py, the multi-volume fleet conversion in
ops/fleet_convert.py, the EC degraded-read engine) accumulates
per-stage wall seconds; this module is the always-on answer to "which
stage bounds throughput, and what was the call doing meanwhile?":

- **Stage** (``job.stage(name)`` / ``job.blocked(name)``) — the EC
  plane's ONE timing primitive.  One enter/exit books the stage's
  seconds, bytes and items to its job (and the ``<stage>_s`` key of the
  job's stats dict, the engines' published output), records a
  stats/trace.py span when the ambient request is sampled, and opens a
  ``jax.profiler.TraceAnnotation`` of the same name while — and only
  while — a profiler session is open, so the program's stages land on
  the host plane of the profiler's trace, on the device planes' clock.
  Span names are dotted and stable: ``<job's span prefix>.<stage>``
  (``ec.encode.read``), or ``codec.<stage>`` for the four stages the
  dispatch seam (ops/dispatch.py) books to the calling job.  The bulk
  engines' stages, work unless marked (b) for blocked, which never
  counts as busy:

    caller   ``open`` (tmp outputs created, sources opened, writer
             pools and pipeline threads started, rings allocated),
             ``map`` (every engine's sources mapped, no page made
             ready), ``await_unit`` (b: the dispatcher waiting for
             the reader's next unit), the seam's ``h2d`` and
             ``dispatch``, rebuild's ``stall`` (b) and ``stage``,
             ``join_drain`` (b: the last unit enqueued, waiting for the
             drain and the reader to end), ``join_writers`` (b: the
             writer pool's close: queued writes, then the join),
             ``commit`` (truncate, close, unmap, ``.vif``, renames)
    reader   ``stall`` (b), ``read``, ``ship_data`` (a unit's data-shard
             copy jobs handed to the writers)
    drain    ``await_parity`` / ``await_batch`` (b: waiting for the next
             enqueued unit), the seam's ``device_wait`` and
             ``d2h_copy``, rebuild's ``stall`` (b) and ``unstage``
    writers  ``write_data``, ``write_parity``, ``write``

  On the calling thread they follow one another from the job's first
  line to its last, so they add up to the job's ``call_s``.

- **PipelineJob** — stage accounting for one run: per-stage busy
  seconds (doing work), blocked seconds (backpressured on a downstream
  ring/queue), bytes, items, and queue-depth high-water marks.
  Finished jobs land in a bounded ring; running jobs are observable
  live.  A tracked job opens a ``jax.profiler.TraceAnnotation`` named
  ``job.<span>`` (``job.ec.encode``) carrying its id and its meta (an
  encode's block sizes, rows of each kind and ``large_row_share``), on
  the thread that
  tracks it, and closes it in ``finish()``, under the stages' gate (an
  open profiler session): the call itself is on the profiler's trace,
  from the first file opened to the last rename, and a reader of the
  trace can cut it to the calls.  ``finish()`` states ``call_s``
  (tracked to finished) beside the engine's own ``wall_s``.
  ``occupancy(name, delta)`` is the ONE gauge: a time-weighted count
  (units between enqueue and materialised result: ``inflight``) which
  ``finish()`` states as ``<name>_max``, ``<name>_avg`` and
  ``<name>_ge2_frac`` over ``wall_s``.  ``bottleneck()`` names the stage
  whose busy fraction bounds throughput.

- **FlowAccount** — the continuous twin for long-lived engines (the EC
  read path ``ec_read``, the reduced-read repair ``ec_regen``, the
  scrubber ``ec_scrub``): cumulative per-stage busy seconds/bytes whose
  counter rates ARE stage occupancy
  (``weedtpu_pipeline_stage_seconds_total``: 1 busy-second per second
  == a saturated stage), so "degraded reads went remote-fetch-bound at
  14:05" is a /cluster/history query.

Surfaces: ``/debug/pipeline`` on every server (loopback-gated, mounted
by trace.debug_routes) renders per-job timelines; master
``/cluster/perf`` fans it out and aggregates fleet occupancy; the
``cluster.perf`` shell command and a /cluster/dashboard panel render
both.  ``WEEDTPU_PERF_OBS=0`` turns the whole plane off.
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
import uuid

from seaweedfs_tpu.stats import trace as _trace

# -- knobs ----------------------------------------------------------------

_enabled_cache: tuple[float, bool] = (0.0, True)


def perf_obs_enabled() -> bool:
    """WEEDTPU_PERF_OBS != "0" (default on), cached ~0.5s so hot-path
    checks cost a tuple compare while flipping the env retargets live
    servers."""
    global _enabled_cache
    now = time.monotonic()
    ts, val = _enabled_cache
    if now - ts > 0.5:
        val = os.environ.get("WEEDTPU_PERF_OBS", "1") != "0"
        _enabled_cache = (now, val)
    return val


def _jobs_keep() -> int:
    try:
        return max(1, int(os.environ.get("WEEDTPU_PERF_OBS_JOBS", "32")))
    except ValueError:
        return 32


# -- the job registry -----------------------------------------------------

# one id per process: the master's fleet fan-out dedupes co-hosted
# "nodes" (the all-in-one binary, in-process test clusters) that share
# this module's registry, exactly like the heat tracker id
TRACKER_ID = uuid.uuid4().hex
_seq = itertools.count(1)
_reg_lock = threading.Lock()
_active: dict[int, "PipelineJob"] = {}
_recent: collections.deque = collections.deque(maxlen=_jobs_keep())
_flows: dict[str, "FlowAccount"] = {}

# stages that are WAITING, not working: excluded from busy fractions and
# bottleneck attribution (a fully backpressured producer reads as
# blocked, not as the bottleneck)
IDLE_STAGES = ("stall", "blocked", "idle")

_trace_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded

# attributes of a stage that ride its profiler annotation beside the ids:
# what kind of unit it worked on (its stripe rows and their block size; a
# column cut of a large-block row is rows 1)
UNIT_GEOMETRY = ("rows", "block")


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is open
    in this process, else None.  The session is the only switch.  Never
    imports jax (a process that has not loaded it has no session) and
    asks it nothing that could initialise a backend."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        cls = _trace_annotation = profiler.TraceAnnotation
    return cls if cls.is_enabled() else None


class Stage:
    """One enter/exit of a stage: see the module docstring.  ``seconds``
    holds the stage's wall time after exit (the dispatch seam feeds the
    kernel profile from it); ``set(**attrs)`` adds attributes to the
    trace span, as a ``trace.span`` does."""

    __slots__ = ("_job", "_stage", "_span_name", "_nbytes", "_items",
                 "_blocked", "_unit", "_attrs", "_t0", "_span", "_ann",
                 "seconds")

    def __init__(self, job, stage, span_name, nbytes, items, blocked, unit,
                 attrs):
        self._job = job
        self._stage = stage
        self._span_name = span_name
        self._nbytes = nbytes
        self._items = items
        self._blocked = blocked
        self._unit = unit
        self._attrs = attrs
        self.seconds = 0.0

    def set(self, **attrs) -> None:
        self._span.set(**attrs)

    def __enter__(self):
        self._span = _trace.span(self._span_name, **self._attrs)
        self._span.__enter__()
        ann = _profiler_annotation()
        if ann is not None:
            ids = self._job.annotation_ids()
            if self._unit is not None:
                ids["unit"] = self._unit
            ids.update((k, self._attrs[k]) for k in UNIT_GEOMETRY
                       if k in self._attrs)
            ann = ann(self._span_name, **ids)
            ann.__enter__()
        self._ann = ann
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        self._span.__exit__(etype, exc, tb)
        self._job._book(self._stage, self.seconds, self._nbytes,
                        self._items, self._blocked)
        return False


class PipelineJob:
    """Stage accounting for ONE pipeline run (an encode, a rebuild, a
    fleet conversion).  Its stages book their seconds here AND into the
    ``<stage>_s`` keys of the wrapped stats dict — the engines' published
    output, which /admin/ec/progress reads.  The job adds what a
    dict of floats can't carry: busy apart from blocked time, bytes and
    items per stage, queue-depth high-water marks, liveness, and the
    registry that makes the run observable at /debug/pipeline while it
    is still running.  ``span`` prefixes the job's span and annotation
    names (``ec.encode``; the kind when not given).  ``sums`` names the
    lumps the engine publishes beside their parts, ``{"d2h":
    ("device_wait", "d2h_copy")}``: a part's seconds book to its lump
    too, so the lump IS the sum at every moment of the run.  A job made
    to be registered puts itself on the profiler's trace too, as
    ``job.<span>`` from here to ``finish()``: make it and finish it on
    one thread, the engine's caller."""

    def __init__(self, kind: str, stats: dict | None = None,
                 total_bytes: int = 0, meta: dict | None = None,
                 register: bool = True, span: str | None = None,
                 sums: dict[str, tuple] | None = None):
        self.kind = kind
        self.span = span or kind
        self._lump_of = {part: lump for lump, parts in (sums or {}).items()
                         for part in parts}
        self.stats = stats if stats is not None else {}
        self.total_bytes = total_bytes
        self.meta = meta or {}
        self.started = time.time()
        self._t0 = time.perf_counter()
        self.wall_s: float | None = None
        self.state = "running"
        self.error: str | None = None
        self.job_id = next(_seq)
        self._lock = threading.Lock()
        # stage -> [busy_s, blocked_s, bytes, items]
        self._stages: dict[str, list[float]] = {}
        # queue -> [last, max, sum, samples, bound]
        self._queues: dict[str, list[float]] = {}
        # gauge -> [level, since, level-seconds, seconds at >= 2, max]
        self._gauges: dict[str, list[float]] = {}
        # the call on the profiler's trace, outside the stages' `ec.` /
        # `codec.` names: a reader of stages must not take it for one
        ann = _profiler_annotation() if register else None
        if ann is not None:
            # with the job's meta: what the call works on, said once
            ann = ann("job." + self.span, **self.annotation_ids(),
                      **{k: v for k, v in self.meta.items()
                         if isinstance(v, (int, float, str))})
            ann.__enter__()
        self._ann = ann
        self._registered = register and perf_obs_enabled()
        if self._registered:
            with _reg_lock:
                _active[self.job_id] = self

    # -- accounting ------------------------------------------------------

    def stage(self, name: str, nbytes: float = 0.0, items: float = 1.0,
              unit: int | None = None, span: str | None = None,
              **attrs) -> Stage:
        """CM bracketing productive work attributed to `name`.  `unit`
        rides the profiler annotation, `attrs` the trace span; `span`
        overrides the span name (the dispatch seam's ``codec.*``)."""
        return Stage(self, name, span or f"{self.span}.{name}", nbytes,
                     items, False, unit, attrs)

    def blocked(self, name: str, unit: int | None = None) -> Stage:
        """CM bracketing time spent backpressured on a downstream
        queue/ring — never counted as busy.  The engines book theirs
        under the wait stage `stall`."""
        return Stage(self, name, f"{self.span}.{name}", 0.0, 0.0, True,
                     unit, {})

    def annotation_ids(self) -> dict:
        """What ties a profiler annotation back to this run."""
        return {"job": self.job_id}

    def annotate(self, name: str, **attrs):
        """A profiler annotation `name` with the job's ids and `attrs`,
        entered now, or None while no profiler session is open: the caller
        exits it, on the same thread, where what it marks ends.  For a part
        of a call that is neither the job nor a stage (a volume of a
        backlog rebuild): named outside `job.`, `ec.` and `codec.`, so that
        no reader of the trace takes it for either."""
        ann = _profiler_annotation()
        if ann is None:
            return None
        ann = ann(name, **self.annotation_ids(), **attrs)
        ann.__enter__()
        return ann

    def _book(self, name: str, secs: float, nbytes: float, items: float,
              blocked: bool) -> None:
        with self._lock:
            self._add(name, secs, nbytes, items, blocked)
            lump = self._lump_of.get(name)
            if lump is not None:
                self._add(lump, secs, 0.0, 0.0, blocked)

    def _add(self, name, secs, nbytes, items, blocked) -> None:
        row = self._stages.get(name)
        if row is None:
            row = self._stages[name] = [0.0, 0.0, 0.0, 0.0]
        row[1 if blocked else 0] += secs
        row[2] += nbytes
        row[3] += items
        if secs:
            # a work stage's busy seconds, a wait stage's blocked ones
            key = name + "_s"
            self.stats[key] = self.stats.get(key, 0.0) + secs

    def add_bytes(self, name: str, nbytes: float,
                  items: float = 0.0) -> None:
        self._book(name, 0.0, nbytes, items, False)

    def count(self, name: str, n: int) -> None:
        """Add to a plain count the run states beside its stage seconds
        (``stats[name]``: a value of /admin/ec/progress `stages`)."""
        with self._lock:
            self.stats[name] = self.stats.get(name, 0) + n

    def occupancy(self, name: str, delta: int) -> None:
        """Move the gauge `name` by `delta` (+1 where a unit is enqueued,
        -1 where its result is materialised, from whichever threads):
        the count is weighted by the time it stood, and `finish()` states
        its maximum, its mean and the share of the wall at two or more."""
        now = time.perf_counter()
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = [0, now, 0.0, 0.0, 0]
            self._settle(g, now)
            g[0] += delta
            if g[0] > g[4]:
                g[4] = g[0]

    @staticmethod
    def _settle(g: list, now: float) -> None:
        """Book the time gauge `g` stood at its level since its last move."""
        held = now - g[1]
        g[2] += g[0] * held
        if g[0] >= 2:
            g[3] += held
        g[1] = now

    def queue(self, name: str, depth: int, bound: int = 0) -> None:
        """Sample a queue's depth (producers call at put/get sites)."""
        with self._lock:
            q = self._queues.get(name)
            if q is None:
                q = self._queues[name] = [0.0, 0.0, 0.0, 0.0, float(bound)]
            q[0] = depth
            if depth > q[1]:
                q[1] = depth
            q[2] += depth
            q[3] += 1
            if bound:
                q[4] = float(bound)

    def _wall(self) -> float:
        """The run's clock (under the lock): the stats dict's wall_s when
        the pipeline stamped one — the job's own bracket includes
        setup/teardown outside it — else that bracket, so far."""
        wall = self.stats.get("wall_s")
        if isinstance(wall, (int, float)) and wall > 0:
            return wall
        return self.wall_s if self.wall_s is not None \
            else time.perf_counter() - self._t0

    def finish(self, error: BaseException | str | None = None) -> None:
        """Seal the job: stamp the wall clock (`call_s`), state the
        gauges, close the job's annotation, book the cumulative stage
        seconds/bytes counters, move registry entry active -> recent."""
        with self._lock:
            if self.state != "running":
                return
            now = time.perf_counter()
            self.wall_s = self.stats["call_s"] = now - self._t0
            self.state = "failed" if error else "done"
            if error:
                self.error = str(error) or type(error).__name__
            wall = max(self._wall(), 1e-9)  # a gauge moves only inside it
            for name, g in self._gauges.items():
                self._settle(g, now)
                self.stats[name + "_max"] = int(g[4])
                self.stats[name + "_avg"] = round(g[2] / wall, 4)
                self.stats[name + "_ge2_frac"] = round(
                    min(1.0, g[3] / wall), 4)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._registered:
            with _reg_lock:
                _active.pop(self.job_id, None)
                _recent.append(self)
            try:
                from seaweedfs_tpu.stats import metrics
                for stage, row in self.snapshot()["stages"].items():
                    if row["busy_s"]:
                        # occupancy-seconds: an N-worker pool's summed
                        # busy seconds divide by N so the counter RATE
                        # tops out at 1/s for a saturated stage (the
                        # "1.0 = saturated" dashboard/README contract)
                        metrics.PIPELINE_STAGE_SECONDS.labels(
                            self.kind, stage).inc(
                                row["busy_s"] / row.get("workers", 1))
                    if row["bytes"]:
                        metrics.PIPELINE_STAGE_BYTES.labels(
                            self.kind, stage).inc(row["bytes"])
            except Exception:
                pass  # metric export must never fail the data plane

    def __enter__(self):
        return self

    def __exit__(self, etype, exc, tb):
        self.finish(exc)
        return False

    # -- rendering -------------------------------------------------------

    def _stats_stage_seconds(self) -> dict[str, float]:
        """Stage wall-seconds from the wrapped stats dict (`encode_s`,
        `write_parity_s`, ...).  `wall_s` and `call_s` are clocks, not
        stages."""
        out: dict[str, float] = {}
        for key, v in list(self.stats.items()):
            if key.endswith("_s") and key not in ("wall_s", "call_s") and \
                    isinstance(v, (int, float)):
                out[key[:-2]] = float(v)
        return out

    def snapshot(self) -> dict:
        with self._lock:
            stages_own = {k: list(v) for k, v in self._stages.items()}
            queues = {k: list(v) for k, v in self._queues.items()}
            wall = self._wall()
            state, error = self.state, self.error
        merged: dict[str, dict] = {
            name: {"busy_s": busy, "blocked_s": blocked, "bytes": nbytes,
                   "items": items}
            for name, (busy, blocked, nbytes, items) in stages_own.items()}
        # seconds no Stage timed: `<stage>_s` keys an engine folds
        # straight into the stats dict
        for name, secs in self._stats_stage_seconds().items():
            row = merged.setdefault(
                name, {"busy_s": 0.0, "blocked_s": 0.0, "bytes": 0.0,
                       "items": 0.0})
            if not (row["busy_s"] or row["blocked_s"]):
                row["busy_s"] = secs
        # the stall stage is idle/backpressure time, not work
        for name in list(merged):
            if name in IDLE_STAGES:
                row = merged.pop(name)
                merged.setdefault(
                    "_idle", {"busy_s": 0.0, "blocked_s": 0.0,
                              "bytes": 0.0, "items": 0.0})
                merged["_idle"]["blocked_s"] += row["busy_s"] + \
                    row["blocked_s"]
        idle = merged.pop("_idle", None)
        wall = max(wall, 1e-9)
        for name, row in merged.items():
            # a stage served by N parallel workers (the shard writer
            # pools publish `<stage>_workers`) accumulates up to N busy
            # seconds per wall second: busy_frac is OCCUPANCY of the
            # stage's capacity, not raw seconds over wall — otherwise a
            # 4-worker 30%-busy pool reads as a 120%-saturated bottleneck
            w = self.stats.get(f"{name}_workers")
            if isinstance(w, (int, float)) and w > 1:
                # may be fractional: a shared pool's threads split
                # across its stages by busy share.  Keep the float —
                # finish() divides the exported counter by this value,
                # and truncating to int would re-inflate the rate
                row["workers"] = round(float(w), 2)
            else:
                w = 1
            row["busy_frac"] = round(row["busy_s"] / (w * wall), 4)
            for k in ("busy_s", "blocked_s", "bytes", "items"):
                row[k] = round(row[k], 6)
        snap = {
            "id": self.job_id, "kind": self.kind, "state": state,
            "started": round(self.started, 3), "wall_s": round(wall, 4),
            "bytes": self.total_bytes or self.stats.get("bytes", 0),
            "stages": merged,
            "queues": {k: {"last": int(q[0]), "max": int(q[1]),
                           "avg": round(q[2] / q[3], 2) if q[3] else 0.0,
                           "bound": int(q[4])}
                       for k, q in queues.items()},
        }
        if idle is not None:
            snap["blocked_s"] = round(idle["blocked_s"], 4)
        if error:
            snap["error"] = error
        if self.meta:
            snap["meta"] = dict(self.meta)
        bn = bottleneck(snap)
        if bn is not None:
            snap["bottleneck"] = bn
        return snap


class FlowAccount(PipelineJob):
    """A never-finishing PipelineJob for long-lived engines (the EC
    degraded-read path): cumulative per-stage busy seconds and bytes,
    exported incrementally as ``weedtpu_pipeline_stage_seconds_total``
    so the counter RATE is live stage occupancy.  Registered once per
    (process, kind)."""

    def __init__(self, kind: str, span: str | None = None):
        super().__init__(kind, register=False, span=span)
        self.state = "flow"
        # per-stage (seconds-counter, bytes-counter) children, resolved
        # once: a labels() registry lookup per read is measurable tax on
        # a ~60us page-cache needle read
        self._children: dict[str, tuple] = {}
        with _reg_lock:
            # first registration wins: a racing creator books to the
            # same (shared) metric counters either way
            _flows.setdefault(kind, self)

    def _stage_counters(self, name: str) -> tuple | None:
        pair = self._children.get(name)
        if pair is None:
            try:
                from seaweedfs_tpu.stats import metrics
                pair = (metrics.PIPELINE_STAGE_SECONDS.labels(
                            self.kind, name),
                        metrics.PIPELINE_STAGE_BYTES.labels(
                            self.kind, name))
            except Exception:
                return None
            self._children[name] = pair
        return pair

    def _book(self, name, secs, nbytes, items, blocked):
        super()._book(name, secs, nbytes, items, blocked)
        if blocked or not perf_obs_enabled():
            return
        pair = self._stage_counters(name)
        if pair is None:
            return
        if secs:
            pair[0].inc(secs)
        if nbytes:
            pair[1].inc(nbytes)

    def annotation_ids(self) -> dict:
        t = _trace.current()
        return {"trace": t.trace_id} if t is not None else {}


def track(kind: str, stats: dict | None = None, total_bytes: int = 0,
          meta: dict | None = None, span: str | None = None,
          sums: dict[str, tuple] | None = None) -> PipelineJob:
    """The one-liner pipelines wrap themselves in::

        with pipeline.track("ec_encode", stats, dat_size,
                            span="ec.encode") as job:
            with job.stage("read", unit=i): ...
            ... job.queue("read", q.qsize()) ...

    With the observatory off the job is unregistered: its stages still
    time into the stats dict, record spans and annotate the profiler's
    trace, but nothing is retained or exported."""
    return PipelineJob(kind, stats, total_bytes, meta, span=span, sums=sums)


class _Untracked(PipelineJob):
    """Where the dispatch seam books the stages of a caller that runs no
    job (the scrubber, the repair plane, a bare codec call): the spans
    and annotations are recorded, the seconds kept nowhere."""

    def _book(self, name, secs, nbytes, items, blocked):
        pass

    def annotation_ids(self) -> dict:
        return {}


UNTRACKED = _Untracked("untracked", register=False)


def flow(kind: str, span: str | None = None) -> FlowAccount:
    # lock-free fast path: dict.get is atomic under the GIL, and this
    # rides per-needle-read hot paths (the EC read engine)
    acct = _flows.get(kind)
    if acct is not None:
        return acct
    FlowAccount(kind, span)  # registers itself (first registration wins)
    return _flows[kind]


def jobs_snapshot(limit: int | None = None) -> list[dict]:
    """Recent + running jobs, newest first, plus the continuous flow
    accounts."""
    with _reg_lock:
        jobs = list(_active.values()) + list(_recent)
        flows = list(_flows.values())
    out = [j.snapshot() for j in jobs]
    out.sort(key=lambda s: -s["started"])
    if limit:
        out = out[:limit]
    return out + [f.snapshot() for f in flows]


def reset() -> None:
    """Tests: drop every retained job and flow account."""
    global _recent
    with _reg_lock:
        _active.clear()
        _recent = collections.deque(maxlen=_jobs_keep())
        _flows.clear()


# -- bottleneck attribution -----------------------------------------------

def bottleneck(snap: dict) -> dict | None:
    """The stage whose busy fraction bounds this job's throughput, with
    its achieved GB/s where it booked bytes.  Stages are concurrent
    (that is the point of the pipelines), so the max busy-FRACTION
    stage — occupancy of the stage's worker capacity, see snapshot() —
    IS the throughput bound: the wall clock can never beat the time its
    most-saturated stage needs.  Busy seconds break busy_frac ties
    (long-lived flow accounts round their fractions to ~0)."""
    stages = snap.get("stages") or {}
    best_name, best = None, None
    for name, row in stages.items():
        if name in IDLE_STAGES or row.get("busy_s", 0.0) <= 0:
            continue
        key = (row.get("busy_frac", 0.0), row["busy_s"])
        if best is None or key > best:
            best_name, best = name, key
    if best_name is None:
        return None
    row = stages[best_name]
    out = {"stage": best_name,
           "busy_frac": row.get("busy_frac", 0.0)}
    if row.get("bytes"):
        # aggregate stage rate: N workers' summed seconds cover bytes
        # in busy_s/N of wall time
        active = row["busy_s"] / row.get("workers", 1)
        gbps = row["bytes"] / 1e9 / max(active, 1e-9)
        out["achieved_gbps"] = round(gbps, 3)
    return out


# -- fleet aggregation (master /cluster/perf) ------------------------------

def aggregate_fleet(per_node: list[tuple[str, dict]]) -> dict:
    """Merge per-node /debug/pipeline payloads into fleet occupancy:
    per (kind, stage) busy seconds / bytes / max busy fraction across
    every reporting node, the currently-running jobs, and the worst
    bottleneck verdict per kind.
    Payloads from nodes sharing one process (the all-in-one binary,
    in-process test clusters) carry the same tracker ``id`` and are
    merged once, not once per node."""
    occupancy: dict[str, dict[str, dict]] = {}
    running: list[dict] = []
    verdicts: dict[str, dict] = {}
    seen: set[str] = set()
    nodes: list[str] = []
    for node, payload in per_node:
        tid = payload.get("id")
        if tid is not None and tid in seen:
            continue
        if tid is not None:
            seen.add(tid)
        nodes.append(node)
        for job in payload.get("jobs", []):
            kind = job.get("kind", "?")
            krow = occupancy.setdefault(kind, {})
            for stage, row in (job.get("stages") or {}).items():
                srow = krow.setdefault(
                    stage, {"busy_s": 0.0, "bytes": 0.0, "jobs": 0,
                            "max_busy_frac": 0.0})
                srow["busy_s"] = round(srow["busy_s"] + row["busy_s"], 4)
                srow["bytes"] += row.get("bytes", 0.0)
                srow["jobs"] += 1
                if row.get("busy_frac", 0.0) > srow["max_busy_frac"]:
                    srow["max_busy_frac"] = row["busy_frac"]
            if job.get("state") == "running":
                running.append({"node": node, **job})
            bn = job.get("bottleneck")
            if bn:
                prev = verdicts.get(kind)
                if prev is None or bn.get("busy_frac", 0.0) > \
                        prev.get("busy_frac", 0.0):
                    verdicts[kind] = {"node": node, **bn}
    return {"nodes": nodes, "occupancy": occupancy,
            "bottlenecks": verdicts, "running": running}


# -- /debug/pipeline -------------------------------------------------------

def local_snapshot(limit: int = 16) -> dict:
    """Everything this process knows about its own data-plane
    performance: jobs + flows and the kernel roofline.  The payload
    /cluster/perf federates."""
    from seaweedfs_tpu.stats import profile as _profile
    return {"id": TRACKER_ID, "enabled": perf_obs_enabled(),
            "jobs": jobs_snapshot(limit),
            "roofline": _profile.roofline_snapshot(),
            # what each codec selection resolved to (backend, device,
            # class, interpret, tile); empty until a codec was built
            "codecs": _profile.codecs_snapshot(),
            # backend compilations by the codec entry point open on the
            # compiling thread: {entry: {count, seconds}}
            "compiles": _profile.compiles_snapshot()}


async def handle_debug_pipeline(req):
    """``/debug/pipeline[?limit=N]``: per-job stage timelines (busy /
    blocked / queue depths / bottleneck verdicts), the continuous flow
    accounts, and the per-kernel roofline table.  Mounted loopback-gated
    on every server by trace.debug_routes()."""
    from aiohttp import web
    try:
        limit = int(req.query.get("limit", "16"))
    except ValueError:
        limit = 16
    return web.json_response(local_snapshot(limit))


async def handle_perf(req):
    """``/perf``: the same payload, mounted OPEN on cluster-internal
    servers (the /heat posture — netflow classifies it internal) so the
    master's /cluster/perf fan-out works when nodes are not loopback to
    the master; the public s3 gateway wraps it in the debug guard."""
    return await handle_debug_pipeline(req)
