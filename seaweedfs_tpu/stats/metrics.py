"""Prometheus-style metrics: counters/gauges/histograms + text exposition.

Reference: weed/stats/metrics.go — per-role registries (master/volume/filer)
with request counters, latency histograms, volume gauges, and optional push
to a gateway. Implemented on the stdlib; the /metrics endpoint on every
server serves `render()` in Prometheus text exposition format 0.0.4.
"""

from __future__ import annotations

import threading
import time
import urllib.error
import urllib.request

from seaweedfs_tpu.stats import trace as _trace
from seaweedfs_tpu.utils import weedlog

_DEFAULT_BUCKETS = (0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _esc(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: tuple[tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{k}="{_esc(v)}"' for k, v in labels]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    kind = "untyped"
    # cardinality bound: label values can come from client input (collection
    # names); past this, samples collapse into an "__other__" series instead
    # of growing server memory without bound
    MAX_CHILDREN = 1000

    def __init__(self, name: str, help_text: str, label_names: tuple[str, ...]):
        self.name, self.help, self.label_names = name, help_text, label_names
        self._children: dict[tuple[str, ...], object] = {}
        self._lock = threading.Lock()

    def labels(self, *values: str):
        values = tuple(str(v) for v in values)
        if len(values) != len(self.label_names):
            raise ValueError(f"{self.name}: want {len(self.label_names)} labels")
        with self._lock:
            child = self._children.get(values)
            if child is None:
                if len(self._children) >= self.MAX_CHILDREN:
                    values = ("__other__",) * len(self.label_names)
                    child = self._children.get(values)
                    if child is not None:
                        return child
                child = self._new_child()
                self._children[values] = child
            return child

    def remove_matching(self, **by_label) -> int:
        """Drop every child whose labels match the given values.  Servers
        retire their own per-instance series (disk dirs, hosted volumes)
        at stop(), so a long-lived process that restarts or decommissions
        a server does not accumulate stale capacity series forever."""
        idx = {self.label_names.index(k): str(v)
               for k, v in by_label.items()}
        with self._lock:
            dead = [vals for vals in self._children
                    if all(vals[i] == v for i, v in idx.items())]
            for vals in dead:
                del self._children[vals]
        return len(dead)

    def _pairs(self):
        with self._lock:
            items = list(self._children.items())
        for values, child in items:
            yield tuple(zip(self.label_names, values)), child


class _CounterValue:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount


class Counter(_Metric):
    kind = "counter"
    _new_child = staticmethod(_CounterValue)

    def render(self, openmetrics: bool = False) -> list[str]:
        name = self.name
        if openmetrics:
            # OpenMetrics names the counter FAMILY without _total and the
            # samples WITH it — a negotiating Prometheus rejects the whole
            # scrape otherwise
            family = name[:-6] if name.endswith("_total") else name
            out = [f"# HELP {family} {self.help}",
                   f"# TYPE {family} counter"]
            for labels, child in self._pairs():
                out.append(
                    f"{family}_total{_fmt_labels(labels)} {child.value}")
            return out
        out = [f"# HELP {name} {self.help}", f"# TYPE {name} counter"]
        for labels, child in self._pairs():
            out.append(f"{name}{_fmt_labels(labels)} {child.value}")
        return out


class _GaugeValue(_CounterValue):
    def set(self, value: float) -> None:
        with self._lock:
            self.value = value

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class Gauge(_Metric):
    kind = "gauge"
    _new_child = staticmethod(_GaugeValue)

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        for labels, child in self._pairs():
            out.append(f"{self.name}{_fmt_labels(labels)} {child.value}")
        return out


class _HistogramValue:
    __slots__ = ("buckets", "counts", "total", "count", "exemplars",
                 "_lock")

    def __init__(self, buckets=_DEFAULT_BUCKETS):
        self.buckets = buckets
        self.counts = [0] * len(buckets)
        self.total = 0.0
        self.count = 0
        # last sampled-trace observation per bucket (+Inf last):
        # (value, trace_id, unix_ts) — the exemplar that lets a latency
        # bucket link to a trace in /debug/traces
        self.exemplars: list[tuple | None] = [None] * (len(buckets) + 1)
        self._lock = threading.Lock()

    def observe(self, value: float, trace_id: str | None = None) -> None:
        with self._lock:
            self.total += value
            self.count += 1
            slot = len(self.buckets)
            for i, b in enumerate(self.buckets):
                if value <= b:
                    self.counts[i] += 1
                    slot = i
                    break
            if trace_id is not None:
                self.exemplars[slot] = (value, trace_id, time.time())

    def time(self):
        return _Timer(self)


class _Timer:
    def __init__(self, hist: _HistogramValue):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._hist.observe(time.perf_counter() - self._t0,
                           _trace.current_exemplar())
        return False


def _exemplar_suffix(ex: tuple | None) -> str:
    """OpenMetrics exemplar: ` # {trace_id="..."} value timestamp` — links
    a latency bucket to a sampled trace in /debug/traces.  The trace id is
    escaped exactly like a label value: exemplars go through the same
    strict OpenMetrics parser, and observe() takes the id from a header
    the CALLER controls, so a stray quote must not break the scrape."""
    if ex is None:
        return ""
    value, trace_id, ts = ex
    return f' # {{trace_id="{_esc(str(trace_id))}"}} {value} {round(ts, 3)}'


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help_text, label_names, buckets=_DEFAULT_BUCKETS):
        super().__init__(name, help_text, label_names)
        self._buckets = buckets

    def _new_child(self):
        return _HistogramValue(self._buckets)

    def render(self, openmetrics: bool = False) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        for labels, child in self._pairs():
            cum = 0
            for i, (b, c) in enumerate(zip(child.buckets, child.counts)):
                cum += c
                le = f'le="{b}"'
                ex = _exemplar_suffix(child.exemplars[i]) \
                    if openmetrics else ""
                out.append(f"{self.name}_bucket"
                           f"{_fmt_labels(labels, le)} {cum}{ex}")
            inf = 'le="+Inf"'
            ex = _exemplar_suffix(child.exemplars[-1]) if openmetrics else ""
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels(labels, inf)} {child.count}{ex}")
            out.append(f"{self.name}_sum{_fmt_labels(labels)} {child.total}")
            out.append(f"{self.name}_count{_fmt_labels(labels)} {child.count}")
        return out


class Registry:
    def __init__(self):
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()
        # optional self-cost gauge: stamped with series_count() on every
        # render so a dashboard can watch the registry's own cardinality
        self._series_gauge: "Gauge | None" = None

    def series_count(self) -> int:
        """Live label sets (children) across every family — the
        registry's own cardinality, i.e. what each scrape costs."""
        with self._lock:
            ms = list(self._metrics.values())
        return sum(len(m._children) for m in ms)

    def _register(self, metric: _Metric) -> _Metric:
        with self._lock:
            if metric.name in self._metrics:
                return self._metrics[metric.name]
            self._metrics[metric.name] = metric
            return metric

    def counter(self, name, help_text="", labels=()) -> Counter:
        return self._register(Counter(name, help_text, tuple(labels)))

    def gauge(self, name, help_text="", labels=()) -> Gauge:
        return self._register(Gauge(name, help_text, tuple(labels)))

    def histogram(self, name, help_text="", labels=(),
                  buckets=_DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram(name, help_text, tuple(labels), buckets))

    def render(self, openmetrics: bool = False) -> str:
        if self._series_gauge is not None:
            self._series_gauge.labels().set(self.series_count())
        with self._lock:
            metrics = list(self._metrics.values())
        lines: list[str] = []
        for m in metrics:
            if isinstance(m, (Histogram, Counter)):
                lines.extend(m.render(openmetrics))
            else:
                lines.extend(m.render())
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def push(self, gateway_url: str, job: str, pool=None) -> bool:
        """One push-gateway PUT (stats/metrics.go:14 StartPushingMetric).
        A gateway failure is a monitoring problem, not a server problem:
        it is logged at V(1) and reported as False — never raised into
        the caller's loop.  Retry cadence lives in MetricsPusher, which
        passes its PooledHTTP so repeated pushes reuse one keep-alive
        socket instead of dialing the gateway every interval."""
        body = self.render().encode()
        url = f"{gateway_url.rstrip('/')}/metrics/job/{job}"
        try:
            if pool is not None:
                status, _, _ = pool.request(
                    url, method="PUT", body=body,
                    headers={"Content-Type": "text/plain"}, timeout=5.0)
                if status // 100 != 2:
                    raise ValueError(f"gateway answered HTTP {status}")
                return True
            req = urllib.request.Request(
                url, data=body, method="PUT",
                headers={"Content-Type": "text/plain"})
            urllib.request.urlopen(req, timeout=5).close()
            return True
        except Exception as e:  # URLError/OSError/HTTPException/ValueError
            weedlog.V(1, "metrics").infof(
                "metrics push to %s failed: %s", gateway_url, e)
            return False


class MetricsPusher:
    """Background push-gateway loop (stats/metrics.go StartPushingMetric):
    pushes every `interval` seconds over one keep-alive PooledHTTP,
    backing off exponentially (capped at `max_backoff`) while the gateway
    is unreachable, and stop()s cleanly at shutdown.

    DNS is NOT latched for the process lifetime: the socket pool is keyed
    by hostname and a parked keep-alive connection pins whatever address
    the first dial resolved.  After two consecutive push failures the
    pool is dropped and the gateway hostname re-resolved, so a
    re-pointed gateway CNAME (the common failover move for a
    long-lived daemon's monitoring sink) is picked up mid-process
    instead of failing until restart."""

    RE_RESOLVE_AFTER = 2  # consecutive failures before forcing fresh DNS

    def __init__(self, registry: Registry, gateway_url: str, job: str,
                 interval: float = 15.0, max_backoff: float = 300.0):
        from seaweedfs_tpu.utils.http import PooledHTTP
        self.registry = registry
        self.gateway_url = gateway_url
        self.job = job
        self.interval = interval
        self.max_backoff = max_backoff
        self.failures = 0
        self.re_resolves = 0
        self._make_pool = lambda: PooledHTTP(timeout=5.0,
                                             max_idle_per_host=1)
        self.pool = self._make_pool()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="metrics-pusher", daemon=True)

    def start(self) -> "MetricsPusher":
        self._thread.start()
        return self

    def _re_resolve(self) -> None:
        """Drop every pooled socket and ask the resolver again: the next
        push dials whatever the gateway name points at NOW."""
        import socket
        import urllib.parse
        self.pool.close()
        self.pool = self._make_pool()
        self.re_resolves += 1
        host = urllib.parse.urlsplit(self.gateway_url).hostname or ""
        try:
            addrs = sorted({ai[4][0] for ai in
                            socket.getaddrinfo(host, None)})
        except OSError as e:
            addrs = [f"unresolvable: {e}"]
        weedlog.V(1, "metrics").infof(
            "gateway %s unreachable %d times; re-resolved %s -> %s",
            self.gateway_url, self.failures, host, addrs)

    def _run(self) -> None:
        # shared decorrelated-jitter backoff (utils/resilience.py): a
        # fleet of pushers whose gateway died must NOT re-converge on
        # one retry instant the way synchronized exponential delays do
        from seaweedfs_tpu.utils.resilience import Backoff
        bo = Backoff(base=self.interval, cap=self.max_backoff)
        delay = self.interval
        while not self._stop.wait(delay):
            if self.registry.push(self.gateway_url, self.job,
                                  pool=self.pool):
                self.failures = 0
                bo.reset()
                delay = self.interval
            else:
                self.failures += 1
                if self.failures >= self.RE_RESOLVE_AFTER:
                    self._re_resolve()
                delay = bo.next()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)
        self.pool.close()


def start_pushing(gateway_url: str, job: str, interval: float = 15.0,
                  registry: "Registry | None" = None) -> MetricsPusher:
    """stats/metrics.go StartPushingMetric: spawn the pusher thread."""
    return MetricsPusher(registry or REGISTRY, gateway_url, job,
                         interval).start()


def scrape_response(req):
    """Shared aiohttp /metrics response with content negotiation: the
    OpenMetrics rendering (exemplars linking latency buckets to trace
    ids) when the scraper asks for it, Prometheus text 0.0.4 otherwise."""
    from aiohttp import web
    if "application/openmetrics-text" in req.headers.get("Accept", ""):
        return web.Response(text=REGISTRY.render(openmetrics=True),
                            content_type="application/openmetrics-text")
    return web.Response(text=REGISTRY.render(),
                        content_type="text/plain")


# Global registry + the standard gauges/counters each role uses
# (stats/metrics.go: MasterReceivedHeartbeatCounter, VolumeServerRequestCounter,
# VolumeServerVolumeCounter, FilerRequestCounter, FilerRequestHistogram, ...).
REGISTRY = Registry()

MASTER_RECEIVED_HEARTBEATS = REGISTRY.counter(
    "weedtpu_master_received_heartbeats_total",
    "Heartbeats received by master")
# every completed HTTP request by role/read-write/status class, counted in
# the trace middleware so all four servers feed it — the availability
# input of the cluster SLO engine (stats/aggregate.py)
HTTP_REQUESTS = REGISTRY.counter(
    "weedtpu_http_requests_total",
    "completed requests by server role, read/write op, and status class",
    ("server", "op", "class"))
# byte-flow ledger (stats/netflow.py): body bytes crossing a process
# boundary, by direction (sent/recv), traffic class (data/replication/
# repair/scrub/readahead/internal — carried on X-Weedtpu-Class), and the
# peer's role.  Sender and receiver totals conserve per class.
NET_BYTES = REGISTRY.counter(
    "weedtpu_net_bytes_total",
    "network body bytes by direction, traffic class, and peer role",
    ("direction", "class", "peer_role"))
# PooledHTTP connection economics: how often a request rode a warm
# keep-alive socket vs paid a fresh dial — without these the per-peer
# byte counters can't distinguish "chatty" from "reconnect storm"
HTTP_POOL_REUSE = REGISTRY.counter(
    "weedtpu_http_pool_reuse_total",
    "pooled-client requests served on a reused keep-alive connection")
HTTP_POOL_DIAL = REGISTRY.counter(
    "weedtpu_http_pool_dial_total",
    "pooled-client requests that dialed a fresh connection")
# resilience layer (utils/resilience.py): every retry anywhere spends a
# token from one process-wide budget — `denied` climbing under a fault
# is the storm-damper working, not a bug.  Hedge outcomes and deadline
# 504s complete the picture chaos tests assert on.
RETRY_TOTAL = REGISTRY.counter(
    "weedtpu_retry_total",
    "retry-budget spends by traffic class and outcome (allowed/denied)",
    ("class", "outcome"))
HEDGE_TOTAL = REGISTRY.counter(
    "weedtpu_hedge_total",
    "hedged degraded-read outcomes (fired / hedge_won / primary_rescued)",
    ("outcome",))
DEADLINE_TIMEOUTS = REGISTRY.counter(
    "weedtpu_deadline_timeouts_total",
    "requests aborted with 504 by an expired deadline budget",
    ("server",))
# canary prober (stats/canary.py): synthetic write/read/delete probes
# through each gateway path.  The class label holds the status bucket
# (2xx/5xx) so the SLO engine's availability machinery evaluates probe
# success like any other request family.
CANARY_PROBES = REGISTRY.counter(
    "weedtpu_canary_probes_total",
    "canary probes by gateway path and status class", ("path", "class"))
CANARY_PROBE_SECONDS = REGISTRY.histogram(
    "weedtpu_canary_probe_seconds", "canary probe latency", ("path",))
# per-tenant accounting (stats/heat.py resolves the tenant once per s3
# request: access key, else bucket, else "anonymous").  The request
# counter is the future QoS admission plane's rate input; the byte
# counter conserves with the netflow ledger's data-class totals on the
# gateway that resolved the tenant.
TENANT_REQUESTS = REGISTRY.counter(
    "weedtpu_tenant_requests_total",
    "completed gateway requests by tenant and read/write op",
    ("tenant", "op"))
TENANT_BYTES = REGISTRY.counter(
    "weedtpu_tenant_bytes_total",
    "body bytes moved for a tenant by direction and op",
    ("tenant", "direction", "op"))
# geo-replication observatory (replication/filer_sync.py): each
# SyncDirection pump exports per-direction lag (now minus the
# last-applied event's ts, refreshed by live-stream keepalives so an
# idle healthy pipe reads ~0), backlog depth (source meta-log head
# minus the resume offset), and applied/skipped/errors counters —
# today's unexported Python attributes promoted to the wire.  The
# stalled gauge is computed BY the pump (no progress for
# WEEDTPU_SYNC_STALL_AFTER s while errors or backlog say there is
# work) because the alert engine can't express that conjunction.
REPLICATION_LAG = REGISTRY.gauge(
    "weedtpu_replication_lag_seconds",
    "per-direction replication lag: now minus last applied/confirmed "
    "source event timestamp", ("direction",))
REPLICATION_BACKLOG = REGISTRY.gauge(
    "weedtpu_replication_backlog_events",
    "per-direction replication backlog: source meta-log events newer "
    "than the resume offset", ("direction",))
REPLICATION_STALLED = REGISTRY.gauge(
    "weedtpu_replication_stalled",
    "1 while a sync direction has made no progress for the stall "
    "window despite errors or backlog, else 0", ("direction",))
REPLICATION_APPLIED = REGISTRY.counter(
    "weedtpu_replication_applied_total",
    "meta-log events applied to the remote filer", ("direction",))
REPLICATION_SKIPPED = REGISTRY.counter(
    "weedtpu_replication_skipped_total",
    "meta-log events skipped by signature loop-prevention",
    ("direction",))
REPLICATION_ERRORS = REGISTRY.counter(
    "weedtpu_replication_errors_total",
    "sync pump apply/stream errors", ("direction",))
# divergence auditor (stats/canary.py DivergenceAuditor): rolling
# subtree digests pulled from both filers' /__meta__/digest — 0 means
# byte-identical metadata trees, 1 means the regions have diverged.
# Clean after heal is ROADMAP item 3's convergence proof.
GEO_DIVERGENCE = REGISTRY.gauge(
    "weedtpu_geo_divergence",
    "1 while the two regions' subtree digests differ, 0 when "
    "byte-identical", ("prefix",))
GEO_AUDITS = REGISTRY.counter(
    "weedtpu_geo_audits_total",
    "divergence audit passes by outcome (clean/diverged/error)",
    ("outcome",))
# WAN ledger: bytes that crossed a region boundary, booked by netflow
# alongside weedtpu_net_bytes_total whenever the ambient wan_region is
# set (the sync pump sets it around cross-region calls).  The region
# label names the REMOTE region so each side's sent/recv pairs
# conserve per class, same as the PR 6 ledger.
WAN_BYTES = REGISTRY.counter(
    "weedtpu_wan_bytes_total",
    "body bytes crossing a region boundary by direction, traffic "
    "class, and remote region", ("direction", "class", "region"))
MASTER_ASSIGN_COUNTER = REGISTRY.counter(
    "weedtpu_master_assign_total", "fid assignments", ("collection",))
VOLUME_REQUEST_COUNTER = REGISTRY.counter(
    "weedtpu_volume_request_total", "volume server requests", ("type",))
VOLUME_REQUEST_HISTOGRAM = REGISTRY.histogram(
    "weedtpu_volume_request_seconds", "volume request latency", ("type",))
VOLUME_COUNT_GAUGE = REGISTRY.gauge(
    "weedtpu_volumes", "volumes served", ("collection", "type"))
FILER_REQUEST_COUNTER = REGISTRY.counter(
    "weedtpu_filer_request_total", "filer requests", ("type",))
FILER_REQUEST_HISTOGRAM = REGISTRY.histogram(
    "weedtpu_filer_request_seconds", "filer request latency", ("type",))
EC_ENCODE_BYTES = REGISTRY.counter(
    "weedtpu_ec_encode_bytes_total", "bytes EC-encoded", ("codec",))
# read-path engine: filer chunk-cache counters (mirrored from ChunkCache at
# scrape time), streaming singleflight joins, and the per-stage EC
# degraded-read counters (mirrored from every mounted EcVolume.read_stats)
FILER_CHUNK_CACHE = REGISTRY.gauge(
    "weedtpu_filer_chunk_cache", "filer chunk cache counters "
    "(hits/misses/mem_bytes/tierN_bytes, cumulative where applicable)",
    ("stat",))
FILER_SINGLEFLIGHT_JOINED = REGISTRY.counter(
    "weedtpu_filer_chunk_singleflight_joined_total",
    "concurrent chunk fetches collapsed into an already in-flight one")
# serving plane: the master lookup fan-in the vid cache exists to
# eliminate (tests assert it stays flat at steady state), the shared
# vid-cache counters mirrored at scrape time, and the consistent-hash
# hot tier's event ledger (hit_local / route_out / route_in / seeded /
# fallback — mirrored from each gateway's per-instance stats dict)
MASTER_LOOKUPS = REGISTRY.counter(
    "weedtpu_master_lookup_total", "/dir/lookup requests served by the "
    "master — the fan-in the gateway vid caches absorb")
VID_CACHE = REGISTRY.gauge(
    "weedtpu_vid_cache", "shared vid->location cache counters "
    "(hits/misses/negative_hits/invalidations/entries)", ("stat",))
HOT_TIER_EVENTS = REGISTRY.gauge(
    "weedtpu_hot_tier_events", "cluster hot-tier event counters by kind "
    "(cumulative; mirrored from the filer's hot-tier ledger)", ("event",))
HOT_TIER_RING = REGISTRY.gauge(
    "weedtpu_hot_tier_ring_members", "live filers in the hot-tier "
    "rendezvous ring, as this node sees it")
S3_QOS = REGISTRY.counter(
    "weedtpu_s3_qos_total", "tenant QoS admission verdicts at the s3 "
    "edge", ("outcome",))
EC_DEGRADED_READ = REGISTRY.gauge(
    "weedtpu_ec_degraded_read", "EC degraded-read engine counters "
    "(shards fetched, intervals coalesced, reconstruct batches/intervals, "
    "cache hits)", ("stat",))
# self-healing maintenance plane (maintenance/): read-path CRC verdicts,
# needle-map integrity-repair drops, scrubber progress, and the master's
# repair planner outcomes + health ledger
NEEDLE_CRC_MISMATCH = REGISTRY.counter(
    "weedtpu_needle_crc_mismatch_total",
    "store-volume reads that failed CRC verification")
NEEDLE_MAP_DROPS = REGISTRY.counter(
    "weedtpu_needle_map_integrity_drops_total",
    "needle-map entries discarded by integrity repair / .sdx rebuild",
    ("kind",))
SCRUB_BYTES = REGISTRY.counter(
    "weedtpu_scrub_bytes_total", "bytes verified by the background "
    "scrubber", ("kind",))
SCRUB_CORRUPTIONS = REGISTRY.counter(
    "weedtpu_scrub_corruptions_total",
    "corruptions found by the scrubber", ("kind",))
REPAIR_ACTIONS = REGISTRY.counter(
    "weedtpu_repair_actions_total",
    "automatic repair executions by outcome", ("kind", "outcome"))
REPAIR_BYTES = REGISTRY.counter(
    "weedtpu_repair_bytes_total",
    "repair bytes moved by locality class of the source "
    "(node/rack/dc/remote; reduced-path partials measured, naive "
    "survivor copies estimated)", ("locality",))
VOLUME_HEALTH = REGISTRY.gauge(
    "weedtpu_volume_health", "volumes per health-ledger state (master)",
    ("state",))
# historical telemetry plane (stats/history.py): disk/volume capacity
# inputs set by volume servers on each heartbeat, the master's fill-rate
# forecasts over them, the history store's own bounds, and per-rule
# firing-alert counts
DISK_BYTES = REGISTRY.gauge(
    "weedtpu_disk_bytes",
    "per-data-dir disk capacity by volume server, directory, and kind "
    "(total/used/free)", ("vs", "dir", "kind"))
VOLUME_SIZE = REGISTRY.gauge(
    "weedtpu_volume_size_bytes",
    "size of each locally served volume, per hosting server",
    ("vid", "vs"))
PREDICTED_FULL = REGISTRY.gauge(
    "weedtpu_predicted_full_seconds",
    "seconds until a data dir is predicted to fill (linear fill-rate "
    "regression over /cluster/history; capped ~10y when not filling)",
    ("vs", "dir"))
VOLUME_PREDICTED_FULL = REGISTRY.gauge(
    "weedtpu_volume_predicted_full_seconds",
    "seconds until a growing volume is predicted to hit the size limit "
    "(only volumes actually filling get a series)", ("vid",))
HISTORY_SERIES = REGISTRY.gauge(
    "weedtpu_history_series",
    "series held by the master's history store (bounded by "
    "WEEDTPU_HISTORY_MAX_SERIES)")
HISTORY_EVICTED = REGISTRY.counter(
    "weedtpu_history_evicted_total",
    "series refused or evicted by the history store's cardinality bound")
ALERTS_FIRING = REGISTRY.gauge(
    "weedtpu_alerts_firing", "alert groups currently firing, per rule",
    ("rule",))
# canary latency as direct gauges (stats/canary.py sets them after each
# probe): the dashboard reads per-path p50/p99 trends from history
# without bucket math
CANARY_LATENCY = REGISTRY.gauge(
    "weedtpu_canary_latency_seconds",
    "canary probe latency quantiles over the rolling window",
    ("path", "quantile"))
# performance observatory (stats/pipeline.py): per-stage busy seconds
# whose RATE is stage occupancy (1 busy-second/second == a saturated
# stage) and bytes moved per stage.
PIPELINE_STAGE_SECONDS = REGISTRY.counter(
    "weedtpu_pipeline_stage_seconds_total",
    "busy seconds per data-plane pipeline stage (rate == occupancy)",
    ("kind", "stage"))
PIPELINE_STAGE_BYTES = REGISTRY.counter(
    "weedtpu_pipeline_stage_bytes_total",
    "bytes processed per data-plane pipeline stage", ("kind", "stage"))
# interference observatory + governor (stats/interference.py): the
# foreground-impact index per node and background traffic class, the
# governed rate per background-work target, and the retune event
# counter — all recorded by the master's history store so retune
# decisions are queryable as series after the fact.
INTERFERENCE_INDEX = REGISTRY.gauge(
    "weedtpu_interference_index",
    "fractional foreground read-p99 inflation attributable to a "
    "background traffic class (per node, EWMA over aggregator ticks; "
    "0 = no measurable impact, 1.0 = p99 doubled)",
    ("node", "class"))
GOVERNOR_RATE = REGISTRY.gauge(
    "weedtpu_governor_rate",
    "current governed rate per background-work target (repair_xrack "
    "bytes/s, convert volumes/s, scrub MB/s)", ("target",))
GOVERNOR_RETUNES = REGISTRY.counter(
    "weedtpu_governor_retunes_total",
    "governor rate-retune decisions by target and direction (up/down)",
    ("target", "direction"))
# fleet-conversion scheduler (maintenance/convert.py): volumes put BACK
# on the queue after a node call failed or skipped them — previously
# only visible in logs, and the autopilot must see the parked backlog
# to avoid re-planning volumes already waiting there
CONVERT_REQUEUED = REGISTRY.counter(
    "weedtpu_convert_requeued_total",
    "fleet-conversion volumes re-queued (never dropped) by reason "
    "(node_error: the node call failed; skipped: the node answered "
    "but left the volume unconverted)", ("reason",))
# autopilot decision plane (maintenance/autopilot.py): plans created
# per policy and executions per policy/outcome, plus the volume-server
# side of the balancing actuator
AUTOPILOT_PLANS = REGISTRY.counter(
    "weedtpu_autopilot_plans_total",
    "autopilot action plans created, by policy "
    "(tiering_demote / tiering_promote / balance_move)", ("policy",))
AUTOPILOT_ACTIONS = REGISTRY.counter(
    "weedtpu_autopilot_actions_total",
    "autopilot plan executions by policy and outcome (done/aborted)",
    ("policy", "outcome"))
VOLUME_MOVES = REGISTRY.counter(
    "weedtpu_volume_moves_total",
    "volume rebalance moves driven through /admin/volume/move on this "
    "server, by outcome (ok/aborted)", ("outcome",))
# registry self-cost: stamped on every render (see Registry.render) so
# the dashboard — itself fed from these series — can watch what the
# telemetry plane costs
METRIC_SERIES = REGISTRY.gauge(
    "weedtpu_metric_series",
    "label sets live across all metric families in this registry")
REGISTRY._series_gauge = METRIC_SERIES
# control-plane observatory (stats/loops.py): every master background
# loop (aggregator, history record, alerts, forecast, interference,
# governor, repair, convert, autopilot, canary, expire) reports each
# tick through a shared LoopMonitor.  The loop label is a closed set of
# master loop names, so cardinality is bounded by construction.  The
# overrun ratio (tick wall seconds / loop interval) is the alertable
# signal: a loop whose ratio crosses 1 can no longer keep its cadence,
# which is how control planes die at fleet scale — see the default
# loop_overrun alert rule.
LOOP_TICK_SECONDS = REGISTRY.histogram(
    "weedtpu_loop_tick_seconds",
    "wall-clock seconds per master background-loop tick", ("loop",))
LOOP_CPU_SECONDS = REGISTRY.counter(
    "weedtpu_loop_cpu_seconds_total",
    "thread CPU seconds consumed by each master background loop "
    "(thread_time delta around the tick; awaits that migrate work to "
    "other threads are attributed to those threads' loops)", ("loop",))
LOOP_ITEMS = REGISTRY.counter(
    "weedtpu_loop_items_total",
    "items processed per master background loop (nodes scraped, plans "
    "made, actions launched, probes fired)", ("loop",))
LOOP_OVERRUNS = REGISTRY.counter(
    "weedtpu_loop_overruns_total",
    "ticks whose wall time exceeded the loop's own interval", ("loop",))
LOOP_ERRORS = REGISTRY.counter(
    "weedtpu_loop_errors_total",
    "ticks that raised; the exception is swallowed by the loop's own "
    "guard but recorded here and in /cluster/loops last_error", ("loop",))
LOOP_BACKLOG = REGISTRY.gauge(
    "weedtpu_loop_backlog",
    "queue/backlog depth behind each master background loop (convert "
    "queue, repair queue, ...; 0 for loops without a queue)", ("loop",))
LOOP_OVERRUN_RATIO = REGISTRY.gauge(
    "weedtpu_loop_overrun_ratio",
    "last tick wall seconds / loop interval (>1 = the loop can no "
    "longer hold its cadence; 0 when the loop has no fixed interval)",
    ("loop",))
# master self-accounting (stats/loops.py cardinality providers): live
# entry counts per stateful master subsystem, so memory growth is a
# first-class queryable signal rather than an RSS surprise
SUBSYSTEM_ENTRIES = REGISTRY.gauge(
    "weedtpu_subsystem_entries",
    "live entries per stateful master subsystem (registry series, "
    "history series + counter baselines, alert-engine state groups, "
    "interference node states, heat tracker entries, pinned traces)",
    ("subsystem",))
