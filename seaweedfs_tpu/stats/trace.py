"""Request tracing: context-propagated spans in a per-process ring buffer.

The concurrent data paths (PRs 1-2) span filer -> volume server -> peer
shard fetch -> batched reconstruct; aggregate counters can't show WHERE
one slow degraded read spent its time.  This module is the whole tracing
runtime:

- a `Trace` (128-bit trace id, current span id, sampled flag) carried in a
  contextvar, so it follows the request across `await`s and into
  `asyncio.to_thread` workers (both copy the context);
- cross-process propagation via the `X-Weedtpu-Trace` header
  (`<trace_id>-<span_id>-<flags>`, flags bit 0 = sampled) — injected by
  utils/http.py for the pooled blocking client and by the aiohttp client
  trace-config, extracted by the aiohttp server middleware below;
- `span(name, **attrs)` context managers recording finished spans into a
  bounded ring buffer.  Appends are lock-free (one itertools.count next()
  + a slot store, both atomic under the GIL) and an UNSAMPLED request
  allocates nothing: span() returns a shared no-op singleton.

Sampling (`WEEDTPU_TRACE_SAMPLE`, default 16 = keep 1/16): every Nth root
request is fully traced; 0 disables local sampling entirely.  Unsampled
requests still get a retroactive root span when they finish slow
(> `WEEDTPU_SLOW_MS`) or errored (status >= 500) — the "keep slow +
errored" default — plus a slow-request log line.  An incoming sampled
header always wins over the local rate, so one trace id survives every
hop of a cross-server request no matter how each server samples.

Introspection, mounted on every server via `debug_routes()`:
  /debug/traces    recent traces as JSON, ?min_ms= filters, ?limit=
  /debug/requests  in-flight requests with age — finds the hung peer
"""

from __future__ import annotations

import itertools
import os
import random
import time
from collections import OrderedDict
from contextvars import ContextVar

from seaweedfs_tpu.stats import heat, netflow
from seaweedfs_tpu.utils import resilience, weedlog

TRACE_HEADER = "X-Weedtpu-Trace"

_rand = random.Random()


class Trace:
    """Immutable trace context: who we are inside which trace."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled


_current: ContextVar[Trace | None] = ContextVar("weedtpu_trace",
                                                default=None)


def sample_rate() -> int:
    """1-in-N root sampling; 0 disables local sampling (env read per
    request so the bench can flip it between interleaved reps)."""
    try:
        return int(os.environ.get("WEEDTPU_TRACE_SAMPLE", "16"))
    except ValueError:
        return 16


def slow_ms() -> float:
    try:
        return float(os.environ.get("WEEDTPU_SLOW_MS", "1000"))
    except ValueError:
        return 1000.0


def _new_trace_id() -> str:
    return f"{_rand.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_rand.getrandbits(64):016x}"


def current() -> Trace | None:
    return _current.get()


def new_root(sampled: bool = True) -> Trace:
    """Fresh root context for work that starts outside any request —
    background maintenance (scrub passes, repair executions) parents its
    spans here so a whole repair shows up as one trace in /debug/traces."""
    return Trace(_new_trace_id(), _new_span_id(), sampled)


def current_exemplar() -> str | None:
    """Trace id for histogram exemplars — only sampled traces qualify."""
    t = _current.get()
    return t.trace_id if t is not None and t.sampled else None


def format_header(t: Trace) -> str:
    return f"{t.trace_id}-{t.span_id}-{1 if t.sampled else 0}"


def parse_header(value: str) -> Trace | None:
    parts = value.split("-")
    if len(parts) != 3 or len(parts[0]) != 32 or len(parts[1]) != 16:
        return None
    try:
        int(parts[0], 16), int(parts[1], 16)
    except ValueError:
        return None
    return Trace(parts[0], parts[1], parts[2] == "1")


def inject(headers: dict) -> dict:
    """Stamp the current trace context into an outgoing header dict
    (the blocking-client injection point; aiohttp clients go through
    aiohttp_trace_config below)."""
    t = _current.get()
    if t is not None:
        headers[TRACE_HEADER] = format_header(t)
    return headers


# -- ring buffer --------------------------------------------------------

def _ring_capacity() -> int:
    try:
        return max(64, int(os.environ.get("WEEDTPU_TRACE_BUF", "4096")))
    except ValueError:
        return 4096


class _Ring:
    """Fixed-capacity overwrite-oldest span store.  append() is one
    atomic counter bump plus one list-slot store — no lock, no growth;
    readers snapshot by copying the slot list."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._slots: list[dict | None] = [None] * capacity
        self._n = itertools.count()

    def append(self, rec: dict) -> None:
        self._slots[next(self._n) % self.capacity] = rec

    def snapshot(self) -> list[dict]:
        return [r for r in list(self._slots) if r is not None]

    def clear(self) -> None:
        self._slots = [None] * self.capacity
        self._n = itertools.count()


_ring = _Ring(_ring_capacity())

# pinned traces: span lists that survive ring wrap-around.  The master's
# cross-node assembler pins any trace id it is asked about (an operator
# or the canary prober is LOOKING at it — the worst moment for the ring
# to overwrite the evidence), and record_span mirrors further spans of a
# pinned trace here as they finish.  Bounded FIFO of _PIN_CAP ids.
_PIN_CAP = 64
_PIN_SPAN_CAP = 512  # per-trace: a runaway pinned trace can't hoard
_pinned: "OrderedDict[str, list[dict]]" = OrderedDict()


def pin_trace(trace_id: str) -> None:
    """Retro-keep `trace_id`: copy its spans currently in the ring into
    the pinned store and keep mirroring new ones.  Also forces SAMPLING
    for future requests carrying this trace id, so a pinned id survives
    every hop regardless of each server's local rate."""
    spans = _pinned.get(trace_id)
    if spans is None:
        _pinned[trace_id] = spans = []
        while len(_pinned) > _PIN_CAP:
            _pinned.popitem(last=False)
    seen = {r["span"] for r in spans}
    for rec in _ring.snapshot():
        if rec["trace"] == trace_id and rec["span"] not in seen:
            spans.append(rec)
            seen.add(rec["span"])


def pinned_ids() -> list[str]:
    return list(_pinned)


def ring_snapshot() -> list[dict]:
    return _ring.snapshot()


def reset_ring() -> None:
    _ring.clear()
    _pinned.clear()


# -- spans --------------------------------------------------------------

class _NoopSpan:
    """Shared do-nothing span for sampled-out requests: entering,
    exiting, and set() must cost nothing and allocate nothing."""

    __slots__ = ()
    trace = None  # parity with _Span for callers that propagate headers

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "trace", "parent_id", "attrs", "error",
                 "_t0", "_start", "_token")

    def __init__(self, name: str, parent: Trace, attrs: dict):
        self.name = name
        self.trace = Trace(parent.trace_id, _new_span_id(), True)
        self.parent_id = parent.span_id
        self.attrs = attrs
        self.error = False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        self._token = _current.set(self.trace)
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        _current.reset(self._token)
        record_span(self.name, self.trace.trace_id, self.trace.span_id,
                    self.parent_id, self._start, dur * 1000.0,
                    self.attrs, self.error or exc_type is not None)
        return False


def span(name: str, parent: Trace | None = None, **attrs):
    """Span context manager.  Uses the ambient contextvar trace unless
    `parent` is passed explicitly (worker threads that were handed a
    captured Trace rather than a copied context).  Sampled out -> the
    shared no-op singleton, zero allocation."""
    t = parent if parent is not None else _current.get()
    if t is None or not t.sampled:
        return _NOOP
    return _Span(name, t, attrs)


def record_span(name: str, trace_id: str, span_id: str,
                parent_id: str | None, start: float, ms: float,
                attrs: dict | None = None, error: bool = False) -> None:
    rec = {"name": name, "trace": trace_id, "span": span_id,
           "parent": parent_id, "start": start, "ms": round(ms, 3)}
    if attrs:
        rec["attrs"] = attrs
    if error:
        rec["error"] = True
    _ring.append(rec)
    if _pinned:  # one truthiness test on the hot path
        spans = _pinned.get(trace_id)
        if spans is not None and len(spans) < _PIN_SPAN_CAP:
            spans.append(rec)


def _trace_spans(tid: str) -> list[dict]:
    """Every known span of one trace id: ring + pinned store, deduped by
    span id, start-time ordered."""
    seen: set[str] = set()
    spans: list[dict] = []
    for rec in _ring.snapshot() + _pinned.get(tid, []):
        if rec["trace"] == tid and rec["span"] not in seen:
            seen.add(rec["span"])
            spans.append(rec)
    spans.sort(key=lambda r: r["start"])
    return spans


def traces(min_ms: float = 0.0, limit: int = 50,
           tid: str | None = None) -> list[dict]:
    """Recent traces, newest first: spans grouped by trace id — in
    start-time order inside each trace, the contract the cross-node
    assembler stitches on — trace duration = the span envelope (covers
    cross-server spans recorded by different middlewares into one shared
    ring in tests).  `tid` is an exact lookup: that one trace (pinned
    spans included), or nothing."""
    by_trace: dict[str, list[dict]] = {}
    if tid is not None:
        spans = _trace_spans(tid)
        if spans:
            by_trace[tid] = spans
        min_ms = 0.0
    else:
        for rec in _ring.snapshot():
            by_trace.setdefault(rec["trace"], []).append(rec)
    out = []
    for t_id, spans in by_trace.items():
        spans.sort(key=lambda r: r["start"])
        t0 = spans[0]["start"]
        t1 = max(r["start"] + r["ms"] / 1000.0 for r in spans)
        total = (t1 - t0) * 1000.0
        if total < min_ms:
            continue
        out.append({"trace_id": t_id, "start": t0,
                    "ms": round(total, 3),
                    "error": any(r.get("error") for r in spans),
                    "spans": spans})
    out.sort(key=lambda t: t["start"], reverse=True)
    return out[:max(1, limit)]


def assemble(spans: list[dict]) -> dict:
    """Stitch one trace's spans (possibly collected from several nodes,
    possibly overlapping) into a parent-ordered waterfall.

    Dedupes by span id, orders depth-first with siblings by start time,
    and stamps each span with its tree ``depth``.  For a server-side
    request span whose parent (the client's send span) is present, the
    per-hop network cost is inferred from the two clocks we have:
    ``net_ms`` = client-observed duration minus server-observed duration
    (wire + framing, both directions) and ``send_ms`` = server start
    minus client start (one-way send + clock skew).  Orphan spans (their
    parent fell out of a remote ring) become extra roots and are counted
    in ``orphans``."""
    by_id: dict[str, dict] = {}
    for s in spans:
        by_id.setdefault(s["span"], dict(s))
    children: dict[str, list[dict]] = {}
    roots: list[dict] = []
    orphans = 0
    for s in by_id.values():
        pid = s.get("parent")
        if pid and pid in by_id:
            children.setdefault(pid, []).append(s)
        else:
            if pid:
                orphans += 1
            roots.append(s)
    for lst in children.values():
        lst.sort(key=lambda r: r["start"])
    roots.sort(key=lambda r: r["start"])
    out: list[dict] = []

    def emit(s: dict, depth: int) -> None:
        s["depth"] = depth
        parent = by_id.get(s.get("parent") or "")
        if parent is not None and s["name"].endswith(".request"):
            # a cross-process hop: the gap between what the caller saw
            # and what the server measured is the network's share
            s["net_ms"] = round(max(0.0, parent["ms"] - s["ms"]), 3)
            s["send_ms"] = round((s["start"] - parent["start"]) * 1000.0, 3)
        out.append(s)
        for c in children.get(s["span"], []):
            emit(c, depth + 1)

    for r in roots:
        emit(r, 0)
    if not out:
        return {"spans": [], "span_count": 0, "servers": [], "nodes": [],
                "regions": []}
    t0 = min(s["start"] for s in out)
    t1 = max(s["start"] + s["ms"] / 1000.0 for s in out)
    servers = sorted({s.get("attrs", {}).get("server") for s in out
                      if s.get("attrs", {}).get("server")})
    nodes = sorted({s["node"] for s in out if s.get("node")})
    regions = sorted({s.get("attrs", {}).get("region") for s in out
                      if s.get("attrs", {}).get("region")})
    return {"trace_id": out[0]["trace"], "start": t0,
            "ms": round((t1 - t0) * 1000.0, 3),
            "error": any(s.get("error") for s in out),
            "span_count": len(out), "servers": servers, "nodes": nodes,
            "regions": regions, "orphans": orphans, "spans": out}


# -- in-flight request registry -----------------------------------------

_inflight: dict[int, dict] = {}
_inflight_seq = itertools.count(1)


def request_started(method: str, path: str, remote: str | None,
                    trace_id: str | None) -> int:
    rid = next(_inflight_seq)
    _inflight[rid] = {"id": rid, "method": method, "path": path,
                      "remote": remote or "", "trace_id": trace_id or "",
                      "start": time.time(), "_t0": time.perf_counter()}
    return rid


def request_finished(rid: int) -> None:
    _inflight.pop(rid, None)


def inflight() -> list[dict]:
    now = time.perf_counter()
    out = []
    for rec in list(_inflight.values()):
        r = {k: v for k, v in rec.items() if not k.startswith("_")}
        r["age_ms"] = round((now - rec["_t0"]) * 1000.0, 1)
        out.append(r)
    out.sort(key=lambda r: r["age_ms"], reverse=True)
    return out


# -- aiohttp server glue ------------------------------------------------

def _request_op(method: str, path: str) -> str:
    # cluster-internal surfaces get op="internal" in the request counter
    # so the SLO availability rules (op=read/write) measure the DATA
    # plane — on a lightly-loaded cluster the self-generated
    # heartbeat/scrape volume would otherwise dominate the denominator
    # and mask real client failures.  The prefix list (exact-or-slash
    # matched) lives in netflow so the byte ledger's default class and
    # this op classification can never disagree.
    if netflow.is_internal(path):
        return "internal"
    return "read" if method in ("GET", "HEAD") else "write"


def aiohttp_middleware(role: str, slow_exempt: tuple = (),
                       trust_flow: bool = True, tenant_resolver=None,
                       region: str = ""):
    """Server-side half of the propagation: extract X-Weedtpu-Trace (or
    make a root sampling decision), register the request in the in-flight
    table, and on completion record the root span — always for sampled
    requests, retroactively for unsampled ones that finished slow or
    errored (with a slow-request log line either way).  `slow_exempt`
    lists long-poll paths (meta subscribe and friends) whose lifetime IS
    their duration — they'd otherwise bury real outliers in the ring.
    Client disconnects (CancelledError) are neither slow nor errored.

    `trust_flow` controls whether incoming X-Weedtpu-Class/-Role headers
    are honored: an external client could otherwise declare itself
    `internal` to drop its failures out of the data-plane availability
    SLO, or `repair` to poison the byte ledger's repair-traffic
    measurement.  The public s3 gateway passes "loopback" (trust only
    same-host callers — the all-in-one master's canary — never remote
    clients).  Cluster-internal servers keep the default True: that
    propagation is how a repair's shard pulls book as repair two hops
    away, and a caller who can reach those servers directly is already
    inside the cluster's trusted-network boundary (the same posture as
    the open /admin surface).

    `tenant_resolver` marks this server as a TENANT EDGE (the s3
    gateway): the callable resolves the request's tenant identity once
    (stats/heat.resolve_tenant — access key, else bucket, else
    anonymous), the resolved tenant rides the request contextvar (so
    downstream hops and future QoS admission read one field), and the
    per-tenant request/byte counters + the tenant heat dimension are
    accounted HERE and only here — inner servers inherit the tenant via
    X-Weedtpu-Tenant (same trust rule as the flow headers: the public
    gateway only honors it from loopback) without double-counting the
    same logical request fleet-wide."""
    import asyncio
    from aiohttp import web

    counter = itertools.count(1)

    @web.middleware
    async def middleware(req: web.Request, handler):
        hdr = req.headers.get(TRACE_HEADER)
        t_in = parse_header(hdr) if hdr else None
        rate = sample_rate()
        parent_id = None
        if t_in is not None:
            # continue the caller's trace under a fresh span id — the
            # header's span id is the CALLER's current span, our parent.
            # A pinned trace id samples regardless of the header bit:
            # someone is actively looking at that trace.
            parent_id = t_in.span_id
            sampled = t_in.sampled or (bool(_pinned)
                                       and t_in.trace_id in _pinned)
            t = Trace(t_in.trace_id, _new_span_id(), sampled)
        elif rate > 0 and next(counter) % rate == 0:
            t = Trace(_new_trace_id(), _new_span_id(), True)
        else:
            t = None
        token = _current.set(t) if t is not None else None
        # byte-flow ledger: the caller's declared traffic class (or the
        # path default) becomes ambient for the handler, so requests the
        # handler makes downstream inherit it across the next hop
        trusted = trust_flow is True or \
            (trust_flow == "loopback" and req.remote in ("127.0.0.1",
                                                         "::1"))
        if trusted:
            flow_cls = netflow.extract_class(req.headers, req.path)
            flow_peer = req.headers.get(netflow.ROLE_HEADER, "client")
        else:
            flow_cls = netflow.classify(req.path)
            flow_peer = "client"
        # a declared-internal request (canary probes, cluster plumbing
        # hitting data-plane paths) must not inflate the data-plane
        # availability denominators — the same dilution the path-based
        # op=internal classification exists to prevent
        op = "internal" if flow_cls == "internal" \
            else _request_op(req.method, req.path)
        # tenant identity: a trusted header wins (an inner hop inheriting
        # the edge's resolution, or the same-host canary declaring one);
        # otherwise the tenant edge resolves it from the request itself
        tenant = None
        hdr_tenant = req.headers.get(heat.TENANT_HEADER)
        if hdr_tenant and trusted:
            # same bound resolve_tenant enforces: the value becomes a
            # metric label and a sketch key, and the header is
            # caller-sized
            tenant = hdr_tenant[:64]
        elif tenant_resolver is not None:
            try:
                tenant = tenant_resolver(req)
            except Exception:
                tenant = "anonymous"
        tenant_token = heat.set_tenant(tenant) if tenant else None
        flow_token = netflow.set_class(flow_cls)
        # deadline budget (utils/resilience.py): honor an incoming
        # X-Weedtpu-Deadline always; apply the WEEDTPU_DEADLINE_MS edge
        # default only to data-plane requests (internal plumbing and
        # long-polls manage their own lifetimes).  The handler is
        # aborted at expiry with a fast 504 — the "slow shard fetch
        # can't eat the whole request" contract — and the root span is
        # tagged op=timeout so the waterfall names the hop that died.
        deadline_s = resilience.extract_deadline_s(req.headers)
        if deadline_s is None and op != "internal" \
                and req.path not in slow_exempt:
            edge = resilience.default_deadline_ms()
            if edge > 0:
                deadline_s = edge / 1000.0
        dl_token = resilience.set_deadline(
            time.monotonic() + deadline_s) if deadline_s is not None \
            else None
        rid = request_started(req.method, req.path_qs, req.remote,
                              t.trace_id if t is not None else None)
        start = time.time()
        t0 = time.perf_counter()
        status = 500
        cancelled = False
        timed_out = False
        resp_obj = None
        try:
            if dl_token is not None:
                try:
                    resp = await asyncio.wait_for(handler(req),
                                                  timeout=deadline_s)
                except (asyncio.TimeoutError,
                        resilience.DeadlineExceeded) as te:
                    # only OUR budget expiring is a deadline 504: a
                    # timeout escaping the handler with budget still on
                    # the clock (an upstream session timeout, a futures
                    # timeout) is that code path's own failure and must
                    # surface as such, not masquerade as budget expiry
                    rem = resilience.remaining()
                    if not isinstance(te, resilience.DeadlineExceeded) \
                            and rem is not None and rem > 0.01:
                        raise
                    timed_out = True
                    from seaweedfs_tpu.stats import metrics as _metrics
                    _metrics.DEADLINE_TIMEOUTS.labels(role).inc()
                    if req.get(netflow.PREPARED_KEY):
                        # a StreamResponse already put headers on the
                        # wire: a fresh 504 can't be delivered — tear
                        # the connection down so the client fails NOW
                        # instead of waiting out the stream
                        if req.transport is not None:
                            req.transport.close()
                        raise ConnectionResetError(
                            "deadline exceeded mid-stream") from None
                    resp = web.json_response(
                        {"error": "deadline exceeded",
                         "budget_ms": round(deadline_s * 1000.0, 1)},
                        status=504)
            else:
                resp = await handler(req)
            status = resp.status
            resp_obj = resp
            return resp
        except web.HTTPException as e:
            status = e.status
            resp_obj = e  # an HTTPException IS a Response (has a body)
            raise
        except (asyncio.CancelledError, ConnectionResetError,
                BrokenPipeError):
            # the client hung up (cancelled handler, or resp.write onto
            # a closed transport): a fact about the caller, not a server
            # error — trace it if sampled, never retro-keep or slow-log.
            # EXCEPT the mid-stream deadline teardown we raised
            # ourselves just above: that one is the SERVER failing the
            # request and must count as a 5xx in the availability SLO
            # exactly like the pre-headers 504 does
            if timed_out:
                status = 504
            else:
                cancelled = True
            raise
        finally:
            ms = (time.perf_counter() - t0) * 1000.0
            request_finished(rid)
            if token is not None:
                _current.reset(token)
            if dl_token is not None:
                resilience.reset_deadline(dl_token)
            netflow.reset(flow_token)
            if tenant_token is not None:
                heat.reset_tenant(tenant_token)
            # chunked uploads have no Content-Length; the payload
            # StreamReader's total_bytes knows what actually arrived
            recv = req.content_length if req.content_length is not None \
                else getattr(req.content, "total_bytes", 0)
            sent = netflow.response_bytes(resp_obj)
            netflow.account("recv", flow_cls, flow_peer, recv or 0)
            netflow.account("sent", flow_cls, flow_peer, sent)
            if tenant and tenant_resolver is not None \
                    and op != "internal":
                # per-tenant accounting at the resolving edge only: the
                # byte counter mirrors the netflow booking above (same
                # values, same spot) so tenant totals conserve with the
                # data-class ledger on this gateway.  The COUNTERS are
                # gated on success: the tenant identity is syntactic
                # (pre-auth), and booking 4xx requests would let an
                # unauthenticated client mint label children from
                # random access keys until every real tenant collapses
                # into __other__ — rejected load still shows in the
                # bounded, decaying heat sketch below.
                if status < 400 and not cancelled:
                    from seaweedfs_tpu.stats import metrics as _metrics
                    _metrics.TENANT_REQUESTS.labels(tenant, op).inc()
                    if recv:
                        _metrics.TENANT_BYTES.labels(
                            tenant, "recv", op).inc(recv)
                    if sent:
                        _metrics.TENANT_BYTES.labels(
                            tenant, "sent", op).inc(sent)
                heat.record("tenant", tenant, (recv or 0) + sent,
                            "write" if op == "write" else "read")
            if not cancelled:
                # per-class request counters: the SLO engine's
                # availability input (a disconnect is the caller's fact,
                # not an availability event). Lazy import: metrics
                # imports this module at its own top level.
                from seaweedfs_tpu.stats import metrics as _metrics
                _metrics.HTTP_REQUESTS.labels(
                    role, op, f"{status // 100}xx").inc()
            slow = ms >= slow_ms() and not cancelled and \
                req.path not in slow_exempt
            errored = status >= 500 and not cancelled
            if t is not None and t.sampled:
                attrs = {"method": req.method, "path": req.path,
                         "status": status, "server": role}
                if region:
                    # geo federation: the waterfall shows which side of
                    # the WAN each hop ran on
                    attrs["region"] = region
                if cancelled:
                    attrs["cancelled"] = True
                if timed_out:
                    # the waterfall's "this hop ran out of budget" mark
                    attrs["op"] = "timeout"
                    attrs["budget_ms"] = round(deadline_s * 1000.0, 1)
                record_span(f"{role}.request", t.trace_id, t.span_id,
                            parent_id, start, ms, attrs, errored)
            elif rate > 0 and (slow or errored):
                # keep slow + errored even when sampled out: a root span
                # appears retroactively (children were skipped, but the
                # trace id in the log line finds it in /debug/traces)
                retro = t or Trace(_new_trace_id(), _new_span_id(), True)
                retro_attrs = {"method": req.method, "path": req.path,
                               "status": status, "server": role,
                               "retro": True}
                if region:
                    retro_attrs["region"] = region
                if timed_out:
                    retro_attrs["op"] = "timeout"
                record_span(f"{role}.request", retro.trace_id,
                            retro.span_id, None, start, ms,
                            retro_attrs, errored)
                t = retro
            if slow and rate > 0:
                weedlog.info(
                    "slow request: %s %s %s took %.1fms (status %d) "
                    "trace=%s", role, req.method, req.path_qs, ms,
                    status, t.trace_id if t is not None else "-",
                    name="trace")

    return middleware


async def handle_debug_traces(req):
    from aiohttp import web
    try:
        min_ms = float(req.query.get("min_ms", "0"))
    except ValueError:
        min_ms = 0.0
    try:
        limit = int(req.query.get("limit", "50"))
    except ValueError:
        limit = 50
    tid = req.query.get("tid") or None
    if tid is not None and req.query.get("pin"):
        # the master's cross-node assembler asks with pin=1: keep this
        # trace's spans alive past ring wrap while it is being examined
        pin_trace(tid)
    return web.json_response({"sample_rate": sample_rate(),
                              "traces": traces(min_ms, limit, tid=tid)})


async def handle_debug_requests(req):
    from aiohttp import web
    return web.json_response({"requests": inflight()})


def loopback_error(req):
    """None when the request originates on loopback; a 403 JSON response
    otherwise.  The ONE copy of the operator-surface gate — /debug/* on
    every server and the volume server's fault/scrub admin hooks all
    route through here."""
    from aiohttp import web
    if req.remote not in ("127.0.0.1", "::1"):
        return web.json_response({"error": "forbidden"}, status=403)
    return None


def debug_guard(handler):
    """Wrap a debug handler in the shared loopback gate: the debug
    surface (traces, in-flight requests, profiles) must not leak request
    paths, presigned-URL query strings, or stack contents to remote
    callers on ANY server."""
    async def guarded(req):
        err = loopback_error(req)
        if err is not None:
            return err
        return await handler(req)
    return guarded


def debug_routes():
    """Routes every server mounts (before any catch-all), loopback-gated
    as one unit: /debug/traces, /debug/requests, /debug/pprof,
    /debug/jax_profile, /debug/pipeline."""
    from aiohttp import web

    from seaweedfs_tpu.stats import pipeline as _pipeline
    from seaweedfs_tpu.stats import profile as _profile
    return [web.get("/debug/traces", debug_guard(handle_debug_traces)),
            web.get("/debug/requests", debug_guard(handle_debug_requests)),
            web.get("/debug/pprof",
                    debug_guard(_profile.handle_debug_pprof)),
            web.get("/debug/jax_profile",
                    debug_guard(_profile.handle_debug_jax_profile)),
            web.get("/debug/pipeline",
                    debug_guard(_pipeline.handle_debug_pipeline))]
