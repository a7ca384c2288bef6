"""Observability stack tests: trace spans + ring buffer + header
propagation (one trace id across filer -> volume -> peer shard fetch),
/debug introspection, promtool-style exposition lint, push-gateway
retry/backoff, histogram exemplars, and weedlog -vmodule parity."""

import asyncio
import json
import logging
import re
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu.stats import metrics, trace
from seaweedfs_tpu.utils import weedlog


# ---- trace core --------------------------------------------------------

def test_header_roundtrip_and_malformed():
    t = trace.Trace(trace._new_trace_id(), trace._new_span_id(), True)
    t2 = trace.parse_header(trace.format_header(t))
    assert (t2.trace_id, t2.span_id, t2.sampled) == \
        (t.trace_id, t.span_id, True)
    off = trace.Trace(t.trace_id, t.span_id, False)
    assert not trace.parse_header(trace.format_header(off)).sampled
    for bad in ("", "x", "abc-def-1", "-".join(["z" * 32, "0" * 16, "1"]),
                "0" * 32 + "-" + "0" * 16):
        assert trace.parse_header(bad) is None, bad


def test_span_without_context_is_noop_and_writes_nothing():
    trace.reset_ring()
    with trace.span("nope", a=1) as sp:
        sp.set(b=2)
    assert trace.ring_snapshot() == []
    # the sampled-out singleton is shared: zero allocation per request
    assert trace.span("x") is trace.span("y")


def test_span_nesting_records_parentage_and_attrs():
    trace.reset_ring()
    t = trace.Trace(trace._new_trace_id(), trace._new_span_id(), True)
    tok = trace._current.set(t)
    try:
        with trace.span("outer", stage="a") as sp:
            sp.set(extra=1)
            with trace.span("inner"):
                pass
    finally:
        trace._current.reset(tok)
    recs = {r["name"]: r for r in trace.ring_snapshot()}
    assert set(recs) == {"outer", "inner"}
    assert recs["inner"]["parent"] == recs["outer"]["span"]
    assert recs["outer"]["parent"] == t.span_id
    assert recs["outer"]["attrs"] == {"stage": "a", "extra": 1}
    ts = trace.traces()
    assert len(ts) == 1 and ts[0]["trace_id"] == t.trace_id
    assert len(ts[0]["spans"]) == 2


def test_ring_overwrites_oldest():
    ring = trace._Ring(4)
    for i in range(10):
        ring.append({"i": i})
    got = sorted(r["i"] for r in ring.snapshot())
    assert got == [6, 7, 8, 9]


def test_inflight_registry_shows_and_clears():
    rid = trace.request_started("GET", "/x?y=1", "1.2.3.4", "t" * 32)
    try:
        entries = [r for r in trace.inflight() if r["id"] == rid]
        assert len(entries) == 1
        assert entries[0]["path"] == "/x?y=1"
        assert entries[0]["age_ms"] >= 0
    finally:
        trace.request_finished(rid)
    assert not [r for r in trace.inflight() if r["id"] == rid]


# ---- exposition lint (promtool-style) ---------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (-?[0-9.e+-]+|NaN)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _lint_exposition(text: str) -> None:
    """Minimal promtool check-metrics: HELP/TYPE precede a metric's
    samples, label syntax/escaping parses, `le` is strictly increasing
    and ends at +Inf, cumulative buckets are monotone, and
    _bucket/_sum/_count agree."""
    assert text.endswith("\n"), "exposition must end with a newline"
    typed: dict[str, str] = {}
    helped: set = set()
    seen_samples: set = set()
    hist: dict = {}  # (name, labels-sans-le) -> [(le, cum)]
    counts: dict = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in helped, f"duplicate HELP {name}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name not in typed, f"duplicate TYPE {name}"
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), kind
            assert name not in seen_samples, \
                f"TYPE {name} after its samples"
            typed[name] = kind
            continue
        assert not line.startswith("#"), f"unexpected comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, _, labels_raw, value = m.groups()
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and \
                    name[:-len(suffix)] in typed and \
                    typed[name[:-len(suffix)]] == "histogram":
                base = name[:-len(suffix)]
        assert base in typed, f"sample {name} without TYPE"
        seen_samples.add(base)
        labels = _LABEL_RE.findall(labels_raw or "")
        consumed = re.sub(_LABEL_RE, "", labels_raw or "")
        assert not consumed.strip(" ,"), \
            f"bad label syntax in {line!r}"
        if typed[base] == "histogram":
            key = (base, tuple(sorted(
                (k, v) for k, v in labels if k != "le")))
            if name.endswith("_bucket"):
                le = dict(labels)["le"]
                le_f = float("inf") if le == "+Inf" else float(le)
                hist.setdefault(key, []).append((le_f, float(value)))
            elif name.endswith("_count"):
                counts[key] = float(value)
    for key, buckets in hist.items():
        les = [le for le, _ in buckets]
        assert les == sorted(les) and len(set(les)) == len(les), \
            f"le not strictly increasing for {key}"
        assert les[-1] == float("inf"), f"missing +Inf bucket for {key}"
        cums = [c for _, c in buckets]
        assert cums == sorted(cums), f"buckets not cumulative for {key}"
        assert key in counts, f"missing _count for {key}"
        assert counts[key] == cums[-1], \
            f"_count != +Inf bucket for {key}"


def test_global_registry_exposition_lints():
    # exercise the standard metrics, including awkward label values
    metrics.MASTER_ASSIGN_COUNTER.labels('col"w\\eird\n').inc()
    metrics.VOLUME_REQUEST_COUNTER.labels("read").inc()
    metrics.VOLUME_REQUEST_HISTOGRAM.labels("read").observe(0.004)
    metrics.VOLUME_REQUEST_HISTOGRAM.labels("read").observe(7.0)
    metrics.VOLUME_REQUEST_HISTOGRAM.labels("read").observe(100.0)
    metrics.FILER_CHUNK_CACHE.labels("hits").set(3)
    _lint_exposition(metrics.REGISTRY.render())


def test_registry_wide_metric_conventions():
    """Registry-wide lint (tier-1): every series in the global registry —
    today's AND every future one — follows the naming convention:
    `weedtpu_`-prefixed lowercase snake_case, counters `_total`-suffixed
    (the OpenMetrics rendering depends on it), histograms unit-suffixed,
    non-counters never faking the counter suffix, and non-empty help
    text on everything.  Importing the modules that register metrics
    lazily makes the sweep cover them."""
    import seaweedfs_tpu.stats.canary  # noqa: F401 — registers counters
    import seaweedfs_tpu.stats.heat  # noqa: F401
    import seaweedfs_tpu.stats.netflow  # noqa: F401
    with metrics.REGISTRY._lock:
        families = dict(metrics.REGISTRY._metrics)
    assert families, "global registry is empty?"
    for name, m in families.items():
        assert re.fullmatch(r"weedtpu_[a-z0-9_]+", name), \
            f"{name}: not weedtpu_-prefixed lowercase snake_case"
        assert m.help and m.help.strip(), f"{name}: missing help text"
        assert m.kind in ("counter", "gauge", "histogram"), \
            f"{name}: unknown kind {m.kind}"
        if m.kind == "counter":
            assert name.endswith("_total"), \
                f"{name}: counters must be _total-suffixed"
        else:
            assert not name.endswith("_total"), \
                f"{name}: _total suffix is reserved for counters"
        if m.kind == "histogram":
            assert name.endswith(("_seconds", "_bytes")), \
                f"{name}: histograms carry a unit suffix"
        assert len(m.label_names) == len(set(m.label_names)), \
            f"{name}: duplicate label names"
        for label in m.label_names:
            assert re.fullmatch(r"[a-z][a-z0-9_]*", label), \
                f"{name}: bad label name {label!r}"
        # label-cardinality bound: a family drifting toward the
        # MAX_CHILDREN collapse is leaking label values (fids, paths,
        # tenant ids); catch it at half the hard cap, while __other__
        # folding has not yet corrupted the data
        bound = m.MAX_CHILDREN // 2
        assert len(m._children) <= bound, \
            f"{name}: {len(m._children)} label sets exceed the " \
            f"cardinality bound {bound}"


def test_metric_series_self_gauge_tracks_registry_cost():
    """Rendering the global registry stamps weedtpu_metric_series with
    its own live series count, so the dashboard (fed from these very
    series) can watch what the telemetry plane costs."""
    text = metrics.REGISTRY.render()
    m = re.search(r"^weedtpu_metric_series (\d+)", text, re.M)
    assert m, "self-gauge missing from exposition"
    count = int(m.group(1))
    assert count > 0
    # matches reality at render time (rendering itself may add a child)
    assert abs(count - metrics.REGISTRY.series_count()) <= 2
    # registering a new label set moves the next render
    metrics.MASTER_ASSIGN_COUNTER.labels("self-gauge-probe").inc()
    text2 = metrics.REGISTRY.render()
    m2 = re.search(r"^weedtpu_metric_series (\d+)", text2, re.M)
    assert int(m2.group(1)) >= count


def test_cardinality_collapses_to_other():
    reg = metrics.Registry()
    c = reg.counter("weedtpu_test_cardinality_total", "t", ("who",))
    for i in range(c.MAX_CHILDREN):
        c.labels(f"v{i}").inc()
    overflow_a = c.labels("straggler-a")
    overflow_b = c.labels("straggler-b")
    assert overflow_a is overflow_b, "overflow must share one child"
    overflow_a.inc()
    text = reg.render()
    assert '__other__' in text
    _lint_exposition(text)


def test_remove_matching_retires_instance_series():
    """A stopped server retires its own gauge children (volume_server
    stop() drops its disk/volume capacity series) without touching other
    instances' series — the registry-wide cardinality bound depends on
    restarts not accumulating stale label sets."""
    reg = metrics.Registry()
    g = reg.gauge("weedtpu_test_capacity_bytes", "t", ("vs", "dir", "kind"))
    for vs in ("127.0.0.1:1", "127.0.0.1:2"):
        for kind in ("total", "used", "free"):
            g.labels(vs, "/data", kind).set(1.0)
    assert g.remove_matching(vs="127.0.0.1:1") == 3
    remaining = {pairs for pairs, _ in g._pairs()}
    assert len(remaining) == 3
    assert all(dict(p)["vs"] == "127.0.0.1:2" for p in remaining)
    assert g.remove_matching(vs="127.0.0.1:1") == 0, "idempotent"


def test_openmetrics_counters_get_total_suffix():
    """A negotiating Prometheus parses OpenMetrics strictly: counter
    samples must end in _total with the family named without it."""
    reg = metrics.Registry()
    reg.counter("weedtpu_beats", "no suffix").labels().inc()
    reg.counter("weedtpu_assign_total", "has suffix").labels().inc(2)
    om = reg.render(openmetrics=True)
    assert "# TYPE weedtpu_beats counter" in om
    assert "weedtpu_beats_total 1" in om
    assert "# TYPE weedtpu_assign counter" in om
    assert "weedtpu_assign_total 2" in om
    assert "weedtpu_assign_total_total" not in om
    # the 0.0.4 rendering is untouched
    plain = reg.render()
    assert "weedtpu_beats 1" in plain and "weedtpu_beats_total" not in plain
    _lint_exposition(plain)


def _mock_req(path, peer):
    from unittest import mock

    from aiohttp.test_utils import make_mocked_request
    tr = mock.Mock()
    tr.get_extra_info = lambda key, default=None: \
        (peer, 1234) if key == "peername" else default
    return make_mocked_request("GET", path, transport=tr)


def test_debug_routes_share_one_loopback_guard():
    """The loopback gate is ONE helper (trace.debug_guard): the s3
    gateway's debug surface uses it verbatim, and it 403s non-loopback
    peers for traces, requests, and pprof alike."""
    from seaweedfs_tpu.s3.s3api_server import S3ApiServer
    from seaweedfs_tpu.stats import profile

    assert S3ApiServer._debug_local is trace.debug_guard

    for handler in (trace.handle_debug_requests, trace.handle_debug_traces,
                    profile.handle_debug_pprof):
        guarded = trace.debug_guard(handler)
        resp = asyncio.run(guarded(
            _mock_req("/debug/requests", "203.0.113.9")))
        assert resp.status == 403, handler
    resp = asyncio.run(trace.debug_guard(trace.handle_debug_requests)(
        _mock_req("/debug/requests", "127.0.0.1")))
    assert resp.status == 200


def test_histogram_exemplars_openmetrics_only():
    reg = metrics.Registry()
    h = reg.histogram("weedtpu_test_seconds", "t")
    t = trace.Trace(trace._new_trace_id(), trace._new_span_id(), True)
    tok = trace._current.set(t)
    try:
        with h.labels().time():
            pass
    finally:
        trace._current.reset(tok)
    plain = reg.render()
    assert "trace_id" not in plain, "exemplars must not leak into 0.0.4"
    _lint_exposition(plain)
    om = reg.render(openmetrics=True)
    assert f'# {{trace_id="{t.trace_id}"}}' in om
    assert om.rstrip().endswith("# EOF")
    # unsampled observations leave no exemplar
    reg2 = metrics.Registry()
    reg2.histogram("weedtpu_test2_seconds", "t").labels().observe(0.001)
    assert "trace_id" not in reg2.render(openmetrics=True)


# ---- push gateway ------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_push_failure_logged_not_raised(caplog):
    reg = metrics.Registry()
    reg.counter("weedtpu_push_test_total", "t").labels().inc()
    weedlog.set_vmodule("metrics=1")
    try:
        with caplog.at_level(logging.DEBUG, logger="metrics"):
            # nothing listens on this port: must return False, not raise
            ok = reg.push(f"http://127.0.0.1:{_free_port()}", "job")
        assert ok is False
        assert "push" in caplog.text
    finally:
        weedlog.set_vmodule("")


def test_push_success_against_local_gateway():
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    got: dict = {}

    class Gateway(BaseHTTPRequestHandler):
        def do_PUT(self):
            got["path"] = self.path
            got["body"] = self.rfile.read(
                int(self.headers.get("Content-Length", "0")))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Gateway)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        reg = metrics.Registry()
        reg.counter("weedtpu_pushed_total", "t").labels().inc()
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        assert reg.push(url, "weedtpu") is True
        assert got["path"] == "/metrics/job/weedtpu"
        assert b"weedtpu_pushed_total" in got["body"]
    finally:
        srv.shutdown()
        srv.server_close()


def test_metrics_pusher_backoff_and_stop():
    reg = metrics.Registry()
    dead = f"http://127.0.0.1:{_free_port()}"
    p = metrics.MetricsPusher(reg, dead, "j", interval=0.02,
                              max_backoff=0.2).start()
    deadline = time.time() + 5
    while time.time() < deadline and p.failures < 2:
        time.sleep(0.02)
    assert p.failures >= 2, "pusher never retried after failure"
    p.stop()
    assert not p._thread.is_alive()
    # backoff grew but stayed capped
    assert p.interval * 2 <= min(p.interval * (2 ** p.failures),
                                 p.max_backoff) <= p.max_backoff


# ---- weedlog -vmodule --------------------------------------------------

def test_vmodule_per_module_verbosity(caplog):
    weedlog.set_vmodule("ec_volume=2,http=1, junk, bad=x")
    try:
        assert weedlog.verbosity("ec_volume") == 2
        assert weedlog.verbosity("http") == 1
        assert weedlog.verbosity("other") == weedlog.verbosity()
        with caplog.at_level(logging.DEBUG):
            weedlog.V(2, "ec_volume").infof("deep %s detail", "engine")
            weedlog.V(2, "http").infof("http v2 MUST NOT appear")
            weedlog.V(1, "http").infof("http v1 detail")
            weedlog.V(1, "other").infof("other v1 MUST NOT appear")
        assert "deep engine detail" in caplog.text
        assert "http v1 detail" in caplog.text
        assert "MUST NOT appear" not in caplog.text
    finally:
        weedlog.set_vmodule("")
    assert weedlog.verbosity("ec_volume") == weedlog.verbosity()


# ---- sampling profiler -------------------------------------------------

def _spin(stop: threading.Event) -> None:
    while not stop.is_set():
        sum(range(500))


def test_profiler_samples_busy_thread_and_stops_clean():
    from seaweedfs_tpu.stats import profile
    stop = threading.Event()
    worker = threading.Thread(target=_spin, args=(stop,), daemon=True)
    worker.start()
    p = profile.SamplingProfiler(hz=400).start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and p.samples < 20:
            time.sleep(0.01)
    finally:
        p.stop()
        stop.set()
        worker.join(2)
    assert p.samples >= 20
    collapsed = p.collapsed()
    assert "_spin" in collapsed, collapsed[:400]
    # collapsed-stack format: "root;...;leaf count" per line
    line = next(l for l in collapsed.splitlines() if "_spin" in l)
    stack, _, count = line.rpartition(" ")
    assert int(count) > 0 and ";" in stack
    table = p.table()
    assert "_spin" in table and "self" in table


def test_profiler_start_stop_leaves_zero_threads(monkeypatch):
    from seaweedfs_tpu.stats import profile

    def profiler_threads():
        return [t for t in threading.enumerate()
                if t.name == "weedtpu-profiler"]

    for _ in range(3):
        p = profile.SamplingProfiler(hz=500).start()
        assert profiler_threads()
        p.stop()
        assert not profiler_threads()
    # the env-driven continuous profiler is idempotent and shuts down
    monkeypatch.setenv("WEEDTPU_PROFILE_HZ", "250")
    p1 = profile.ensure_started()
    p2 = profile.ensure_started()
    assert p1 is p2 and p1.running
    profile.shutdown()
    assert not profiler_threads()
    monkeypatch.setenv("WEEDTPU_PROFILE_HZ", "0")
    assert profile.ensure_started() is None
    assert not profiler_threads()


def test_debug_pprof_on_demand_window_and_formats():
    from seaweedfs_tpu.stats import profile
    profile.shutdown()  # no continuous profiler: seconds=0 must 400
    resp = asyncio.run(profile.handle_debug_pprof(
        _mock_req("/debug/pprof", "127.0.0.1")))
    assert resp.status == 400

    stop = threading.Event()
    worker = threading.Thread(target=_spin, args=(stop,), daemon=True)
    worker.start()
    try:
        resp = asyncio.run(profile.handle_debug_pprof(_mock_req(
            "/debug/pprof?seconds=0.25&hz=400", "127.0.0.1")))
        assert resp.status == 200
        assert "_spin" in resp.text
        resp = asyncio.run(profile.handle_debug_pprof(_mock_req(
            "/debug/pprof?seconds=0.2&hz=400&format=table",
            "127.0.0.1")))
        assert "kernel profile" in resp.text
        resp = asyncio.run(profile.handle_debug_pprof(_mock_req(
            "/debug/pprof?seconds=0.2&hz=400&format=json",
            "127.0.0.1")))
        body = json.loads(resp.text)
        assert body["samples"] > 0 and isinstance(body["stacks"], list)
        assert "kernels" in body
    finally:
        stop.set()
        worker.join(2)
    # the window samplers are gone once their responses are built
    assert not [t for t in threading.enumerate()
                if t.name == "weedtpu-profiler"]


def test_kernel_profile_accumulates_from_dispatch():
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.stats import profile

    profile.KERNELS.reset()
    codec = rs.get_code(10, 4)
    batch = np.arange(10 * 64, dtype=np.uint8).reshape(10, 64)
    parity = dispatch.materialize(dispatch.dispatch_parity(codec, batch))
    assert parity.shape == (4, 64)
    out = dispatch.reconstruct_batch(
        codec, np.concatenate([batch[2:], parity[:2]]), range(2, 12),
        wanted=[0, 1])
    assert np.array_equal(out[0], batch[0])
    snap = profile.KERNELS.snapshot()
    assert snap["encode_parity[host]"]["calls"] == 1
    assert snap["encode_parity[host]"]["bytes"] == batch.nbytes
    assert snap["encode_parity[host]"]["wall_s"] >= 0
    assert snap["reconstruct[host]"]["calls"] == 1
    assert "encode_parity" in profile.KERNELS.table()


# ---- exemplar escaping + OpenMetrics lint ------------------------------

_EXEMPLAR_RE = re.compile(
    r' # \{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"\} '
    r'-?[0-9.e+-]+( [0-9.]+)?$')


def _lint_openmetrics(text: str) -> None:
    """Exemplar-aware lint: every ` # {...}` suffix must parse as a
    properly escaped OpenMetrics exemplar (raw quotes or newlines in a
    trace id would break a negotiating scraper), and the exposition
    sans exemplars must pass the plain lint."""
    stripped: list[str] = []
    for line in text.splitlines():
        if " # " in line and not line.startswith("#"):
            body, _, _ = line.partition(" # ")
            suffix = line[len(body):]
            assert _EXEMPLAR_RE.match(suffix), f"bad exemplar: {line!r}"
            line = body
        stripped.append(line)
    assert stripped[-1] == "# EOF"
    plain = "\n".join(stripped[:-1]) + "\n"
    # counters are _total-suffixed in OM; the plain linter only needs
    # label syntax + histogram shape, which survive the strip
    for ln in plain.splitlines():
        if ln.startswith("#") or not ln:
            continue
        assert _SAMPLE_RE.match(ln), f"unparseable after strip: {ln!r}"


def test_exemplar_trace_ids_are_escaped():
    reg = metrics.Registry()
    h = reg.histogram("weedtpu_esc_seconds", "t")
    # a hostile trace id must come out escaped, not spliced raw
    h.labels().observe(0.001, trace_id='evil"id\\with\nnewline')
    om = reg.render(openmetrics=True)
    assert '\\"' in om and "\\n" in om
    assert 'evil"id' not in om.replace('evil\\"id', "")
    _lint_openmetrics(om)
    # and the global registry's OM rendering lints clean too
    metrics.VOLUME_REQUEST_HISTOGRAM.labels("read").observe(0.004)
    _lint_openmetrics(metrics.REGISTRY.render(openmetrics=True))


# ---- pusher DNS re-resolution ------------------------------------------

def test_metrics_pusher_re_resolves_on_consecutive_failures():
    """Two consecutive push failures drop the socket pool and re-query
    DNS, so a re-pointed gateway name is picked up mid-process."""
    reg = metrics.Registry()
    dead = f"http://127.0.0.1:{_free_port()}"
    p = metrics.MetricsPusher(reg, dead, "j", interval=0.02,
                              max_backoff=0.2)
    pool0 = p.pool
    p.start()
    deadline = time.time() + 5
    while time.time() < deadline and p.re_resolves < 1:
        time.sleep(0.02)
    p.stop()
    assert p.re_resolves >= 1, "pusher never re-resolved"
    assert p.pool is not pool0, "socket pool not replaced"
    assert pool0._closed, "old pool left open"
    assert not p._thread.is_alive()


# ---- cluster aggregation unit layer ------------------------------------

def test_parse_exposition_roundtrip():
    from seaweedfs_tpu.stats import aggregate as ag
    reg = metrics.Registry()
    reg.counter("weedtpu_agg_total", "c", ("who",)).labels(
        'we"ird\\v\n').inc(3)
    reg.gauge("weedtpu_agg_gauge", "g").labels().set(7.5)
    reg.histogram("weedtpu_agg_seconds", "h").labels().observe(0.003)
    fams = ag.parse_exposition(reg.render())
    assert fams["weedtpu_agg_total"]["type"] == "counter"
    name, labels, value = fams["weedtpu_agg_total"]["samples"][0]
    assert labels == {"who": 'we"ird\\v\n'} and value == 3.0
    assert fams["weedtpu_agg_gauge"]["samples"][0][2] == 7.5
    hist = fams["weedtpu_agg_seconds"]
    assert hist["type"] == "histogram"
    names = {s[0] for s in hist["samples"]}
    assert {"weedtpu_agg_seconds_bucket", "weedtpu_agg_seconds_sum",
            "weedtpu_agg_seconds_count"} <= names


def test_counters_sum_across_nodes_and_federation_labels():
    from seaweedfs_tpu.stats import aggregate as ag

    def reg_with(n):
        reg = metrics.Registry()
        reg.counter("weedtpu_sum_total", "c", ("op",)).labels("read").inc(n)
        return ag.parse_exposition(reg.render())

    per_node = {"n1": reg_with(5), "n2": reg_with(7)}
    merged = ag.merge_counters(per_node)
    assert merged[("weedtpu_sum_total", (("op", "read"),))] == 12.0


def test_histogram_bucket_merge_p99_between_per_node_p99s():
    """Two nodes with different counts and different latency profiles:
    the merged histogram's count is the sum and its p99 lands between
    the two per-node p99s."""
    from seaweedfs_tpu.stats import aggregate as ag

    def node(obs):
        reg = metrics.Registry()
        h = reg.histogram("weedtpu_m_seconds", "h", ("type",))
        for v in obs:
            h.labels("read").observe(v)
        return ag.parse_exposition(reg.render())

    fast = node([0.001] * 180 + [0.02] * 20)       # p99 ~ 25ms
    slow = node([0.3] * 30 + [2.0] * 10)           # p99 ~ seconds
    key = ("weedtpu_m_seconds", (("type", "read"),))

    def p99(per_node):
        return ag.histogram_quantile(
            ag.merge_histograms(per_node)[key]["buckets"], 0.99)

    p_fast, p_slow = p99({"a": fast}), p99({"b": slow})
    merged = ag.merge_histograms({"a": fast, "b": slow})[key]
    assert merged["count"] == 240.0
    # buckets summed per le: the +Inf cum equals the count
    import math as _math
    assert merged["buckets"][_math.inf] == 240.0
    p_merged = ag.histogram_quantile(merged["buckets"], 0.99)
    assert min(p_fast, p_slow) < p_merged < max(p_fast, p_slow), \
        (p_fast, p_merged, p_slow)


def _avail_counters(good, bad):
    from seaweedfs_tpu.stats import aggregate as ag
    reg = metrics.Registry()
    c = reg.counter("weedtpu_http_requests_total", "t",
                    ("server", "op", "class"))
    c.labels("volume", "read", "2xx").inc(good)
    c.labels("volume", "read", "5xx").inc(bad)
    return ag.merge_counters({"n": ag.parse_exposition(reg.render())})


def test_slo_engine_burn_rate_flips():
    """Error-free window -> ok; a 5% 5xx ratio against a 99.9% target
    burns 50x the budget in BOTH windows -> violated; recovery -> ok."""
    from seaweedfs_tpu.stats import aggregate as ag

    def snap(good, bad):
        return {"n": _avail_counters(good, bad)}

    eng = ag.SLOEngine(rules=ag.parse_rules(
        "read_availability=availability,op=read,target=0.999"),
        windows=[5.0, 30.0])
    t0 = time.time()
    hist = [(t0 - 20, snap(0, 0), {}), (t0 - 10, snap(100, 0), {})]
    ok = eng.evaluate(hist)
    assert ok["state"] == "ok", ok
    hist.append((t0, snap(195, 5), {}))
    bad = eng.evaluate(hist)
    rule = bad["rules"][0]
    assert rule["state"] == "violated", rule
    assert all(w["burn_rate"] > 1 for w in rule["windows"].values())
    # recovery: later windows see no new errors
    hist = [(t0 - 10, snap(195, 5), {}), (t0, snap(400, 5), {})]
    assert eng.evaluate(hist)["rules"][0]["state"] == "ok"


def test_slo_engine_survives_node_counter_reset():
    """Deltas are per-node (rate-before-sum): node B restarting with
    zeroed counters must NOT clamp the cluster delta to zero while node
    A serves a 5xx burst — and B's post-restart errors count from 0."""
    from seaweedfs_tpu.stats import aggregate as ag
    eng = ag.SLOEngine(rules=ag.parse_rules(
        "read_availability=availability,op=read,target=0.999"),
        windows=[5.0, 30.0])
    t0 = time.time()
    hist = [
        (t0 - 10, {"a": _avail_counters(1000, 0),
                   "b": _avail_counters(5000, 0)}, {}),
        # b restarted (5000 -> 40 with 4 fresh errors); a burst 20 errors
        (t0, {"a": _avail_counters(1080, 20),
              "b": _avail_counters(40, 4)}, {}),
    ]
    rule = eng.evaluate(hist)["rules"][0]
    assert rule["state"] == "violated", rule
    win = rule["windows"]["5s"]
    # a: 20 bad / 100 total; b (reset): 4 bad / 44 total
    assert win["bad"] == 24.0 and win["total"] == 144.0, win


def test_slo_rule_parsing_and_defaults():
    from seaweedfs_tpu.stats import aggregate as ag
    rules = ag.parse_rules(None)  # defaults
    names = {r["name"] for r in rules}
    assert {"read_availability", "write_availability", "read_latency_p99",
            "repair_backlog"} <= names
    custom = ag.parse_rules(
        "p99=latency,family=weedtpu_x_seconds,label.type=read,ms=250,"
        "target=0.99;junk;bl=backlog,family=weedtpu_g,"
        "label.state!=healthy")
    assert len(custom) == 2
    assert custom[0]["ms"] == 250.0 and custom[0]["labels"] == \
        {"type": "read"}
    assert custom[1]["not_labels"] == {"state": "healthy"}


def test_cluster_aggregator_scrapes_local_and_http_node():
    """Aggregator end-to-end at the unit level: one local registry, one
    node served over real HTTP; federation output carries a node label
    per sample and the merged counters sum both."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from seaweedfs_tpu.stats import aggregate as ag

    reg_a = metrics.Registry()
    reg_a.counter("weedtpu_fed_total", "c").labels().inc(2)
    # big counters must render at full precision (':g' would emit
    # 1.23457e+07 and rate() over federated data would read zero)
    reg_a.counter("weedtpu_fed_big_total", "c").labels().inc(12345678)
    reg_b = metrics.Registry()
    reg_b.counter("weedtpu_fed_total", "c").labels().inc(3)

    class Node(BaseHTTPRequestHandler):
        def do_GET(self):
            body = reg_b.render().encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Node)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    node_b = f"127.0.0.1:{srv.server_address[1]}"
    agg = ag.ClusterAggregator(lambda: {node_b: node_b},
                               local=("master:1", reg_a), interval=0)
    try:
        agg.scrape_once()
        text = agg.render()
        assert 'node="master:1"' in text and f'node="{node_b}"' in text
        assert 'weedtpu_cluster_node_up{node="master:1"} 1' in text
        assert 'weedtpu_fed_big_total{node="master:1"} 12345678' in text
        merged = ag.merge_counters(agg.per_node)
        assert merged[("weedtpu_fed_total", ())] == 5.0
        # a vanished node shows up as an error, not an exception
        agg.nodes_fn = lambda: {"127.0.0.1:1": "127.0.0.1:1"}
        agg.scrape_once()
        assert "127.0.0.1:1" in agg.errors
        assert 'weedtpu_cluster_node_up{node="127.0.0.1:1"} 0' \
            in agg.render()
        st = agg.slo_status()
        assert st["state"] in ("ok", "warn", "violated", "unknown")
    finally:
        agg.stop()
        srv.shutdown()
        srv.server_close()


# ---- end-to-end trace propagation -------------------------------------

class _Cluster:
    """master + 2 volume servers + filer on one loop thread."""

    def __init__(self, tmp_path):
        self.tmp = tmp_path
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)

    def submit(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(60)

    def start(self):
        from seaweedfs_tpu.server.filer_server import FilerServer
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        self.thread.start()
        self.master = MasterServer("127.0.0.1", _free_port())
        self.submit(self.master.start())
        self.volume_servers = []
        for i in range(2):
            d = self.tmp / f"vs{i}"
            d.mkdir(exist_ok=True)
            vs = VolumeServer([str(d)], self.master.url, "127.0.0.1",
                              _free_port(), max_volumes=20,
                              heartbeat_interval=0.3)
            self.submit(vs.start())
            self.volume_servers.append(vs)
        # cache off: every GET pays the full filer->volume->shard path
        self.filer = FilerServer(self.master.url, port=_free_port(),
                                 chunk_cache_mem=0)
        self.submit(self.filer.start())
        deadline = time.time() + 5
        while time.time() < deadline and len(self.master.topo.nodes) < 2:
            time.sleep(0.05)
        return self

    def stop(self):
        self.submit(self.filer.stop())
        for vs in self.volume_servers:
            self.submit(vs.stop())
        self.submit(self.master.stop())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)


def test_trace_propagation_degraded_filer_read(tmp_path, monkeypatch):
    """A degraded EC read through the filer yields ONE trace id whose
    spans cover the filer request, the volume-server blob read, and the
    peer shard fetches; sampled-out requests write nothing to the ring;
    /debug/traces and /debug/requests serve it all as JSON."""
    import io
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    from seaweedfs_tpu.storage.ec import layout

    # local sampling off: only the explicit header below may trace, so
    # the sampled-out assertion sees a quiet ring
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "0")
    c = _Cluster(tmp_path).start()
    try:
        size = 10 * 1024 * 1024  # 3 chunks -> needles span many shards
        payload = np.random.default_rng(11).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        url = f"http://127.0.0.1:{c.filer.port}/obs/trace.bin"
        req = urllib.request.Request(url, data=payload, method="PUT")
        urllib.request.urlopen(req, timeout=60).read()
        with urllib.request.urlopen(url + "?metadata=true",
                                    timeout=10) as r:
            entry = json.load(r)
        vids = sorted({int(ch["fid"].partition(",")[0])
                       for ch in entry["chunks"]})
        assert vids
        time.sleep(0.7)

        env = CommandEnv(c.master.url)
        out = io.StringIO()
        run_command(env, "lock", out)
        for vid in vids:
            run_command(env, f"ec.encode -volumeId {vid}", out)
        run_command(env, "unlock", out)
        time.sleep(0.7)

        # drop two data shards everywhere: reads must reconstruct, and
        # reconstruction needs k=10 survivors while each server holds
        # ~7 -> the peer shard fetch is guaranteed
        for vid in vids:
            body = json.dumps({"volume": vid, "shards": [0, 1]}).encode()
            for vs in c.volume_servers:
                dreq = urllib.request.Request(
                    f"http://{vs.url}/admin/ec/delete_shards", data=body,
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(dreq, timeout=10).close()
        time.sleep(0.7)

        # -- forced-sample degraded GET: one trace id, many spans -------
        trace.reset_ring()
        tid = trace._new_trace_id()
        treq = urllib.request.Request(url, headers={
            trace.TRACE_HEADER: f"{tid}-{trace._new_span_id()}-1"})
        with urllib.request.urlopen(treq, timeout=120) as r:
            assert r.read() == payload
        # the root span lands in the middleware's finally — in the
        # server's loop thread, AFTER the last response byte reaches the
        # client — so give it a moment instead of racing it
        deadline = time.time() + 5.0
        while True:
            spans = [s for s in trace.ring_snapshot()
                     if s["trace"] == tid]
            names = {s["name"] for s in spans}
            if "filer.request" in names or time.time() > deadline:
                break
            time.sleep(0.05)
        assert len(spans) >= 5, (len(spans), sorted(names))
        assert "filer.request" in names
        assert "filer.chunk_fetch" in names
        assert "volume.request" in names
        # EC engine stages from the worker thread
        assert "ec.plan" in names and "ec.read.reconstruct" in names
        # ... and the dispatch seam's stage under it (a host codec here)
        assert "codec.dispatch" in names
        # peer shard spans: the fetch on the serving server AND the
        # peer's handling of /admin/ec/shard_read in the same trace
        assert "volume.shard_fetch" in names
        assert any(s["name"] == "volume.request" and
                   s.get("attrs", {}).get("path") == "/admin/ec/shard_read"
                   for s in spans)
        servers = {s.get("attrs", {}).get("server")
                   for s in spans if s["name"].endswith(".request")}
        assert {"filer", "volume"} <= servers
        # every non-root span hangs off a span of the same trace
        ids = {s["span"] for s in spans}
        roots = [s for s in spans if s["parent"] not in ids]
        assert roots, spans

        # visible through the filer's /debug/traces endpoint
        with urllib.request.urlopen(
                f"http://127.0.0.1:{c.filer.port}/debug/traces?limit=100",
                timeout=10) as r:
            dbg = json.load(r)
        assert tid in {t["trace_id"] for t in dbg["traces"]}
        # min_ms filter: an absurd floor hides it
        with urllib.request.urlopen(
                f"http://127.0.0.1:{c.filer.port}"
                f"/debug/traces?min_ms=1e12", timeout=10) as r:
            assert json.load(r)["traces"] == []

        # -- sampled-out GET writes NOTHING to the ring -----------------
        trace.reset_ring()
        with urllib.request.urlopen(url, timeout=120) as r:
            assert len(r.read()) == size
        assert trace.ring_snapshot() == []

        # -- /debug/requests shows the in-flight request (itself), and
        # it clears once finished
        with urllib.request.urlopen(
                f"http://127.0.0.1:{c.filer.port}/debug/requests",
                timeout=10) as r:
            reqs = json.load(r)["requests"]
        assert any(e["path"].startswith("/debug/requests")
                   for e in reqs), reqs
        time.sleep(0.1)
        assert not any(e["path"].startswith("/debug/requests")
                       for e in trace.inflight())
    finally:
        c.stop()


def test_every_env_knob_documented_in_readme():
    """Repo lint: every WEEDTPU_* environment knob read anywhere in
    seaweedfs_tpu/ must appear in README.md — an undocumented knob is a
    behavior nobody can discover, tune, or audit (the interference
    governor's floor/ceiling semantics made this a hard requirement:
    a knob that silently throttles repair MUST be findable)."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    src_knobs: set[str] = set()
    for p in (root / "seaweedfs_tpu").rglob("*.py"):
        src_knobs |= set(re.findall(r"WEEDTPU_[A-Z0-9_]+",
                                    p.read_text(encoding="utf-8")))
    assert src_knobs, "no knobs found — is the scan broken?"
    documented = set(re.findall(r"WEEDTPU_[A-Z0-9_]+",
                                (root / "README.md").read_text(
                                    encoding="utf-8")))
    missing = sorted(src_knobs - documented)
    assert not missing, (
        f"env knobs read in seaweedfs_tpu/ but undocumented in "
        f"README.md: {missing}")


def test_removed_ec_fork_knobs_are_read_nowhere():
    """Repo lint: the variables that selected a second write engine, a
    second encode strategy, a second read engine or another tile are
    gone from the package, the README and chip_smoke.py — a path that
    only a user-set variable selects has no cell on either side of it."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    gone = re.compile(
        r"WEEDTPU_(AIO|AIO_DEPTH|AIO_DIRECT|EC_PIPELINE|EC_READ|EC_TILE|"
        r"TILE_PIN|TILE_SENTINEL_INTERVAL)\b")
    files = [root / "README.md", root / "chip_smoke.py",
             *(root / "seaweedfs_tpu").rglob("*.py")]
    hits = sorted({f"{p.relative_to(root)}: {m.group(0)}" for p in files
                   for m in gone.finditer(p.read_text(encoding="utf-8"))})
    assert not hits, hits
    assert not (root / "bench.py").exists()
    assert not (root / "seaweedfs_tpu" / "storage" / "aio.py").exists()


def test_every_control_endpoint_documented_in_readme():
    """Repo lint: every /cluster/* and /admin/* HTTP endpoint the
    servers register must appear in README.md — an undocumented control
    endpoint is an actuator nobody can audit (the autopilot's
    /admin/volume/move made this a hard requirement: an endpoint that
    can relocate data MUST be findable).  Path params normalize
    {x} -> <x> to match the README's convention."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    endpoints: set[str] = set()
    # the server modules are where routes register; client call sites
    # elsewhere necessarily name a subset of these same paths
    for sub in ("server", "s3", "mq"):
        for p in (root / "seaweedfs_tpu" / sub).rglob("*.py"):
            endpoints |= set(re.findall(
                r'"(/(?:cluster|admin)/[A-Za-z0-9_/{}.:-]*)"',
                p.read_text(encoding="utf-8")))
    assert len(endpoints) > 30, (
        f"endpoint scan looks broken: {sorted(endpoints)}")
    readme = (root / "README.md").read_text(encoding="utf-8")
    missing = sorted(
        e for e in endpoints
        if re.sub(r"\{([A-Za-z0-9_:]+)\}", r"<\1>", e) not in readme)
    assert not missing, (
        f"HTTP control endpoints registered in the servers but "
        f"undocumented in README.md: {missing}")
