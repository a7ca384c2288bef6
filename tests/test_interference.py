"""Interference observatory + governor tests (stats/interference.py):
quiet-baseline/busy-tick index math with byte-share attribution and
decay-on-recovery, TokenBucket.set_rate under concurrent take() callers
(including the negative-token debt path), the governor's proportional
floor/ceiling control law with traced+pinned retune decisions, the
ConvertScheduler exact-name pause-alert fix, weedlog exc_info support,
the bench trajectory record-only path over a wiped history file, and a
3-node integration test where injected repair load raises
weedtpu_interference_index{class="repair"} on /cluster/interference,
the governor drops the xrack budget (visible in /maintenance/status)
and the fleet scrub rate, and both recover once the load stops — with
the retune queryable as a history series and a pinned trace."""

import io
import logging
import threading
import time
import types

import pytest

from seaweedfs_tpu.maintenance.repair import TokenBucket
from seaweedfs_tpu.stats import interference as itf
from seaweedfs_tpu.stats import metrics, netflow, trace
from seaweedfs_tpu.stats.aggregate import parse_exposition
from seaweedfs_tpu.utils import weedlog
from tests.test_cluster import Cluster
from tests.test_cluster_obs import _read_all, _upload_and_encode_all
from tests.test_maintenance import _get, _post


# ---- helpers -----------------------------------------------------------

@pytest.fixture(autouse=True)
def _retire_interference_gauges():
    """The observatory exports per-(node, class) gauges on the GLOBAL
    registry; a synthetic node left behind by a unit test would read as
    a real, permanently-inflamed node to the next test's alert engine
    (every in-process server renders the same registry)."""
    yield
    metrics.INTERFERENCE_INDEX.remove_matching()
    metrics.GOVERNOR_RATE.remove_matching()


class FakeNode:
    """One synthetic node: a private registry accumulating foreground
    read latencies and background byte counters, rendered+parsed into
    the per-node family dict the observatory consumes."""

    def __init__(self):
        self.reg = metrics.Registry()
        self.hist = self.reg.histogram("weedtpu_volume_request_seconds",
                                       "t", ("type",))
        self.net = self.reg.counter("weedtpu_net_bytes_total", "t",
                                    ("direction", "class", "peer_role"))

    def read(self, latencies):
        for v in latencies:
            self.hist.labels("read").observe(v)

    def bg(self, cls, nbytes, direction="recv"):
        self.net.labels(direction, cls, "volume").inc(nbytes)

    def fams(self):
        return parse_exposition(self.reg.render())


def _obs(**kw):
    kw.setdefault("quiet_bps", 1000.0)
    kw.setdefault("min_samples", 4)
    kw.setdefault("alpha", 0.5)
    return itf.InterferenceObservatory(**kw)


# ---- observatory math --------------------------------------------------

def test_quiet_baseline_busy_attribution_and_decay():
    obs = _obs()
    node = FakeNode()
    t0 = 1000.0
    node.read([0.01] * 8)
    obs.observe(t0, {"n1": node.fams()})          # first sight: no delta
    node.read([0.01] * 8)
    obs.observe(t0 + 10, {"n1": node.fams()})     # quiet: baseline forms
    st = obs._nodes["n1"]
    assert st.quiet_p99 == pytest.approx(0.01, rel=0.2)
    assert st.index.get("repair", 0.0) == 0.0

    # busy tick: repair bytes flow AND p99 inflates 10x
    node.read([0.1] * 8)
    node.bg("repair", 50 * 1024 * 1024)
    obs.observe(t0 + 20, {"n1": node.fams()})
    idx = st.index["repair"]
    assert idx > 0.5  # alpha * (10x - 1) * share 1.0 >> 0.5
    assert obs.fleet_index()["repair"]["node"] == "n1"
    # the gauge series exists for the history plane to record
    text = metrics.REGISTRY.render()
    assert 'weedtpu_interference_index{node="n1",class="repair"}' in text

    # recovery: quiet ticks decay the index toward zero
    for i in range(1, 6):
        node.read([0.01] * 8)
        obs.observe(t0 + 20 + 10 * i, {"n1": node.fams()})
    assert st.index["repair"] < idx * 0.2
    snap = obs.snapshot()
    assert snap["nodes"]["n1"]["quiet_ticks"] >= 5
    assert snap["nodes"]["n1"]["busy_ticks"] == 1


def test_impact_attributed_by_byte_share():
    obs = _obs()
    node = FakeNode()
    node.read([0.01] * 8)
    obs.observe(0.0, {"n1": node.fams()})
    node.read([0.01] * 8)
    obs.observe(10.0, {"n1": node.fams()})
    # scrub moves 3x the bytes repair does in the same busy window
    node.read([0.05] * 8)
    node.bg("repair", 10 * 1024 * 1024)
    node.bg("scrub", 30 * 1024 * 1024)
    obs.observe(20.0, {"n1": node.fams()})
    st = obs._nodes["n1"]
    assert st.index["scrub"] == pytest.approx(3 * st.index["repair"],
                                              rel=0.05)


def test_too_few_samples_moves_nothing():
    obs = _obs(min_samples=8)
    node = FakeNode()
    node.read([0.01] * 10)
    obs.observe(0.0, {"n1": node.fams()})
    node.read([0.01] * 10)
    obs.observe(10.0, {"n1": node.fams()})
    base = obs._nodes["n1"].quiet_p99
    # 2 slow reads under repair load: below min_samples, so neither the
    # baseline nor the index may move on such thin evidence
    node.read([0.5] * 2)
    node.bg("repair", 50 * 1024 * 1024)
    obs.observe(20.0, {"n1": node.fams()})
    st = obs._nodes["n1"]
    assert st.quiet_p99 == base
    assert st.index.get("repair", 0.0) == 0.0


def test_absent_node_index_decays_instead_of_freezing():
    """A node that crashes mid-engagement stops generating interference
    the moment it stops serving: its index must decay like quiet ticks,
    not steer fleet_index()'s max at its frozen last value for the
    whole 600s eviction window."""
    obs = _obs()
    node = FakeNode()
    node.read([0.01] * 8)
    obs.observe(0.0, {"nd": node.fams()})
    node.read([0.01] * 8)
    obs.observe(10.0, {"nd": node.fams()})
    node.read([0.1] * 8)
    node.bg("repair", 50 * 1024 * 1024)
    obs.observe(20.0, {"nd": node.fams()})
    idx = obs._nodes["nd"].index["repair"]
    assert idx > 0.5
    for i in range(1, 6):  # the node vanishes from every later tick
        obs.observe(20.0 + 10 * i, {})
    assert obs._nodes["nd"].index["repair"] < idx * 0.2
    assert obs.fleet_index()["repair"]["index"] < idx * 0.2


def test_disabled_observatory_is_a_noop(monkeypatch):
    monkeypatch.setenv("WEEDTPU_INTERFERENCE", "0")
    monkeypatch.setattr(itf, "_enabled_cache", (0.0, True))
    obs = _obs()
    node = FakeNode()
    node.read([0.01] * 8)
    obs.observe(0.0, {"n1": node.fams()})
    assert obs.ticks == 0 and not obs._nodes
    assert not itf.governor_enabled()


# ---- TokenBucket.set_rate ----------------------------------------------

def test_token_bucket_set_rate_settles_debt_at_old_rate(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    b = TokenBucket(rate=10.0, burst=10.0)
    # oversized request admitted only at FULL, driving debt
    assert b.try_acquire(110.0)
    assert b.tokens == pytest.approx(-100.0)
    assert not b.try_acquire(1.0)
    # 5s at the OLD rate pays 50 of the debt, THEN the rate drops: a
    # retune never retroactively reprices already-elapsed time
    clock[0] += 5.0
    b.set_rate(1.0)
    assert b.tokens == pytest.approx(-50.0)
    assert b.rate == 1.0
    clock[0] += 49.0
    assert not b.try_acquire(1.0)  # still 1 token short of +1
    clock[0] += 3.0
    assert b.try_acquire(1.0)


def test_token_bucket_set_rate_under_concurrent_takers():
    b = TokenBucket(rate=5000.0, burst=200.0)
    stop = threading.Event()
    took = [0] * 4
    errs: list[BaseException] = []

    def taker(i):
        try:
            while not stop.is_set():
                if b.try_acquire(1.0):
                    took[i] += 1
        except BaseException as e:  # noqa: BLE001 — must surface races
            errs.append(e)

    threads = [threading.Thread(target=taker, args=(i,)) for i in range(4)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for _ in range(50):
        b.set_rate(5000.0)
        b.set_rate(500.0)
        b.credit(1.0)
        b.force_debit(1.0)
        time.sleep(0.002)
    stop.set()
    for t in threads:
        t.join(5)
    elapsed = time.monotonic() - t0
    assert not errs
    # admissions stay bounded by burst + the MAX rate over the window
    # (generous slack for scheduling): the lock kept refill consistent
    assert sum(took) <= 200.0 + 5000.0 * elapsed * 1.5 + 100
    assert sum(took) > 0
    assert b.tokens <= b.burst


# ---- governor ----------------------------------------------------------

class _FakeTopo:
    def __init__(self):
        self.nodes = {}
        self._lock = threading.Lock()


def _fake_master(xrack_rate=1000.0, convert_rate=2.0):
    m = types.SimpleNamespace()
    m.maintenance = types.SimpleNamespace(
        xrack_bucket=TokenBucket(xrack_rate, 4 * xrack_rate))
    m.convert = types.SimpleNamespace(bucket=TokenBucket(convert_rate, 8.0))
    m.topo = _FakeTopo()
    m.aggregator = types.SimpleNamespace(pool=None)
    return m


def test_governor_backoff_floor_recovery_and_audit(monkeypatch):
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "0")  # no scrub target
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    master = _fake_master()
    obs = _obs()
    gov = itf.Governor(master, obs)
    st = itf._NodeState()
    st.index = {"repair": 2.0}
    st.last_seen = time.time()
    obs._nodes["n1"] = st

    # proportional backoff: index 2.0 vs target 0.25 -> rate x 1/8
    made = gov.tick(1000.0)
    assert [d["target"] for d in made] == ["repair_xrack"]
    assert master.maintenance.xrack_bucket.rate == pytest.approx(125.0)
    assert made[0]["direction"] == "down"
    # the decision is a pinned, traced event
    tid = made[0]["trace_id"]
    recs = trace.traces(tid=tid)
    assert recs and any(s["name"] == "governor.retune"
                        for r in recs for s in r["spans"])
    # sustained pressure bottoms out at the floor, never below
    for i in range(6):
        gov.tick(1001.0 + i)
    assert master.maintenance.xrack_bucket.rate == pytest.approx(100.0)

    # recovery: index gone -> multiplicative ramp back to the ceiling
    st.index = {}
    for i in range(20):
        gov.tick(1100.0 + i)
    assert master.maintenance.xrack_bucket.rate == pytest.approx(1000.0)
    assert any(d["direction"] == "up" for d in gov.decisions)
    status = gov.status()
    assert status["targets"]["repair_xrack"]["ceiling"] == 1000.0
    assert status["retunes"] == gov.retunes


def test_governor_disabled_restores_ceiling_once(monkeypatch):
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "0")
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    master = _fake_master()
    obs = _obs()
    gov = itf.Governor(master, obs)
    st = itf._NodeState()
    st.index = {"repair": 5.0}
    obs._nodes["n1"] = st
    gov.tick(1.0)
    assert master.maintenance.xrack_bucket.rate < 1000.0
    monkeypatch.setenv("WEEDTPU_GOVERNOR", "0")
    restored = gov.tick(2.0)
    assert [d["reason"] for d in restored] == ["disabled"]
    assert master.maintenance.xrack_bucket.rate == 1000.0
    assert gov.decisions[-1]["reason"] == "disabled"
    n = len(gov.decisions)
    assert gov.tick(3.0) == []  # stays off, no more decisions
    assert len(gov.decisions) == n


def test_governor_deadband_never_strands_rate_below_ceiling(monkeypatch):
    """The last recovery step from ~0.96x ceiling is a <5% move; the
    deadband must exempt moves landing exactly on the ceiling (or
    floor) or the rate parks just short of the configured static rate
    forever."""
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "0")
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    master = _fake_master()
    obs = _obs()
    gov = itf.Governor(master, obs)
    master.maintenance.xrack_bucket.set_rate(977.0)  # 97.7% of ceiling
    gov.tick(1.0)
    assert master.maintenance.xrack_bucket.rate == pytest.approx(1000.0)
    # at the ceiling with no pressure: steady state, no decision churn
    n = len(gov.decisions)
    gov.tick(2.0)
    assert len(gov.decisions) == n


def test_disable_observatory_retires_index_series(monkeypatch):
    """WEEDTPU_INTERFERENCE=0 mid-engagement must retire the per-node
    gauges, not freeze them at their last (possibly alert-firing)
    values."""
    obs = _obs()
    node = FakeNode()
    node.read([0.01] * 8)
    obs.observe(0.0, {"nfreeze": node.fams()})    # first sight
    node.read([0.01] * 8)
    obs.observe(10.0, {"nfreeze": node.fams()})   # quiet baseline
    node.read([0.1] * 8)
    node.bg("repair", 50 * 1024 * 1024)
    obs.observe(20.0, {"nfreeze": node.fams()})   # busy: index rises
    assert obs._nodes["nfreeze"].index.get("repair", 0.0) > 0
    assert 'node="nfreeze"' in metrics.REGISTRY.render()
    monkeypatch.setenv("WEEDTPU_INTERFERENCE", "0")
    monkeypatch.setattr(itf, "_enabled_cache", (0.0, True))
    obs.observe(30.0, {"nfreeze": node.fams()})
    assert not obs._nodes
    assert 'node="nfreeze"' not in metrics.REGISTRY.render()


def test_governor_repushes_scrub_rate_for_late_joiners(monkeypatch):
    """A volume server restarting mid-engagement re-inits its scrubber
    at the env ceiling; while the governed rate sits away from the
    ceiling the governor must re-push periodically, not only on new
    decisions (a rate pinned at the floor makes no decisions at all)."""
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "8")
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    master = _fake_master()
    obs = _obs()
    gov = itf.Governor(master, obs)
    pushes: list[float] = []
    monkeypatch.setattr(gov, "_push_scrub_rate", pushes.append)
    st = itf._NodeState()
    st.index = {"scrub": 2.0}
    obs._nodes["n1"] = st
    gov.tick(100.0)
    assert pushes == [pytest.approx(1.0)]  # 8 x 0.25/2.0
    gov.tick(101.0)                        # bottoms out at the floor
    assert pushes[-1] == pytest.approx(0.8)
    n = len(pushes)
    gov.tick(102.0)   # pinned at floor: no decision, within REPUSH_S
    gov.tick(110.0)
    assert len(pushes) == n
    gov.tick(101.0 + gov.REPUSH_S + 1)  # periodic re-push kicks in
    assert len(pushes) == n + 1 and pushes[-1] == pytest.approx(0.8)
    # disabling restores the ceiling — and KEEPS re-asserting it at the
    # same cadence, so a node partitioned during the one-shot restore
    # still converges back to its configured rate
    monkeypatch.setenv("WEEDTPU_GOVERNOR", "0")
    t0 = 101.0 + gov.REPUSH_S + 1
    gov.tick(t0 + 1)
    assert pushes[-1] == pytest.approx(8.0)
    n = len(pushes)
    gov.tick(t0 + 2)  # within the cadence: no push spam
    assert len(pushes) == n
    gov.tick(t0 + 1 + gov.REPUSH_S + 1)
    assert len(pushes) == n + 1 and pushes[-1] == pytest.approx(8.0)
    # a disabled scrub knob never renders as a governed target at all
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "0")
    gov2 = itf.Governor(master, _obs())
    assert "scrub" not in gov2.status()["targets"]


def test_scrub_set_mbps_zero_pauses_never_unthrottles():
    """{"mbps": 0} means STOP scrubbing: future passes skip, and the
    live limiter keeps its previous rate — a zero-rate RateLimiter is
    unthrottled, the opposite of the operator's intent."""
    from seaweedfs_tpu.maintenance.scrub import RateLimiter, Scrubber

    class _Store:
        locations = ()

    s = Scrubber(_Store(), mbps=8, interval=3600)
    s._limiter = RateLimiter(8e6)
    assert s.set_mbps(0) == 0.0
    assert s.operator_paused
    assert s._limiter.rate == 8e6  # never dropped to "unlimited"
    assert s.scrub_once().get("paused") is True
    # the governor's periodic re-push cannot override a human stop
    assert s.set_mbps(6, governed=True) == 0.0
    assert s.mbps == 0.0 and s.operator_paused
    # an operator resume releases the latch; governed retunes work again
    assert s.set_mbps(4) == 4.0
    assert not s.operator_paused
    assert s._limiter.rate == 4e6
    assert "paused" not in s.scrub_once()
    assert s.set_mbps(2, governed=True) == 2.0


def test_governed_scale_respects_per_node_config():
    """The governor pushes a FRACTION of the master ceiling; a node
    deliberately configured slower (WEEDTPU_SCRUB_MBPS=2 in an
    8-default fleet) is scaled against its OWN rate, never raised to
    the master's ceiling."""
    from seaweedfs_tpu.maintenance.scrub import Scrubber

    class _Store:
        locations = ()

    s = Scrubber(_Store(), mbps=2, interval=3600)
    assert s.apply_governed_scale(1.0) == 2.0  # full speed = ITS config
    assert s.apply_governed_scale(0.5) == 1.0
    assert s.apply_governed_scale(2.0) == 2.0  # scale clamps at 1.0
    s.set_mbps(0)                              # operator pause
    assert s.apply_governed_scale(1.0) == 0.0  # the latch still wins
    s.set_mbps(4)                              # operator sets a new
    assert s.configured_mbps == 4.0            # baseline to scale from
    assert s.apply_governed_scale(0.25) == 1.0


def test_governor_converges_fleet_scrub_on_first_tick(monkeypatch):
    """A fresh master does not know what rate a predecessor left the
    fleet's scrubbers at: the first enabled tick that sees nodes pushes
    this governor's rate once, so a governed-down fleet never stays
    stranded after a master restart."""
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "8")
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    master = _fake_master()
    master.topo.nodes = {"n1:80": object()}
    obs = _obs()
    gov = itf.Governor(master, obs)
    pushes: list[float] = []
    monkeypatch.setattr(gov, "_push_scrub_rate", pushes.append)
    gov.tick(1.0)  # quiet fleet, no decisions — convergence push only
    assert pushes == [pytest.approx(8.0)]
    gov.tick(2.0)
    assert len(pushes) == 1  # once, not per tick


# ---- convert pause: exact-name matching --------------------------------

class _FakeAlerts:
    def __init__(self, firing):
        self.firing = firing

    def status(self):
        return {"rules": [{"name": n, "state": "firing"}
                          for n in self.firing]}


def _sched(firing, governor=False, monkeypatch=None):
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    m = types.SimpleNamespace(alerts=_FakeAlerts(firing))
    if governor:
        m.governor = types.SimpleNamespace(
            INTERFERENCE_ALERT="interference_high")
    return ConvertScheduler(m)


def test_pause_alert_exact_name_not_substring(monkeypatch):
    monkeypatch.delenv("WEEDTPU_CONVERT_PAUSE_ALERTS", raising=False)
    # the PR 12 bug class: a rule merely CONTAINING "interference" must
    # not pause conversion
    assert _sched(["no_interference_baseline"])._paused_by_alert() is None
    assert _sched(["interference_high"])._paused_by_alert() == \
        "interference_high"
    assert _sched(["disk_full_soon"])._paused_by_alert() == \
        "disk_full_soon"


def test_governor_supersedes_interference_pause(monkeypatch):
    monkeypatch.delenv("WEEDTPU_CONVERT_PAUSE_ALERTS", raising=False)
    monkeypatch.delenv("WEEDTPU_GOVERNOR", raising=False)
    monkeypatch.delenv("WEEDTPU_INTERFERENCE", raising=False)
    monkeypatch.setattr(itf, "_enabled_cache", (0.0, True))
    # governor active: continuous pacing replaces the binary pause...
    s = _sched(["interference_high"], governor=True)
    assert s._paused_by_alert() is None
    # ...but capacity alerts still stop conversion outright
    s = _sched(["interference_high", "disk_full_soon"], governor=True)
    assert s._paused_by_alert() == "disk_full_soon"
    # governor switched off: the binary pause is back
    monkeypatch.setenv("WEEDTPU_GOVERNOR", "0")
    s = _sched(["interference_high"], governor=True)
    assert s._paused_by_alert() == "interference_high"


# ---- weedlog exc_info --------------------------------------------------

def test_weedlog_exc_info_carries_traceback(caplog):
    with caplog.at_level(logging.DEBUG, logger="tlog"):
        try:
            raise ValueError("boom-42")
        except ValueError:
            weedlog.warning("op failed: %s", "ctx", name="tlog",
                            exc_info=True)
            weedlog.info("op failed too", name="tlog", exc_info=True)
            weedlog.V(0, "tlog").infof("gated: %s", "x", exc_info=True)
    assert caplog.text.count("boom-42") >= 3
    assert "Traceback" in caplog.text
    # default stays traceback-free
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="tlog"):
        weedlog.warning("plain", name="tlog")
    assert "Traceback" not in caplog.text


# ---- 3-node integration ------------------------------------------------

@pytest.fixture()
def itf_cluster(tmp_path, monkeypatch):
    """3 volume servers, EC everywhere, deterministic ticks (driven via
    ?refresh=1), a fast observatory (min_samples 4, alpha 0.5) and an
    interference_high rule with no hysteresis so one busy tick shows
    every edge."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    monkeypatch.setenv("WEEDTPU_SCRUB_MBPS", "8")
    monkeypatch.setenv("WEEDTPU_SCRUB_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_REPAIR_INTERVAL", "3600")
    monkeypatch.setenv("WEEDTPU_AGG_INTERVAL", "0")
    monkeypatch.setenv("WEEDTPU_HEDGE_PCT", "0")
    monkeypatch.setenv("WEEDTPU_INTERF_MIN_SAMPLES", "4")
    monkeypatch.setenv("WEEDTPU_INTERF_ALPHA", "0.5")
    monkeypatch.setenv(
        "WEEDTPU_ALERT_RULES",
        # agg=last (not the production max): the test must see the
        # CLEAR edge within seconds of recovery, not after the busy
        # peak ages out of a 60s window
        "interference_high=threshold,series=weedtpu_interference_index,"
        "agg=last,window=60,op=gt,value=0.5,for=0,clear_for=0")
    monkeypatch.setattr(itf, "_enabled_cache", (0.0, True))
    c = Cluster(tmp_path, n_volume_servers=3).start()
    c.wait_heartbeats()
    yield c
    c.stop()


def _interference(master_url, refresh=True):
    qs = "?refresh=1" if refresh else ""
    return _get(master_url, f"/cluster/interference{qs}", timeout=60)


def test_cluster_interference_rises_governs_and_recovers(itf_cluster):
    c = itf_cluster
    master = c.master
    client, payloads = _upload_and_encode_all(c)
    xrack_ceiling = master.maintenance.xrack_bucket.rate
    scrub_ceiling = c.volume_servers[0].scrubber.mbps

    # -- quiet phase: two ticks bracketing fast reads -> baseline --------
    _interference(master.url)
    for _ in range(2):
        _read_all(client, payloads)
        st = _interference(master.url)
    assert any(rec.get("quiet_p99_ms")
               for rec in st["interference"]["nodes"].values()), st

    # -- busy phase: slow reads + repair byte-flow in one tick window ----
    # 250ms: on a loaded CI host the QUIET baseline can already sit at
    # tens of ms, and the index must still clear the governor's 0.25
    # target by a wide margin (a 100ms delay once measured only ~2x
    # inflation under a full parallel suite)
    for vs in c.volume_servers:
        _post(vs.url, "/admin/faults", {"faults": [
            {"action": "delay_shard_read", "ms": 250}]})
    # equal repair + scrub byte-flow so BOTH class indexes rise and the
    # scrub target (which follows its own class) demonstrably backs off
    netflow.account("recv", "repair", "volume", 64 * 1024 * 1024)
    netflow.account("recv", "scrub", "volume", 64 * 1024 * 1024)
    _read_all(client, payloads)
    st = _interference(master.url)
    classes = st["interference"]["classes"]
    # above the governor's target: a down-retune is guaranteed (the
    # absolute value depends on host weather; the CONTROL response and
    # the recorded alert series are the load-bearing assertions)
    assert classes.get("repair", {}).get("index", 0.0) > 0.25, st

    # the governor backed the xrack budget off its ceiling...
    gov = st["governor"]
    assert gov["targets"]["repair_xrack"]["rate"] < xrack_ceiling
    decisions = gov["decisions"]
    down = [d for d in decisions if d["target"] == "repair_xrack"
            and d["direction"] == "down"]
    assert down, decisions
    # ...visibly in /maintenance/status (planner xrack + governor block)
    mst = _get(master.url, "/maintenance/status")
    assert mst["planner"]["xrack"]["budget_bytes_per_s"] < xrack_ceiling
    assert mst["interference"]["governor"]["targets"][
        "repair_xrack"]["rate"] < xrack_ceiling
    # ...and the scrub limiter followed on every volume server
    governed_scrub = [vs.scrubber.mbps for vs in c.volume_servers]
    assert all(m < scrub_ceiling for m in governed_scrub), governed_scrub

    # the retune decision is a pinned trace with a governor.retune span
    tid = down[-1]["trace_id"]
    wf = _get(master.url, f"/cluster/trace/{tid}", timeout=60)
    assert any(s["name"] == "governor.retune" for s in wf["spans"]), wf

    # the interference_high alert fires off the recorded index series
    alerts = _get(master.url, "/cluster/alerts?refresh=1", timeout=60)
    rule = next(r for r in alerts["rules"]
                if r["name"] == "interference_high")
    assert rule["state"] == "firing", alerts

    # retunes are queryable as history series after the next tick
    hist = _get(master.url,
                "/cluster/history?series=weedtpu_governor_rate&range=600")
    assert hist["vectors"], hist
    hist = _get(master.url, "/cluster/history?series="
                            "weedtpu_interference_index&range=600")
    assert hist["vectors"], hist

    # -- recovery: load stops, index decays, rates ramp back -------------
    for vs in c.volume_servers:
        _post(vs.url, "/admin/faults", {"faults": [
            {"action": "delay_shard_read", "ms": 0}]})
    floor = gov["targets"]["repair_xrack"]["floor"]
    deadline = time.time() + 30
    recovered = None
    while time.time() < deadline:
        _read_all(client, payloads)
        st = _interference(master.url)
        idx = st["interference"]["classes"].get("repair",
                                                {}).get("index", 0.0)
        rate = st["governor"]["targets"]["repair_xrack"]["rate"]
        if idx < 0.25 and rate > floor:
            recovered = st
            break
    assert recovered is not None, st
    # the recorded series lags the live index (set-at-tick-N, scraped at
    # N+1) and sums over the in-process "nodes" sharing one registry:
    # give the decay a few more quiet ticks to cross the clear edge
    deadline = time.time() + 20
    while time.time() < deadline:
        alerts = _get(master.url, "/cluster/alerts?refresh=1", timeout=60)
        rule = next(r for r in alerts["rules"]
                    if r["name"] == "interference_high")
        if rule["state"] != "firing":
            break
        time.sleep(0.2)
    assert rule["state"] != "firing", alerts
    assert any(d["direction"] == "up"
               for d in recovered["governor"]["decisions"])
    # scrub follows back up too
    assert c.volume_servers[0].scrubber.mbps > min(governed_scrub)

    # shell one-stop view renders the same story
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    env = CommandEnv(c.master.url)
    out = io.StringIO()
    run_command(env, "cluster.interference", out)
    text = out.getvalue()
    assert "governor" in text and "repair_xrack" in text, text
    out = io.StringIO()
    run_command(env, "maintenance.status", out)
    assert "governor:" in out.getvalue()
    client.close()
