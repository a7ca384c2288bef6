"""The codec family (ops/codecs, ops/lrc, ops/msr) beyond plain RS.

Unit layer: tag grammar + registry, LRC byte identity over EVERY loss
pattern up to its tolerance (the distance-4 claim, verified
exhaustively), single-group local repair, PM-MSR node-MDS byte identity
and the d/(k*alpha) repair bandwidth floor, the bounded decode-matrix
LRU, and the /admin/ec/partial alpha sub-row protocol.

Engine layer: degraded reads through the batched EC read engine stay
byte-identical per family, and an LRC single-shard degraded read
gathers survivors from exactly ONE local group (<= r+1 shards — the
no-wide-fan-in acceptance gate).

Policy layer: the autopilot's codec_select bands (hot -> LRC,
sustained-cold -> MSR), hysteresis, and plan-only inertness.

Cluster layer (chaos cells): LRC whole-group loss heals clean, MSR
survives a helper death mid-repair, and a MIXED-codec cluster passes a
full heal + byte-identical readback + fsck-clean pass.
"""

import asyncio
import io
import itertools
import os
import time
import types

import numpy as np
import pytest

from seaweedfs_tpu.ops import codecs, gf, lrc, msr
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume

LARGE, SMALL = 10000, 100


# ---- tag grammar + registry --------------------------------------------


def test_tag_grammar_and_degradation(monkeypatch):
    """None / "" / bare family names / garbage all resolve to a usable
    spec — an old node that never heard of codec tags means RS, never
    an error (the no-flag-day contract)."""
    assert codecs.parse_tag(None).tag == "rs_10_4"
    assert codecs.parse_tag("").tag == "rs_10_4"
    assert codecs.parse_tag("bogus_7_7").tag == "rs_10_4"
    assert codecs.parse_tag("lrc_10_oops_2").tag == "rs_10_4"
    assert codecs.parse_tag("rs").tag == "rs_10_4"
    s = codecs.parse_tag("lrc_10_2_2")
    assert (s.family, s.k, s.m, s.n, s.alpha) == ("lrc", 10, 4, 14, 1)
    assert s.tolerance == 3  # g + 1, NOT m: LRC is not MDS
    s = codecs.parse_tag("msr_9_16")
    assert (s.family, s.k, s.m, s.n, s.alpha) == ("msr", 9, 9, 18, 8)
    # bare family names follow the WEEDTPU_CODEC_* param knobs
    monkeypatch.setenv("WEEDTPU_CODEC_LRC", "12,3,2")
    assert codecs.parse_tag("lrc").tag == "lrc_12_3_2"
    monkeypatch.setenv("WEEDTPU_CODEC_DEFAULT", "msr")
    assert codecs.default_tag() == "msr_9_16"


@pytest.mark.parametrize("platform, backend, wanted", [
    ("tpu", "xla", [(-3, 32 << 20), (-1, 1 << 30), (-2, 64 << 20)]),
    ("cpu", "xla", []),       # nothing is copied back on the CPU backend
    ("tpu", "numpy", []),     # a host shell copies nothing back either
])
def test_a_device_shell_keeps_freed_result_pages(monkeypatch, platform,
                                                 backend, wanted):
    """Building a device shell on a platform that copies results back
    sets glibc's M_MMAP_THRESHOLD, M_TRIM_THRESHOLD and M_TOP_PAD once
    (ops/dispatch.keep_freed_pages), so the runtime's 16 MiB result
    buffers come back as touched memory; no other build does."""
    import ctypes

    import jax
    calls: list[tuple[int, int]] = []
    libc = types.SimpleNamespace(
        mallopt=lambda param, value: calls.append((param, value)) or 1)
    monkeypatch.setattr(ctypes, "CDLL", lambda name: libc)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    codecs._build.cache_clear()
    try:
        codecs._build("rs_10_4", backend)
        codecs._build("rs_10_4", backend)  # cached: not set again
    finally:
        codecs._build.cache_clear()
    assert calls == wanted


def test_freed_result_pages_stay_in_a_thread_arena():
    """What the three settings are for, on the allocator itself and in a
    process of its own (they last): 16 MiB buffers allocated and freed on
    a thread that is not the main one, as a request's worker allocates
    the copies back, are faulted in once and handed out touched after
    that.  Without M_TOP_PAD glibc unmaps a thread arena's heaps as they
    fall free and every round faults them in again."""
    import subprocess
    import sys
    script = """
import ctypes, resource, threading
try:
    ctypes.CDLL(None).mallopt
except (OSError, AttributeError):
    print("no mallopt"); raise SystemExit
import numpy as np
from seaweedfs_tpu.ops import dispatch
dispatch.keep_freed_pages()
faults = []
def rounds():
    for _ in range(3):
        f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        bufs = [np.empty(16 << 20, np.uint8) for _ in range(6)]
        for b in bufs:
            b[::4096] = 1
        del bufs, b
        faults.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0)
t = threading.Thread(target=rounds); t.start(); t.join()
print(*faults)
"""
    out = subprocess.run([sys.executable, "-c", script], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"}).stdout
    if out.strip() == "no mallopt":
        pytest.skip("another libc")
    first, *later = map(int, out.split())
    assert first > 1000 and max(later) < first // 10, out


def test_registry_lists_every_family():
    tags = {s.family for s in codecs.registered()}
    assert tags == {"rs", "lrc", "msr"}
    for s in codecs.registered():
        d = s.describe()
        assert d["tag"] and d["n"] == d["k"] + d["m"], d


# ---- LRC: exhaustive byte identity -------------------------------------


def test_lrc_byte_identity_every_loss_pattern_to_tolerance():
    """LRC(10,2,2) has distance 4: EVERY loss pattern of 1, 2 or 3
    shards (469 patterns) reconstructs byte-identically.  This is the
    exhaustive verification the construction docstring promises."""
    code = lrc.get_code(10, 2, 2)
    rng = np.random.default_rng(0x16C)
    data = rng.integers(0, 256, (code.k, 64), dtype=np.uint8)
    full = code.encode_numpy(data)
    spec = codecs.parse_tag(code.tag)
    for t in range(1, spec.tolerance + 1):
        for lost in itertools.combinations(range(code.n), t):
            assert code.decodable(list(lost)), lost
            shards = {i: full[i] for i in range(code.n) if i not in lost}
            out = code.reconstruct_numpy(shards, list(lost))
            for s in lost:
                assert np.array_equal(out[s], full[s]), (lost, s)


def test_lrc_single_loss_repair_stays_in_one_group():
    """The headline property: repairing any single data or local-parity
    shard uses exactly r survivors, all from the lost shard's own
    group — never a cross-group or global-parity read."""
    code = lrc.get_code(10, 2, 2)
    everyone = list(range(code.n))
    for lost in range(code.k + code.l):
        gi = code.group_of(lost)
        support = code.repair_support(lost, [s for s in everyone
                                             if s != lost])
        assert support is not None and len(support) == code.r
        assert set(support) <= set(code.group_members(gi))
        # decode_select honors the local path for single losses
        basis = code.decode_select([s for s in everyone if s != lost],
                                   [lost])
        assert basis == support
    # a global parity has no local group: wide decode is correct there
    assert code.repair_support(code.k + code.l, everyone) is None
    # a second loss inside the group kills the local path
    assert code.repair_support(2, [s for s in everyone
                                   if s not in (2, 3)]) is None


# ---- MSR: node-MDS byte identity + repair bandwidth --------------------


def test_msr_byte_identity_single_and_double_node_loss():
    fc = codecs.make_codec("msr_9_16", "numpy")
    code = fc.code
    rng = np.random.default_rng(0x359)
    L = 5 * code.alpha  # byte-interleaved: L % alpha == 0
    data = rng.integers(0, 256, (fc.k, L), dtype=np.uint8)
    full = fc.encode(data)
    pats = [(i,) for i in range(fc.n)] + \
        list(itertools.combinations(range(fc.n), 2))
    for lost in pats:
        shards = {i: full[i] for i in range(fc.n) if i not in lost}
        out = fc.reconstruct(shards, list(lost))
        for s in lost:
            assert np.array_equal(out[s], full[s]), (lost, s)


def test_msr_max_loss_patterns_decode():
    """Node-MDS at the limit: any k=9 surviving whole nodes rebuild
    all m=9 lost ones (sampled corner patterns, not the full C(18,9))."""
    fc = codecs.make_codec("msr_9_16", "numpy")
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (fc.k, 2 * fc.alpha), dtype=np.uint8)
    full = fc.encode(data)
    for lost in (tuple(range(9)), tuple(range(9, 18)),
                 tuple(range(0, 18, 2))):
        shards = {i: full[i] for i in range(fc.n) if i not in lost}
        out = fc.reconstruct(shards, list(lost))
        for s in lost:
            assert np.array_equal(out[s], full[s]), (lost, s)


def test_msr_repair_moves_d_over_k_alpha_of_naive():
    """Regenerating repair: every helper ships exactly ONE combined
    sub-row (1/alpha of its shard), total d/alpha shard-equivalents =
    0.222x the naive k-shard copy, and the rebuilt node is
    byte-identical."""
    code = msr.get_code(9, 16)
    fc = msr.MSRFileCodec(codecs._NumpyShell(code), code)
    rng = np.random.default_rng(11)
    L = 4 * code.alpha
    data = rng.integers(0, 256, (fc.k, L), dtype=np.uint8)
    full = fc.encode(data)
    assert code.repair_ratio() == pytest.approx(16 / 72)
    assert code.repair_ratio() < 0.334  # under the reduced-read RS floor
    for lost in (0, 8, 17):
        helpers = [i for i in range(fc.n) if i != lost][: code.d]
        coeff = code.repair_coeff(lost)
        sent = []
        for h in helpers:
            sub = msr.interleave_split(full[h][None, :], 1, code.alpha)
            sent.append(gf.gf_matmul(coeff, sub)[0])
        moved = sum(r.nbytes for r in sent)
        assert moved == code.d * L // code.alpha  # beta=1: one sub-row each
        assert moved / (fc.k * L) == pytest.approx(code.repair_ratio())
        R = code.repair_matrix(lost, helpers)
        rebuilt = msr.interleave_merge(
            gf.gf_matmul(R, np.stack(sent)), 1, code.alpha)[0]
        assert np.array_equal(rebuilt, full[lost]), lost


# ---- bounded decode-matrix cache ---------------------------------------


def test_decode_cache_is_a_bounded_lru(monkeypatch):
    """WEEDTPU_CODEC_DECODE_CACHE bounds the per-(survivors, wanted)
    matrix cache: churning loss patterns evicts oldest-first instead of
    growing without limit (the LRC/MSR key space is much larger than
    RS's)."""
    monkeypatch.setenv("WEEDTPU_CODEC_DECODE_CACHE", "4")
    from seaweedfs_tpu.ops import gfmat_jax
    codec = gfmat_jax.JaxRSCodec(lrc.get_code(10, 2, 2))
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 32), dtype=np.uint8)
    full = codec.code.encode_numpy(data)
    for lost in range(10):
        shards = {i: full[i] for i in range(codec.code.n) if i != lost}
        out = codec.reconstruct(shards, [lost])
        assert np.array_equal(np.asarray(out[lost]), full[lost])
        assert len(codec._decode_cache) <= 4
    # the LRU keeps the most recent patterns, so a repeat is a hit
    before = len(codec._decode_cache)
    codec.reconstruct({i: full[i] for i in range(14) if i != 9}, [9])
    assert len(codec._decode_cache) == before


# ---- the batched EC read engine, per family ----------------------------


def _make_ec(tmp_path, codec_tag, large=LARGE, small=SMALL, n=40, seed=5):
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(seed)
    blobs = {}
    for i in range(1, n + 1):
        size = int(rng.integers(1, 4000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=large, small_block=small,
                            batch_size=small * 10, codec_tag=codec_tag)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, blobs


@pytest.mark.parametrize("tag,losses,blocks", [
    ("lrc_10_2_2", (2, 5, 11), (LARGE, SMALL)),   # 2 losses in group 1
    ("msr_9_16", (0, 13), (8000, 400)),           # alpha-friendly blocks
])
def test_degraded_read_byte_identity_per_family(tmp_path, monkeypatch,
                                                tag, losses, blocks):
    """Ragged needle tails, deleted shards, batched engine: every blob
    reads back byte-identical under each non-RS family, and the volume
    self-identifies its codec from the .vif."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, blobs = _make_ec(tmp_path, tag, large=blocks[0],
                           small=blocks[1])
    spec = codecs.parse_tag(tag)
    assert os.path.exists(base + layout.to_ext(spec.n - 1))
    for sid in losses:
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    try:
        assert ev.codec_tag == tag  # identity from the .vif sidecar
        for nid, data in blobs.items():
            assert ev.read_needle(nid).data == data, nid
    finally:
        ev.close()


def test_lrc_degraded_read_touches_one_local_group(tmp_path, monkeypatch):
    """ACCEPTANCE: an LRC single-shard degraded read gathers survivors
    from exactly one local group — at most r+1 distinct shards, all of
    them members of the lost shard's group — instead of RS's k-wide
    fan-in."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, blobs = _make_ec(tmp_path, "lrc_10_2_2")
    lost = 2
    os.remove(base + layout.to_ext(lost))
    code = lrc.get_code(10, 2, 2)
    ev = ec_volume.EcVolume(base)
    gathered: list[set[int]] = []
    orig = ev._gather_survivors

    def spy(exclude, segs, shard_reader, want=None, need=None):
        rows = orig(exclude, segs, shard_reader, want=want, need=need)
        gathered.append(set(rows))
        return rows

    ev._gather_survivors = spy
    try:
        for nid, data in blobs.items():
            assert ev.read_needle(nid).data == data, nid
    finally:
        ev.close()
    assert gathered, "no degraded read exercised the gather path"
    members = set(code.group_members(code.group_of(lost)))
    for got in gathered:
        assert len(got) <= code.r + 1, got
        assert got <= members, f"read left group {members}: {got}"


# ---- /admin/ec/partial: the alpha sub-row protocol ---------------------


def test_ec_partial_alpha_sub_rows(tmp_path):
    """A helper serving an MSR repair ships combined SUB-ROWS: virtual
    sid f*alpha+row selects column `row` of the file's [size, alpha]
    de-interleave, and the coeff combines across files — one pread per
    distinct file, alpha-accurate bytes out."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    a = 8
    rng = np.random.default_rng(21)
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18997)
    try:
        base = os.path.join(str(tmp_path), "9")
        size = 512  # sub-row bytes; file length = size * alpha
        files = {}
        for fid in (3, 5):
            files[fid] = rng.integers(0, 256, size * a, dtype=np.uint8)
            with open(base + layout.to_ext(fid), "wb") as f:
                f.write(files[fid].tobytes())
        with open(base + ".ecx", "wb") as f:
            f.write(b"")
        ec_files.write_vif(base, size * a * 9, codec="msr_9_16")
        vs.store.locations[0].load_existing()
        assert vs.store.get_ec_volume(9) is not None

        # virtual rows: sub-rows 0 and 2 of file 3, sub-row 7 of file 5
        sids = [3 * a + 0, 3 * a + 2, 5 * a + 7]
        coeff = rng.integers(1, 256, (1, len(sids)), dtype=np.uint8)
        body = {"volume": 9, "shards": sids, "offset": 0, "size": size,
                "alpha": a, "coeff": coeff.tolist()}

        async def _json():
            return body
        req = types.SimpleNamespace(json=_json)
        resp = asyncio.run(vs.handle_ec_partial(req))
        assert resp.status == 200, resp.text
        got = np.frombuffer(resp.body, np.uint8)

        rows = np.stack([files[s // a].reshape(size, a)[:, s % a]
                         for s in sids])
        assert np.array_equal(got, gf.gf_matmul(coeff, rows)[0])

        # a whole-shard request (alpha absent) still works on the same
        # files — old fetchers keep working against new helpers
        body2 = {"volume": 9, "shards": [3], "offset": 0,
                 "size": size * a, "coeff": [[1]]}

        async def _json2():
            return body2
        resp2 = asyncio.run(vs.handle_ec_partial(
            types.SimpleNamespace(json=_json2)))
        assert resp2.status == 200
        assert np.array_equal(np.frombuffer(resp2.body, np.uint8),
                              files[3])
    finally:
        vs.store.close()


def test_ec_rebuild_500_surfaces_replan_story(tmp_path, monkeypatch):
    """When reduced-path re-planning exhausts its substitutes the 500
    body must carry the replan story — which helper died, its shards,
    and how many replans were burned — not a bare error string (the
    master's fallback-to-naive decision reads these)."""
    from seaweedfs_tpu.ops import regen
    from seaweedfs_tpu.server.volume_server import VolumeServer
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18996)
    try:
        base = os.path.join(str(tmp_path), "4")
        with open(base + ".ec00", "wb") as f:
            f.write(b"\0" * 64)

        def boom(*a, **kw):
            stats = kw.get("stats")
            if stats is not None:
                stats["replans"] = 3
                stats["dead_helpers"] = ["a:1", "b:2", "a:1"]
            raise regen.HelperDied("a:1", (7, 8))

        monkeypatch.setattr(ec_files, "rebuild_ec_reduced", boom)
        body = {"volume": 4, "reduced":
                {"lost": [7], "groups": [{"node": "a:1", "shards": [7]}]}}

        async def _json():
            return body
        resp = asyncio.run(vs.handle_ec_rebuild(
            types.SimpleNamespace(json=_json)))
        assert resp.status == 500
        import json as _json_mod
        out = _json_mod.loads(resp.body)
        assert out["helper"] == "a:1"
        assert out["helper_shards"] == [7, 8]
        assert out["replans"] == 3
        assert out["dead_helpers"] == ["a:1", "b:2", "a:1"]
    finally:
        vs.store.close()


# ---- autopilot codec_select --------------------------------------------


def _codec_ledger(codec="rs_10_4", state="healthy", n=14):
    locs = {str(s): ["n1:80"] for s in range(n)}
    return {"kind": "ec", "state": state, "codec": codec,
            "collection": "", "shard_locations": locs}


def test_codec_select_bands(monkeypatch):
    """Hot EC volumes plan a recode to LRC, sustained-cold ones to MSR,
    the warm middle band is left alone, and unhealthy volumes heal
    first."""
    monkeypatch.setenv("WEEDTPU_AUTOPILOT", "plan")
    from tests.test_autopilot import _StubMaster
    from seaweedfs_tpu.maintenance.autopilot import Autopilot
    ledger = {1: dict(_codec_ledger(), vid=1),          # hot -> lrc
              2: dict(_codec_ledger(), vid=2),          # cold -> msr
              3: dict(_codec_ledger(), vid=3),          # warm: keep
              4: dict(_codec_ledger(state="degraded"), vid=4),
              5: dict(_codec_ledger(codec="lrc_10_2_2"), vid=5)}  # hot, lrc
    ap = Autopilot(_StubMaster(ledger=ledger), hot_rps=5.0, hot_s=120.0,
                   cold_rps=0.2, cold_s=0.0, cooldown_s=0.0)
    now = time.time()
    heat = {1: {"rps": 50.0, "sustained_s": 500.0},
            3: {"rps": 1.0, "sustained_s": 0.0},
            5: {"rps": 50.0, "sustained_s": 500.0}}
    plans = {p["vid"]: p for p in
             ap._plan_codec_select(now, heat, ledger)}
    assert plans[1]["to_codec"] == "lrc_10_2_2"
    assert plans[1]["from_codec"] == "rs_10_4"
    assert plans[1]["reason"]["band"] == "hot"
    assert plans[2]["to_codec"] == "msr_9_16"
    assert plans[2]["reason"]["band"] == "cold"
    assert 3 not in plans and 4 not in plans
    assert 5 not in plans  # already the right family for its band


def test_codec_select_cold_clock_resets_on_warm_sighting(monkeypatch):
    monkeypatch.setenv("WEEDTPU_AUTOPILOT", "plan")
    from tests.test_autopilot import _StubMaster
    from seaweedfs_tpu.maintenance.autopilot import Autopilot
    ledger = {7: dict(_codec_ledger(), vid=7)}
    ap = Autopilot(_StubMaster(ledger=ledger), cold_rps=0.5, cold_s=30.0,
                   cooldown_s=0.0)
    now = time.time()
    assert ap._plan_codec_select(now, {}, ledger) == []
    assert 7 in ap._codec_cold_since  # clock armed, not sustained
    warm = {7: {"rps": 2.0, "sustained_s": 5.0}}
    assert ap._plan_codec_select(now, warm, ledger) == []
    assert 7 not in ap._codec_cold_since  # warm sighting RESETS it
    assert ap._plan_codec_select(now, {}, ledger) == []
    ap._codec_cold_since[7] -= 31.0
    plans = ap._plan_codec_select(now, {}, ledger)
    assert [p["to_codec"] for p in plans] == ["msr_9_16"]
    assert plans[0]["reason"]["cold_for_s"] >= 30.0


def test_codec_select_plan_only_executes_nothing(monkeypatch):
    """ACCEPTANCE: a full tick in the default plan mode emits typed
    codec_select plans and performs ZERO actuator calls."""
    monkeypatch.setenv("WEEDTPU_AUTOPILOT", "plan")
    from tests.test_autopilot import _StubMaster, _tick
    from seaweedfs_tpu.maintenance.autopilot import Autopilot
    ledger = {2: dict(_codec_ledger(), vid=2)}
    m = _StubMaster(ledger=ledger, heat={})
    ap = Autopilot(m, cold_rps=0.2, cold_s=0.0, cooldown_s=0.0)
    plans = _tick(ap)
    sel = [p for p in plans if p["policy"] == "codec_select"]
    assert len(sel) == 1
    assert sel[0]["from_codec"] == "rs_10_4"
    assert sel[0]["to_codec"] == "msr_9_16"
    assert sel[0]["state"] == "planned" and sel[0]["node"] == "n1:80"
    assert ap.actuator_calls == 0
    assert m.convert.enqueued == []
    # a second tick re-plans nothing (the vid has a live plan)
    assert [p for p in _tick(ap) if p["policy"] == "codec_select"] == []
    assert ap.actuator_calls == 0


def test_codec_select_spread_volume_is_counted_not_silent(monkeypatch):
    """No node holds k+ shards: the recode cannot run (no consolidation
    actuator yet), so the skip is COUNTED in status(), not silent."""
    monkeypatch.setenv("WEEDTPU_AUTOPILOT", "plan")
    from tests.test_autopilot import _StubMaster
    from seaweedfs_tpu.maintenance.autopilot import Autopilot
    spread = dict(_codec_ledger(), vid=9)
    spread["shard_locations"] = {str(s): [f"n{s}:80"] for s in range(14)}
    ledger = {9: spread}
    ap = Autopilot(_StubMaster(ledger=ledger), cold_rps=0.2, cold_s=0.0,
                   cooldown_s=0.0)
    assert ap._plan_codec_select(time.time(), {}, ledger) == []
    assert ap.recode_blocked_spread == 1
    assert ap.status()["recode_blocked_spread"] == 1


# ---- shell: ec.codecs --------------------------------------------------


def test_ec_codecs_command_lists_family(monkeypatch):
    from seaweedfs_tpu.shell.commands import COMMANDS, CommandEnv

    class _Env:
        def master_get(self, path):
            return {"volumes": {"1": {"kind": "ec", "codec": "lrc_10_2_2"},
                                "2": {"kind": "ec", "codec": "msr_9_16"},
                                "3": {"kind": "ec"}}}
    out = io.StringIO()
    COMMANDS["ec.codecs"](_Env(), ["-json"], out)
    import json as _json_mod
    got = _json_mod.loads(out.getvalue())
    assert {c["tag"] for c in got["codecs"]} == \
        {"rs_10_4", "lrc_10_2_2", "msr_9_16"}
    assert got["mix"] == {"lrc_10_2_2": 1, "msr_9_16": 1, "rs_10_4": 1}
    assert got["default"] == codecs.default_tag()


# ---- cluster layer: chaos cells per codec ------------------------------


def test_lrc_group_loss_heals_clean(tmp_path, monkeypatch):
    """Chaos cell: an LRC volume loses a whole local-group slice (a
    data shard AND its group's local parity — the local-repair path is
    dead, global decode must carry the heal).  The cluster heals to
    byte-identical readback and a clean fsck."""
    from seaweedfs_tpu.maintenance import chaos, faults
    from seaweedfs_tpu.maintenance.chaos import (ChaosCluster, WORKLOADS,
                                                 encode_all_volumes,
                                                 fsck_report,
                                                 heal_until_clean)
    monkeypatch.setenv("WEEDTPU_CODEC_DEFAULT", "lrc_10_2_2")
    code = lrc.get_code(10, 2, 2)
    c = ChaosCluster(tmp_path, n_volume_servers=2, with_filer=True)
    c.start()
    try:
        c.wait_heartbeats()
        state = WORKLOADS["degraded_read"][0](c)
        encode_all_volumes(c)
        # kill one group-0 data shard AND the group's local parity (10),
        # cluster-wide: two losses in ONE group — local repair is dead,
        # the heal must decode through the global parities.  Never more
        # than two (the fan-out would exceed LRC's tolerance of 3).
        doomed: dict[int, set[int]] = {}
        for vs in c.volume_servers:
            for vid in chaos._ec_vids_on(vs):
                ev = vs.store.get_ec_volume(vid)
                assert ev.codec_tag == "lrc_10_2_2"
                held = set(ev.shard_ids())
                kill = doomed.setdefault(vid, set())
                if code.k in held and code.k not in kill \
                        and len(kill) < 2:
                    kill.add(code.k)  # shard 10: group 0's local parity
                    faults.delete_shard(vs.store, vid, code.k)
                data = sorted(held & set(range(code.r)))
                if data and len(kill) < 2:
                    kill.add(data[0])
                    faults.delete_shard(vs.store, vid, data[0])
            c.submit(vs._heartbeat_once())
        assert any(len(k) == 2 for k in doomed.values()), doomed
        import time as _t
        _t.sleep(2 * c.heartbeat_interval + 0.2)
        heal_until_clean(c)
        WORKLOADS["degraded_read"][1](c, state)  # byte-identical
        rep = fsck_report(c)
        assert rep.get("ok") is True, rep.get("states")
    finally:
        c.stop()


def test_msr_helper_death_mid_repair(tmp_path, monkeypatch):
    """Chaos cell: an MSR-coded cluster loses shards, the regenerating
    repair launches, and a helper node dies mid-fetch.  The repair
    re-plans around the corpse (tmp+rename: no partial shard may
    survive), readback is byte-identical, fsck is clean."""
    from seaweedfs_tpu.maintenance.chaos import ChaosCluster, run_scenario
    monkeypatch.setenv("WEEDTPU_CODEC_DEFAULT", "msr_9_16")
    c = ChaosCluster(tmp_path, n_volume_servers=2, with_filer=True)
    c.start()
    try:
        c.wait_heartbeats()
        report = run_scenario(c, "degraded_read",
                              "helper_death_mid_rebuild")
        assert report["fault"] == "helper_death_mid_rebuild"
    finally:
        c.stop()


def test_mixed_codec_cluster_heal_and_fsck(tmp_path):
    """ACCEPTANCE: volumes carrying DIFFERENT codecs coexist on one
    cluster — each volume is encoded with its own family via
    `ec.encode -codec`, one shard of every volume dies, the heal
    converges per-codec, readback is byte-identical, fsck ends clean,
    and the master's perf report shows the codec mix."""
    import json as _json_mod
    from seaweedfs_tpu.maintenance import chaos, faults
    from seaweedfs_tpu.maintenance.chaos import (ChaosCluster, WORKLOADS,
                                                 fsck_report,
                                                 heal_until_clean)
    from seaweedfs_tpu.shell.commands import run_command
    c = ChaosCluster(tmp_path, n_volume_servers=2, with_filer=True)
    c.start()
    try:
        c.wait_heartbeats()
        state = WORKLOADS["degraded_read"][0](c)
        # two extra collections force extra volumes so all THREE codec
        # families actually coexist on the cluster
        import hashlib as _hl
        rng = np.random.default_rng(0x3C0)
        client = c.client()
        extra = {}
        for col in ("mixa", "mixb"):
            for i in range(6):
                data = rng.integers(0, 256, int(rng.integers(2000, 30000)),
                                    dtype=np.uint8).tobytes()
                fid = client.upload(data, name=f"{col}{i}.bin",
                                    collection=col)
                extra[fid] = _hl.sha256(data).hexdigest()
        with c.leader().topo._lock:
            vols = sorted({(vid, v.collection)
                           for node in c.leader().topo.nodes.values()
                           for vid, v in node.volumes.items()})
        assert len(vols) >= 3, vols
        rotation = ["lrc_10_2_2", "msr_9_16", "rs_10_4"]
        env = c.shell_env()
        out = io.StringIO()
        run_command(env, "lock", out)
        try:
            for i, (vid, col) in enumerate(vols):
                cmd = (f"ec.encode -volumeId {vid} "
                       f"-codec {rotation[i % 3]}")
                if col:
                    cmd += f" -collection {col}"
                run_command(env, cmd, out)
        finally:
            run_command(env, "unlock", out)
        import time as _t
        _t.sleep(2 * c.heartbeat_interval + 0.2)

        # every volume reports its own codec tag in the heartbeat
        want = {vid: rotation[i % 3] for i, (vid, _) in enumerate(vols)}
        seen = {}
        for vs in c.volume_servers:
            for vid in chaos._ec_vids_on(vs):
                ev = vs.store.get_ec_volume(vid)
                seen[vid] = ev.codec_tag
                faults.delete_shard(vs.store, vid, ev.shard_ids()[0])
            c.submit(vs._heartbeat_once())
        for vid, tag in seen.items():
            assert tag == want[vid], (vid, tag, want[vid])
        _t.sleep(2 * c.heartbeat_interval + 0.2)

        heal_until_clean(c)
        WORKLOADS["degraded_read"][1](c, state)  # byte-identical
        for fid, digest in extra.items():
            assert _hl.sha256(client.download(fid)).hexdigest() == digest
        rep = fsck_report(c)
        assert rep.get("ok") is True, rep.get("states")
        # fsck -json rows carry the per-volume codec tag...
        tagged = 0
        for vid_s, rec in rep.get("volumes", {}).items():
            if int(vid_s) in want and \
                    (rec.get("health") or {}).get("kind") == "ec":
                assert rec.get("codec") == want[int(vid_s)], (vid_s, rec)
                tagged += 1
        assert tagged == len(want), rep.get("volumes")
        # ...and the master's perf report aggregates the mix
        perf = c.leader().collect_perf()
        mix = perf.get("codecs", {}).get("mix", {})
        assert set(mix) == {want[v] for v in want}, mix
    finally:
        c.stop()
