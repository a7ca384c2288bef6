"""Reduced-read repair kernel (ops/regen.py + ec_files.rebuild_ec_reduced).

The contract under test: byte-identical output to the naive decode for
EVERY single-shard-loss pattern and helper-count d, exact repair-byte
accounting (measured helper payloads == the plan's prediction), and
helper-death-mid-transfer re-planning with a substitute survivor that
never leaves a partial shard on disk.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import gf, regen
from seaweedfs_tpu.storage.ec import ec_files, layout

CODE = rs.get_code(10, 4)
L = 10_000  # bytes per shard in the synthetic stripe


@pytest.fixture(scope="module")
def shards():
    rng = np.random.default_rng(0xEC)
    data = rng.integers(0, 256, (CODE.k, L), dtype=np.uint8)
    return CODE.encode_numpy(data)


def _groups(lost: set[int]) -> list[regen.HelperGroup]:
    """Local node holds shards 0-5, a same-rack helper 6-8, a remote-DC
    helper 9-13 (minus whatever is lost) — sized so the same-rack helper
    dying still leaves >= k survivors for a substitute plan."""
    spans = [("", range(0, 6), 0), ("a:1", range(6, 9), 1),
             ("b:2", range(9, 14), 3)]
    return [regen.HelperGroup(n, tuple(s for s in span if s not in lost),
                              loc)
            for n, span, loc in spans]


def _fetcher(shards, fetched: dict, die: dict | None = None):
    calls = {"n": 0}

    def fetch(group, sids, coeff, off, n):
        calls["n"] += 1
        if die and die.get("node") == group.node and \
                calls["n"] >= die.get("after", 1):
            raise regen.HelperDied(group.node, tuple(sids))
        rows = np.stack([shards[s][off:off + n] for s in sids])
        out = gf.gf_matmul(coeff, rows)
        fetched[group.node] = fetched.get(group.node, 0) + out.nbytes
        return out.tobytes()

    return fetch


def _repair(shards, lost: int, d=None, align=1024, batch=4096,
            die=None, groups=None, stats=None):
    fetched: dict = {}
    out = np.zeros(L, dtype=np.uint8)

    def read_local(sid, off, n):
        return shards[sid][off:off + n].tobytes()

    def sink(off, row):
        out[off:off + len(row)] = row

    plan = regen.repair_shard(
        CODE, CODE, lost, groups or _groups({lost}), L, read_local,
        _fetcher(shards, fetched, die), sink, d=d, batch_size=batch,
        align=align, stats=stats)
    return out, plan, fetched


def test_byte_identity_all_single_loss_patterns(shards):
    """Every lost-shard id 0..13 rebuilds byte-identically — the MDS
    exactness guarantee the aggregated partial decode must preserve."""
    for lost in range(layout.TOTAL_SHARDS):
        out, plan, fetched = _repair(shards, lost)
        assert np.array_equal(out, shards[lost]), f"shard {lost} differs"
        # vs the naive decode path too (not just ground truth)
        naive = CODE.reconstruct_numpy(
            {s: shards[s] for s in range(14) if s != lost}, [lost])[lost]
        assert np.array_equal(out, naive)


@pytest.mark.parametrize("d", [11, 12, 13])
def test_helper_count_sweep_reduced_reads(shards, d):
    """d > k helpers: output stays byte-identical while each remote
    helper reads only sub-shard ranges (< its full shard span)."""
    out, plan, fetched = _repair(shards, 3, d=d, align=512)
    assert np.array_equal(out, shards[3])
    assert plan.d == d
    pred = plan.predicted_bytes()
    # rotation striped the reads: no remote helper read its full span
    for node, nbytes in pred["helper_reads"].items():
        span = sum(1 for g in _groups({3}) if g.node == node
                   for _ in g.shards) * L
        assert nbytes < span, f"{node} read its whole span under d={d}"
    # network floor: at most one shard-range per remote node (a window
    # that excludes every shard of a node ships nothing for its
    # segment), well under naive
    assert 0 < pred["remote"] <= 2 * L
    assert pred["remote"] < plan.naive_remote_bytes(5)


def test_accounting_measured_equals_predicted(shards):
    """The kernel's predicted repair bandwidth IS what the fetch hop
    measures — per node, byte-exact (the /maintenance/status decision
    records depend on this)."""
    for d in (None, 11, 13):
        out, plan, fetched = _repair(shards, 7, d=d, align=512)
        assert fetched == plan.predicted_bytes()["per_node"]


def test_unaligned_length_and_tiny_ranges(shards):
    """Segment cutting must cover lengths that don't divide by the
    alignment, collapse when the range is smaller than one segment, and
    survive batch sizes larger than the range."""
    for length in (1, 511, 512, 513, 4097):
        sub = {s: shards[s][:length] for s in range(14)}
        fetched: dict = {}
        out = np.zeros(length, dtype=np.uint8)
        regen.repair_shard(
            CODE, CODE, 0, _groups({0}), length,
            lambda sid, off, n: sub[sid][off:off + n].tobytes(),
            _fetcher(sub, fetched),
            lambda off, row: out.__setitem__(
                slice(off, off + len(row)), row),
            batch_size=1 << 20, align=512)
        assert np.array_equal(out, shards[0][:length]), length


def test_helper_death_replans_with_substitute(shards):
    """A helper dying mid-transfer re-plans: the dead node leaves the
    survivor pool, a substitute covers its shards, and the rebuilt
    bytes stay identical."""
    stats: dict = {}
    out, plan, fetched = _repair(shards, 2, die={"node": "a:1",
                                                "after": 2},
                                 stats=stats)
    assert np.array_equal(out, shards[2])
    assert stats["replans"] >= 1
    assert any(dh["node"] == "a:1" for dh in stats["dead_helpers"])
    # the completed plan no longer uses the dead helper
    assert "a:1" not in plan.predicted_bytes()["per_node"]


def test_too_few_survivors_raises(shards):
    """Fewer than k survivors is a critical volume, not a plan."""
    groups = [regen.HelperGroup("", tuple(range(9)), 0)]
    with pytest.raises(ValueError, match="survivors"):
        regen.plan_repair(CODE, 13, groups, L)


def test_local_read_failure_excludes_shard(shards):
    """A local shard that reads short is excluded like a dead helper —
    the replacement plan pulls the slack from the remote pool."""
    bad = {"sid": 4}

    def read_local(sid, off, n):
        if sid == bad["sid"]:
            return None
        return shards[sid][off:off + n].tobytes()

    fetched: dict = {}
    out = np.zeros(L, dtype=np.uint8)
    stats: dict = {}
    regen.repair_shard(
        CODE, CODE, 0, _groups({0}), L, read_local,
        _fetcher(shards, fetched),
        lambda off, row: out.__setitem__(slice(off, off + len(row)), row),
        batch_size=4096, align=1024, stats=stats)
    assert np.array_equal(out, shards[0])
    assert stats["replans"] >= 1


# ---- the on-disk integration surface (ec_files.rebuild_ec_reduced) ----


def _write_shard_files(tmp_path, shards, present):
    base = str(tmp_path / "7")
    for sid in present:
        with open(base + layout.to_ext(sid), "wb") as f:
            f.write(shards[sid].tobytes())
    return base


def _remote_groups(shards, sids_by_node):
    return [{"node": node, "shards": sorted(sids), "locality": loc}
            for node, sids, loc in sids_by_node]


def _disk_fetcher(shards, fetched=None, die=None):
    calls = {"n": 0}

    def fetch(group, sids, coeff, off, n):
        calls["n"] += 1
        if die and die.get("node") == group.node and \
                calls["n"] >= die.get("after", 1):
            raise regen.HelperDied(group.node, tuple(sids))
        rows = np.stack([shards[s][off:off + n] for s in sids])
        out = gf.gf_matmul(np.asarray(coeff, dtype=np.uint8), rows)
        if fetched is not None:
            fetched[group.node] = fetched.get(group.node, 0) + out.nbytes
        return out.tobytes()

    return fetch


def test_rebuild_ec_reduced_multi_loss_sequential(tmp_path, shards,
                                                  monkeypatch):
    """Multi-shard loss repairs as sequential single-shard passes; each
    rebuilt shard joins the local survivors, files land byte-identical,
    and no .tmp residue survives."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    lost = [1, 12]
    local = [s for s in range(0, 7) if s not in lost]
    base = _write_shard_files(tmp_path, shards, local)
    groups = _remote_groups(shards, [
        ("a:1", [s for s in range(7, 11) if s not in lost], 1),
        ("b:2", [s for s in range(11, 14) if s not in lost], 3)])
    fetched: dict = {}
    result = ec_files.rebuild_ec_reduced(
        base, lost, groups, _disk_fetcher(shards, fetched),
        batch_size=4096, align=2048)
    assert result["rebuilt"] == sorted(lost)
    for sid in lost:
        with open(base + layout.to_ext(sid), "rb") as f:
            assert f.read() == shards[sid].tobytes(), sid
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    assert result["helper_bytes"] == fetched
    assert result["predicted"]["per_node"] == fetched
    # the savings the heal bench gates on: well under the naive cost
    assert result["predicted"]["remote"] <= \
        0.6 * result["predicted"]["naive_remote"]


def test_rebuild_ec_reduced_helper_death_no_partial_shard(
        tmp_path, shards, monkeypatch):
    """Helper death mid-rebuild: the pass re-plans onto the surviving
    helper; a loss that makes the plan impossible raises WITHOUT
    leaving a partial shard file behind."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base = _write_shard_files(tmp_path, shards, list(range(0, 7)))
    groups = _remote_groups(shards, [("a:1", [7, 8], 1),
                                     ("b:2", list(range(9, 13)), 3)])
    result = ec_files.rebuild_ec_reduced(
        base, [13], groups,
        _disk_fetcher(shards, die={"node": "a:1", "after": 1}),
        batch_size=4096, align=2048)
    assert result["replans"] >= 1
    assert [d["node"] for d in result["dead_helpers"]] == ["a:1"]
    with open(base + layout.to_ext(13), "rb") as f:
        assert f.read() == shards[13].tobytes()
    os.remove(base + layout.to_ext(13))

    # both helpers dead -> < k survivors -> ValueError, no partial file
    with pytest.raises(ValueError):
        ec_files.rebuild_ec_reduced(
            base, [13], groups, _always_dying_fetcher(),
            batch_size=4096, align=2048)
    assert not os.path.exists(base + layout.to_ext(13))
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def _always_dying_fetcher():
    def fetch(group, sids, coeff, off, n):
        raise regen.HelperDied(group.node, tuple(sids))
    return fetch


def test_rebuild_ec_reduced_device_codec_identity(tmp_path, shards,
                                                  monkeypatch):
    """The partial kernel rides the dispatch seam: the JAX bit-sliced
    backend produces the same bytes as the numpy path."""
    pytest.importorskip("jax")
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    base = _write_shard_files(tmp_path, shards, list(range(0, 10)))
    groups = _remote_groups(shards, [("a:1", list(range(10, 13)), 1)])
    result = ec_files.rebuild_ec_reduced(
        base, [13], groups, _disk_fetcher(shards), batch_size=4096,
        align=2048)
    assert result["rebuilt"] == [13]
    with open(base + layout.to_ext(13), "rb") as f:
        assert f.read() == shards[13].tobytes()


def test_shard_reader_locality_rank(tmp_path):
    """Serving-side locality: the volume server ranks shard locations
    with the planner's locality classes (self < same rack < other rack <
    other DC) and exposes the ranking to the EC read engine's survivor
    fan-out via shard_reader.locality_rank."""
    import time as _time

    from seaweedfs_tpu.server.volume_server import VolumeServer
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18999,
                      data_center="dc1", rack="r0")
    same_rack = {"url": "y:1", "dc": "dc1", "rack": "r0"}
    other_rack = {"url": "x:1", "dc": "dc1", "rack": "r1"}
    other_dc = {"url": "z:1", "dc": "dc2", "rack": "r0"}
    assert vs._loc_rank({"url": vs.url, "dc": "dc1", "rack": "r0"}) == 0
    assert vs._loc_rank(same_rack) == 1
    assert vs._loc_rank(other_rack) == 2
    assert vs._loc_rank(other_dc) == 3
    # labels absent on BOTH sides (pre-upgrade fleet): one rack
    vs.data_center = vs.rack = ""
    assert vs._loc_rank({"url": "q:1"}) == 1
    vs.data_center, vs.rack = "dc1", "r0"
    reader = vs._shard_reader(5)
    vs._ec_loc_cache[5] = (_time.monotonic() + 100,
                           {"3": [other_dc, same_rack],
                            "4": [other_rack]})
    assert reader.locality_rank(3) == 1  # best location wins
    assert reader.locality_rank(4) == 2
    assert reader.locality_rank(9) == 3  # unknown shard: worst class
    vs.store.close()


def test_ec_partial_rejects_oversized_shard_list(tmp_path):
    """/admin/ec/partial bounds the row stack it will pread: an
    over-long or duplicated shard list (each entry costs another `size`
    bytes of memory) is a 400, not an OOM."""
    import asyncio
    import types as _t

    from seaweedfs_tpu.server.volume_server import VolumeServer
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18998)
    try:
        def call(body):
            async def _json():
                return body
            req = _t.SimpleNamespace(json=_json)
            return asyncio.run(vs.handle_ec_partial(req)).status

        too_many = list(range(layout.TOTAL_SHARDS)) + [0]
        assert call({"volume": 7, "shards": too_many, "offset": 0,
                     "size": 4096,
                     "coeff": [[1] * len(too_many)]}) == 400
        assert call({"volume": 7, "shards": [0, 0], "offset": 0,
                     "size": 4096, "coeff": [[1, 1]]}) == 400
        # a well-formed request passes shape validation (404: the test
        # volume is simply not mounted here)
        assert call({"volume": 7, "shards": [0, 1], "offset": 0,
                     "size": 4096, "coeff": [[1, 1]]}) == 404
    finally:
        vs.store.close()


def test_gather_survivors_orders_remote_by_locality(shards, tmp_path,
                                                    monkeypatch):
    """The degraded-read survivor fan-out submits same-rack helpers
    before cross-rack ones when the reader carries a locality ranking
    (submission order == execution-start order on the shared pool)."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base = _write_shard_files(tmp_path, shards, list(range(0, 4)))
    from seaweedfs_tpu.storage.ec import ec_files as ecf
    ecf.write_vif(base, CODE.k * L, large_block=1 << 40, small_block=L)
    with open(base + ".ecx", "wb") as f:
        f.write(b"")
    from seaweedfs_tpu.storage.ec.ec_volume import EcVolume
    ev = EcVolume(base)
    try:
        order = []
        lock = __import__("threading").Lock()

        def reader(sid, off, n):
            with lock:
                order.append(sid)
            return shards[sid][off:off + n].tobytes()

        # even shards are "same rack", odd are "remote"
        reader.locality_rank = lambda sid: 1 if sid % 2 == 0 else 3
        rows = ev._gather_survivors({13}, [(0, 64)], reader)
        assert len(rows) == CODE.k
        fetched_remote = [s for s in order if s % 2]
        fetched_near = [s for s in order if s % 2 == 0]
        # all near candidates were submitted (and so fetched) first
        assert len(fetched_near) >= 4
        if fetched_remote:
            first_remote = order.index(fetched_remote[0])
            assert first_remote >= 2, order
    finally:
        ev.close()


def test_apply_matrix_backends_agree(shards):
    """dispatch.apply_matrix: host and device backends produce the same
    partial products for arbitrary coefficient slices."""
    from seaweedfs_tpu.ops import dispatch
    stack = np.stack([shards[s][:2048] for s in (0, 4, 11)])
    C = CODE.decode_matrix([0, 1, 2, 3, 4, 5, 6, 7, 8, 11], [13])[:, :3]
    want = gf.gf_matmul(C, stack)
    got_host = dispatch.apply_matrix(CODE, C, stack)
    assert np.array_equal(got_host, want)
    jax = pytest.importorskip("jax")
    del jax
    from seaweedfs_tpu.ops import gfmat_jax
    codec = gfmat_jax.get_codec(10, 4)
    got_dev = dispatch.apply_matrix(codec, C, stack)
    assert np.array_equal(got_dev, want)
    # the per-matrix device cache serves repeats
    again = dispatch.apply_matrix(codec, C, stack)
    assert np.array_equal(again, want)
