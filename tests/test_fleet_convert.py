"""Fleet conversion: the interleaved multi-volume device-resident encode
stream (ops/fleet_convert), its clean-abort contract, and the master-side
paced scheduler (maintenance/convert)."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.maintenance import faults
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import fleet_convert
from seaweedfs_tpu.stats import netflow
from seaweedfs_tpu.storage.ec import ec_files, layout


def _make_volumes(tmp_path, sizes, seed=7):
    rng = np.random.default_rng(seed)
    bases, payloads = [], []
    for i, sz in enumerate(sizes):
        base = str(tmp_path / f"{i + 1}")
        data = rng.integers(0, 256, sz, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            f.write(data)
        bases.append(base)
        payloads.append(data)
    return bases, payloads


def _shard_bytes(base):
    out = {}
    for i in range(layout.TOTAL_SHARDS):
        p = base + layout.to_ext(i)
        if os.path.exists(p):
            with open(p, "rb") as f:
                out[i] = f.read()
    return out


def test_convert_volumes_byte_identity(tmp_path, unit_mesh):
    """Interleaved fleet conversion over the unit-sharded CPU mesh is
    byte-identical to an independent numpy-codec write_ec_files run for
    every volume — ragged tails included — and commits .vif sidecars."""
    sizes = [200_000, 137_777, 95_001]
    bases, payloads = _make_volumes(tmp_path, sizes)
    from seaweedfs_tpu.parallel import mesh as pmesh
    codec = pmesh.FleetUnitEncoder(rs.get_code(10, 4), unit_mesh)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        codec=codec, stats=stats)
    assert rep["bytes"] == sum(sizes)
    assert stats["mode"] == "fleet" and stats["unit_batch"] % 8 == 0
    for base, data in zip(bases, payloads):
        ref = str(tmp_path / ("ref_" + os.path.basename(base)))
        with open(ref + ".dat", "wb") as f:
            f.write(data)
        os.environ["WEEDTPU_EC_CODEC"] = "numpy"
        try:
            ec_files.write_ec_files(ref, large_block=10_000,
                                    small_block=100)
        finally:
            del os.environ["WEEDTPU_EC_CODEC"]
        got, want = _shard_bytes(base), _shard_bytes(ref)
        assert sorted(got) == list(range(layout.TOTAL_SHARDS))
        for i in range(layout.TOTAL_SHARDS):
            assert got[i] == want[i], (base, i)
        assert ec_files.read_vif(base)["dat_file_size"] == len(data)


def _reference_shards(tmp_path, base, data):
    """The volume's shard files under the plain numpy RS code
    (models/rs), written by the single-volume engine."""
    ref = str(tmp_path / ("ref_" + os.path.basename(base)))
    with open(ref + ".dat", "wb") as f:
        f.write(data)
    os.environ["WEEDTPU_EC_CODEC"] = "numpy"
    try:
        ec_files.write_ec_files(ref, large_block=10_000, small_block=100)
    finally:
        del os.environ["WEEDTPU_EC_CODEC"]
    return _shard_bytes(ref)


# sizes of the volumes of one run over the 8-device mesh.  Rows are 10
# blocks of 100 bytes, a unit up to ten of them (batch_size 1000), and a
# volume over 100,000 bytes starts with a large-block row cut in columns
# of ten 1000-byte pieces: three unit shapes and the short last units.
SPAN_CASES = {
    "unequal_sizes_mixed_shapes": [200_000, 137_777, 95_001, 4_000, 23_456],
    "ends_on_a_row_boundary": [50_000],
    "ends_inside_a_row": [50_001],
    "shorter_than_one_stripe_row": [37],
    "an_empty_volume_among_others": [0, 12_345],
    "more_volumes_than_devices": [10_000 + 1000 * i for i in range(11)],
    "fewer_volumes_than_devices": [30_000, 30_500, 29_999],
}


@pytest.mark.parametrize("sizes", list(SPAN_CASES.values()),
                         ids=list(SPAN_CASES))
def test_convert_spans_byte_identity(tmp_path, unit_mesh, monkeypatch,
                                     sizes):
    """A codec that lays a unit out on the device (FleetUnitEncoder) gets
    every unit as spans of the `.dat` map, put 1-D a device: shard files
    byte for byte the plain RS code's, `rows_staged` one for each volume
    that ends inside a stripe row and nothing else, no [U, k, W] staging
    buffer, batches of one shape with empty slots where they close short,
    and a zero unit's parity never copied back."""
    from seaweedfs_tpu.parallel import mesh as pmesh
    bases, payloads = _make_volumes(tmp_path, sizes)
    codec = pmesh.FleetUnitEncoder(rs.get_code(10, 4), unit_mesh)
    batches, staging = [], []
    orig_dispatch, orig_empty = fleet_convert.dispatch_parity_batch, np.empty

    def dispatch(codec, units, **kw):
        batches.append((units, kw["stripes"]))
        return orig_dispatch(codec, units, **kw)

    def empty(shape, *a, **kw):
        if np.ndim(shape) and len(shape) == 3:
            staging.append(shape)
        return orig_empty(shape, *a, **kw)

    monkeypatch.setattr(fleet_convert, "dispatch_parity_batch", dispatch)
    monkeypatch.setattr(np, "empty", empty)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        codec=codec, stats=stats)
    monkeypatch.undo()
    assert rep["bytes"] == sum(sizes)
    for base, data in zip(bases, payloads):
        got, want = _shard_bytes(base), _reference_shards(tmp_path, base,
                                                           data)
        assert sorted(got) == list(range(layout.TOTAL_SHARDS))
        for i in range(layout.TOTAL_SHARDS):
            assert got[i] == want[i], (base, i)
        assert ec_files.read_vif(base)["dat_file_size"] == len(data)
    assert stats["rows_staged"] == sum(1 for n in sizes if n % 1000)
    assert not staging
    assert stats["unit_batch"] == 8
    occupied = 0
    for units, stripes in batches:
        assert isinstance(units, list) and len(units) == 8
        shapes = {tuple(map(len, u)) for u in units if u is not None}
        assert len(shapes) == 1  # one program a batch
        assert all(p.ndim == 1 for u in units if u is not None for p in u)
        # whole 100-byte rows, or one large-block row's 1000-byte columns
        assert sum(shapes.pop()) in (stripes * 1000, stripes * 10_000)
        occupied += sum(u is not None for u in units)
    assert occupied == rep["units"] == stats["units"]
    if any(sizes):
        assert rep["devices"] == min(8, max(
            sum(u is not None for u in units) for units, _ in batches))


def test_span_batch_parity_runs_and_empty_slots(unit_mesh):
    """A batch of spans through the seam: each occupied slot's parity is
    m contiguous 1-D runs on the slot's own device, an empty slot gives
    None and is never yielded."""
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.parallel import mesh as pmesh
    code = rs.get_code(10, 4)
    enc = pmesh.FleetUnitEncoder(code, unit_mesh)
    rng = np.random.default_rng(3)
    flat = [rng.integers(0, 256, 3 * 10 * 64, dtype=np.uint8)
            for _ in range(5)]
    parity = dispatch.dispatch_parity_batch(
        enc, [[f] for f in flat] + [None] * 3, stripes=3)
    assert [runs is None for runs in parity] == [False] * 5 + [True] * 3
    assert dispatch.parity_devices(parity) == 5
    blocks = list(dispatch.unit_parity_shards(parity))
    assert [(a, b) for a, b, _ in blocks] == [(s, s + 1) for s in range(5)]
    for (_, _, (runs,)), f in zip(blocks, flat):
        want = code.encode_numpy(
            f.reshape(3, 10, 64).transpose(1, 0, 2).reshape(10, -1))[10:]
        assert len(runs) == 4 and all(r.ndim == 1 for r in runs)
        assert np.array_equal(np.stack(runs), want)


# ---- the stream under the volumes' code ----------------------------------
# k, the shard files a volume and the sub-rows a file come from the codec:
# every tag a single volume's encode carries, on the mesh encoder, on a host
# codec and on a one-device device codec: units as spans of the maps under
# each, a batch one mesh program on the mesh and a dispatch a unit else.

# block sizes that msr_9_16's eight sub-rows a file divide; a unit is up
# to eight small rows, a large block is cut in sixteen columns
LARGE, SMALL, BATCH = 16_384, 128, 1024
TAGS = ["rs_10_4", "rs_6_3", "lrc_12_2_2", "msr_9_16"]


def _model_encode(tag: str):
    """The plain reference's encode of the tag's code: [k, L] -> [n, L]."""
    from seaweedfs_tpu.models import lrc as lrc_model, msr as msr_model
    from seaweedfs_tpu.ops import codecs
    spec = codecs.parse_tag(tag)
    if spec.family == "msr":
        assert spec.params == (9, 16)
        return msr_model.encode
    if spec.family == "lrc":
        assert spec.params == (12, 2, 2)
        return lrc_model.encode
    return rs.get_code(spec.k, spec.m).encode_numpy


def _model_files(tag: str, raw: bytes) -> list[bytes]:
    """The shard files `raw` must convert to: upstream's row-major
    striping k wide (large rows while more than one large row's bytes
    remain, then small rows, the last zero-padded), by hand, under the
    model's reference encode."""
    from seaweedfs_tpu.ops import codecs
    k = codecs.parse_tag(tag).k
    files = [bytearray() for _ in range(k)]
    at = 0
    while len(raw) - at > k * LARGE:
        for j in range(k):
            files[j] += raw[at:at + LARGE]
            at += LARGE
    while at < len(raw):
        for j in range(k):
            files[j] += raw[at:at + SMALL].ljust(SMALL, b"\0")
            at += SMALL
    if not files[0]:
        return [b""] * codecs.parse_tag(tag).n
    return [f.tobytes() for f in _model_encode(tag)(np.array(
        [np.frombuffer(bytes(f), dtype=np.uint8) for f in files]))]


def _files_of(base: str, n: int) -> list[bytes]:
    out = []
    for i in range(n):
        with open(base + layout.to_ext(i), "rb") as f:
            out.append(f.read())
    assert not os.path.exists(base + layout.to_ext(n))
    return out


def _single_volume_files(tmp_path, base: str, raw: bytes, tag: str,
                         monkeypatch) -> list[bytes]:
    """What `write_ec_files(..., codec_tag=tag)` leaves of the same
    `.dat`, under the numpy codec."""
    from seaweedfs_tpu.ops import codecs
    ref = str(tmp_path / ("single_" + os.path.basename(base)))
    with open(ref + ".dat", "wb") as f:
        f.write(raw)
    with monkeypatch.context() as m:
        m.setenv("WEEDTPU_EC_CODEC", "numpy")
        ec_files.write_ec_files(ref, large_block=LARGE, small_block=SMALL,
                                batch_size=BATCH, codec_tag=tag)
    assert ec_files.read_vif(ref)["codec"] == tag
    return _files_of(ref, codecs.parse_tag(tag).n)


def _tag_sizes(tag: str) -> list[int]:
    """Unequal volumes: one with a large row, one that ends inside a
    stripe row, one that ends on a row boundary, one inside its first
    row."""
    from seaweedfs_tpu.ops import codecs
    k = codecs.parse_tag(tag).k
    return [k * LARGE + 5 * k * SMALL + 777, 137_777, 11 * k * SMALL, 37]


@pytest.mark.parametrize("kind", ["fleet", "numpy", "jax"])
@pytest.mark.parametrize("tag", TAGS)
def test_convert_under_the_tag_byte_identity(tmp_path, monkeypatch, tag,
                                             kind):
    """`convert_volumes(..., codec_tag=tag)`: every shard file of every
    volume equals, byte for byte, the file `write_ec_files(...,
    codec_tag=tag)` leaves and the model's reference encode; n files a
    volume, the tag in the `.vif`, the code on the job's stats."""
    from seaweedfs_tpu.ops import codecs
    spec = codecs.parse_tag(tag)
    sizes = _tag_sizes(tag)
    bases, payloads = _make_volumes(tmp_path, sizes, seed=34)
    monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", kind)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=LARGE, small_block=SMALL, batch_size=BATCH,
        codec_tag=tag, stats=stats)
    assert rep["bytes"] == sum(sizes)
    assert (stats["codec"], stats["shard_files"], stats["alpha"]) == \
        (tag, spec.n, spec.alpha)
    spans = kind == "fleet"
    if kind != "numpy":  # numpy: the bare RS code, or a shell round another
        assert stats["backend"] == {"fleet": "FleetUnitEncoder",
                                    "jax": "JaxRSCodec"}[kind]
    for base, raw in zip(bases, payloads):
        got = _files_of(base, spec.n)
        assert got == _single_volume_files(tmp_path, base, raw, tag,
                                           monkeypatch), base
        assert got == _model_files(tag, raw), base
        assert ec_files.read_vif(base) == {
            "version": ec_files.read_vif(base)["version"],
            "dat_file_size": len(raw), "codec": tag,
            "large_block_bytes": LARGE, "small_block_bytes": SMALL}
    # only a volume's last, short row is copied on the host, but under
    # the numpy shells, which build every unit of more than one span or
    # row into a [k, W] array (dispatch._unstriped)
    assert stats["rows_staged"] == sum(1 for n in sizes
                                       if n % (spec.k * SMALL)) + (
        _unstriped_rows(sizes, spec.k) if kind == "numpy" else 0)
    if spans:
        assert rep["devices"] > 1
    else:
        assert rep["devices"] == {"jax": 1, "numpy": 0}[kind]
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def _unstriped_rows(sizes, k: int) -> int:
    """The stripe rows a codec without a span apply has copied into `[k,
    W]` arrays: every row of a unit of more than one span or row."""
    n = 0
    for size in sizes:
        dat = np.zeros(size, dtype=np.uint8)
        for row_start, block, col, step, _, rows in ec_files._iter_spans(
                size, LARGE, SMALL, BATCH, k):
            views, _ = ec_files._unit_spans(dat, size, k, row_start, block,
                                            col, step, rows)
            if views and (rows > 1 or len(views) > 1):
                n += rows
    return n


@pytest.mark.parametrize("kind", ["jax", "cpp"])
@pytest.mark.parametrize("tag", ["rs_10_4", "lrc_12_2_2", "msr_9_16"])
def test_one_device_fleet_takes_spans_under_every_code(tmp_path, monkeypatch,
                                                      tag, kind):
    """A fleet conversion with no mesh (the XLA shell on one device, the
    native host shell) takes every unit as spans of the maps, each one
    `dispatch_parity` by the single-volume rule: files byte-equal to the
    plain reference, at most one row staged a volume (its last, short
    one), and a span selected for every unit that holds data."""
    if kind == "cpp":
        from seaweedfs_tpu import native
        if not native.available():
            pytest.skip("no native codec here")
    from seaweedfs_tpu.ops import codecs
    spec = codecs.parse_tag(tag)
    sizes = _tag_sizes(tag)
    bases, payloads = _make_volumes(tmp_path, sizes, seed=44)
    monkeypatch.setenv("WEEDTPU_CONVERT_CODEC", kind)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=LARGE, small_block=SMALL, batch_size=BATCH,
        codec_tag=tag, stats=stats)
    assert stats["backend"] == {"jax": "JaxRSCodec",
                                "cpp": "NativeRSCodec"}[kind]
    for base, raw in zip(bases, payloads):
        assert _files_of(base, spec.n) == _model_files(tag, raw), base
    assert stats["rows_staged"] <= len(bases)
    assert stats["rows_staged"] == sum(1 for n in sizes
                                       if n % (spec.k * SMALL))
    holding = sum(1 for size in sizes for row_start, _, col, *_ in
                  ec_files._iter_spans(size, LARGE, SMALL, BATCH, spec.k)
                  if row_start + col < size)
    assert stats["spans_mapped"] == holding
    assert rep["devices"] == (1 if kind == "jax" else 0)


def test_convert_msr_on_a_four_device_mesh(tmp_path, monkeypatch):
    """PM-MSR(9,16) over four devices, as a four-chip host has it: a unit
    is eight 9-block stripe rows put 1-D to its device, the mesh program
    splits each file's bytes into eight sub-rows ([1, 72, W / 8]) and
    gives back nine contiguous file runs; eighteen files a volume."""
    from seaweedfs_tpu.ops import codecs, msr
    from seaweedfs_tpu.parallel import mesh as pmesh
    tag = "msr_9_16"
    enc = pmesh.FleetUnitEncoder(msr.get_code(9, 16),
                                 pmesh.make_mesh(4, ("unit",)))
    codec = msr.MSRFileCodec(enc)
    assert (codec.k, codec.m, codec.alpha, enc.k, enc.m) == (9, 9, 8, 72, 72)
    assert codecs.spec_of(codec).tag == tag
    sizes = [9 * SMALL * 8 * 3, 9 * SMALL * 8 * 3 - 5, 9 * SMALL * 13 + 1,
             9 * SMALL * 8, 50_000, 50_001]
    bases, payloads = _make_volumes(tmp_path, sizes, seed=35)
    batches = []
    orig = fleet_convert.dispatch_parity_batch

    def dispatch(codec, units, **kw):
        parity = orig(codec, units, **kw)
        batches.append((units, kw["stripes"], parity))
        return parity

    monkeypatch.setattr(fleet_convert, "dispatch_parity_batch", dispatch)
    stats: dict = {}
    rep = fleet_convert.convert_volumes(
        bases, large_block=LARGE, small_block=SMALL, batch_size=BATCH,
        codec=codec, stats=stats)
    monkeypatch.undo()
    assert (stats["unit_batch"], rep["devices"]) == (4, 4)
    assert (stats["codec"], stats["shard_files"], stats["alpha"]) == \
        (tag, 18, 8)
    for base, raw in zip(bases, payloads):
        assert _files_of(base, 18) == _model_files(tag, raw), base
    assert stats["rows_staged"] == sum(1 for n in sizes if n % (9 * SMALL))
    for units, stripes, parity in batches:
        assert isinstance(units, list) and len(units) == 4
        shapes = {tuple(map(len, u)) for u in units if u is not None}
        assert len(shapes) == 1  # one program a batch
        assert sum(shapes.pop()) == stripes * 9 * SMALL
        for u, runs in zip(units, parity):
            assert (u is None) == (runs is None)
            if runs is not None:  # nine file runs, not 72 sub-rows
                assert [r.shape for r in runs] == [(stripes * SMALL,)] * 9


@pytest.mark.parametrize("kind", ["fleet", "numpy"])
def test_convert_cancel_under_msr_keeps_the_previous_set(tmp_path,
                                                         monkeypatch, kind):
    """A cancelled run under `msr_9_16` leaves the previous 18-file set
    and its `.vif` untouched, nothing of the fresh volume visible and no
    `.tmp` behind."""
    tag = "msr_9_16"
    bases, payloads = _make_volumes(tmp_path, [300_000, 280_000], seed=36)
    with monkeypatch.context() as m:
        m.setenv("WEEDTPU_EC_CODEC", "numpy")
        ec_files.write_ec_files(bases[0], large_block=LARGE,
                                small_block=SMALL, batch_size=BATCH,
                                codec_tag=tag)
    before, vif = _files_of(bases[0], 18), ec_files.read_vif(bases[0])
    assert before == _model_files(tag, payloads[0])
    calls = []

    def cancel():
        calls.append(1)
        return len(calls) > 2  # abort a couple of units in

    with pytest.raises(ec_files.EncodeCancelled):
        fleet_convert.convert_volumes(
            bases, large_block=LARGE, small_block=SMALL, batch_size=BATCH,
            cancel=cancel, codec=fleet_convert.fleet_codec(kind, tag))
    assert _files_of(bases[0], 18) == before
    assert ec_files.read_vif(bases[0]) == vif
    assert _shard_bytes(bases[1]) == {}
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_convert_books_class_convert(tmp_path):
    """The whole conversion runs under netflow class=convert, so any
    network hop made on its behalf books repair-adjacent bytes."""
    bases, _ = _make_volumes(tmp_path, [50_000])
    seen = []
    fleet_convert.convert_volumes(
        bases, large_block=10_000, small_block=100, batch_size=1000,
        progress=lambda n: seen.append(netflow.current_class()))
    assert seen and set(seen) == {"convert"}


@pytest.mark.parametrize("kind", ["fleet", "numpy"])
def test_convert_cancel_clean_abort(tmp_path, kind):
    """Cancel mid-stream: EncodeCancelled, NO partial .ecXX visible, no
    .tmp litter, and a previous valid shard set survives untouched —
    whether a batch is one mesh program (the mesh encoder) or one
    dispatch a unit (the numpy reference)."""
    bases, _ = _make_volumes(tmp_path, [300_000, 280_000], seed=9)
    # volume 0 already has a valid shard set from an earlier encode
    os.environ["WEEDTPU_EC_CODEC"] = "numpy"
    try:
        ec_files.write_ec_files(bases[0], large_block=10_000,
                                small_block=100)
    finally:
        del os.environ["WEEDTPU_EC_CODEC"]
    before = _shard_bytes(bases[0])
    calls = []

    def cancel():
        calls.append(1)
        return len(calls) > 2  # abort a couple of units in

    with pytest.raises(ec_files.EncodeCancelled):
        fleet_convert.convert_volumes(
            bases, large_block=10_000, small_block=100, batch_size=1000,
            cancel=cancel, codec=fleet_convert.fleet_codec(kind))
    # the old set is byte-identical, the fresh volume has nothing visible
    assert _shard_bytes(bases[0]) == before
    assert _shard_bytes(bases[1]) == {}
    for base in bases:
        assert not [p for p in os.listdir(tmp_path)
                    if p.endswith(".tmp")], os.listdir(tmp_path)


def test_convert_shard_write_fault_aborts(tmp_path):
    """An armed shard_write_error fault (the chaos disk-death shape)
    fails the conversion before any tmp shard exists."""
    bases, _ = _make_volumes(tmp_path, [40_000])
    faults.set_shard_write_error("EIO")
    try:
        with pytest.raises(OSError):
            fleet_convert.convert_volumes(
                bases, large_block=10_000, small_block=100,
                batch_size=1000)
    finally:
        faults.clear_net()
    assert _shard_bytes(bases[0]) == {}
    assert not [p for p in os.listdir(tmp_path) if ".ec" in p]


# -- master-side scheduler ------------------------------------------------

class _StubNode:
    def __init__(self, vids):
        self.volumes = {v: object() for v in vids}


class _StubTopo:
    def __init__(self, placement):
        import threading
        self._lock = threading.Lock()
        self.nodes = {url: _StubNode(vids)
                      for url, vids in placement.items()}


class _StubResp:
    def __init__(self, status=200, payload=None):
        self.status = status
        self._payload = payload or {}

    async def json(self):
        return self._payload

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return False


class _StubSession:
    """Records fleet_convert POSTs; `fail` raises like a dead node."""

    def __init__(self, fail=False):
        self.calls = []
        self.fail = fail

    def post(self, url, json=None, timeout=None):
        self.calls.append((url, json))
        if self.fail:
            raise OSError("connection refused")
        return _StubResp(payload={"converted": json["volumes"],
                                  "bytes": 1, "wall_s": 0.1})


class _StubAlerts:
    def __init__(self, firing=()):
        self._firing = firing

    def status(self):
        return {"rules": [{"name": n, "state": "firing"}
                          for n in self._firing]}


class _StubMaintenance:
    def __init__(self, active_nodes=None):
        self._active_nodes = dict(active_nodes or {})


class _StubMaster:
    def __init__(self, placement, firing=(), active_nodes=None,
                 fail=False):
        self.topo = _StubTopo(placement)
        self.alerts = _StubAlerts(firing)
        self.maintenance = _StubMaintenance(active_nodes)
        self._session = _StubSession(fail=fail)


def _tick(sched):
    return asyncio.run(sched.tick())


def test_scheduler_groups_paces_and_converts():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [1, 2, 3], "n2:80": [7]})
    sched = ConvertScheduler(master, rate=100.0, burst=100.0,
                             node_batch=2)
    assert sched.enqueue([1, 2, 3, 7, 7, "x"]) == [1, 2, 3, 7]
    actions = _tick(sched)
    # node_batch caps n1 at 2 volumes per call; 3 stays queued
    by_node = {a["node"]: a for a in actions}
    assert sorted(by_node) == ["n1:80", "n2:80"]
    assert by_node["n1:80"]["volumes"] == [1, 2]
    assert by_node["n1:80"]["outcome"] == "ok"
    assert sched.queued == [3] and sched.converted == 3
    assert _tick(sched)[0]["volumes"] == [3]
    assert not sched.queued and not sched.active


def test_scheduler_requeues_on_node_failure():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [5, 6]}, fail=True)
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([5, 6])
    actions = _tick(sched)
    assert actions and actions[0]["outcome"].startswith("error")
    # RE-QUEUED with backoff, never dropped
    assert sorted(sched.queued) == [5, 6]
    st = sched.status()
    assert st["backoffs"]["5"]["failures"] == 1
    # while backing off, nothing launches
    assert _tick(sched) == []
    # node recovers, backoff expires -> converted on the next tick
    master._session.fail = False
    sched._backoff = {v: (f, 0.0) for v, (f, _) in sched._backoff.items()}
    actions = _tick(sched)
    assert actions[0]["outcome"] == "ok"
    assert sched.converted == 2 and not sched.queued


def test_scheduler_pauses_on_interference_alert():
    # exact-name matching (ISSUE 14): the default pause list names the
    # actual default rules; a rule merely CONTAINING "interference"
    # must not pause (tests/test_interference.py covers that edge)
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [4]},
                         firing=("interference_high",))
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([4])
    assert _tick(sched) == []
    assert sched.status()["paused"] == "interference_high"
    assert sched.queued == [4]  # still queued, resumes when it clears
    master.alerts._firing = ()
    assert _tick(sched)[0]["outcome"] == "ok"
    assert sched.status()["paused"] is None


def test_scheduler_yields_to_active_repair_and_drops_unplaceable():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [8]}, active_nodes={"n1:80": 1})
    sched = ConvertScheduler(master, rate=100.0, burst=100.0)
    sched.enqueue([8, 99])  # 99 lives nowhere (already EC / deleted)
    assert _tick(sched) == []
    assert sched.queued == [8]  # deferred behind the repair, not lost
    assert any(h.get("outcome") == "unplaceable" and h["vid"] == 99
               for h in sched.history)
    master.maintenance._active_nodes = {}
    assert _tick(sched)[0]["outcome"] == "ok"


def test_cluster_fleet_convert_end_to_end(tmp_path):
    """Full plane: blobs land in real volumes, the master scheduler
    paces a /admin/ec/fleet_convert batch to the owning node, shard sets
    commit (all 14 + .ecx + .vif, never a partial subset), convert bytes
    book on the netflow ledger, and readback stays byte-identical."""
    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import WeedClient
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        client = WeedClient(c.master.url)
        rng = np.random.default_rng(0xFEE7)
        blobs = {}
        for i in range(12):
            data = rng.integers(0, 256, int(rng.integers(5_000, 40_000)),
                                dtype=np.uint8).tobytes()
            blobs[client.upload(data, name=f"f{i}.bin")] = data
        vs = c.volume_servers[0]
        vids = sorted({vid for loc in vs.store.locations
                       for vid in loc.volumes})
        assert vids
        for v in vids:
            vs.store.get_volume(v).nm.flush()
        recv0 = netflow.class_total("recv", "convert")
        res = c.submit(asyncio.wait_for(_enqueue_and_tick(
            c.master, vids), 60))
        assert res["accepted"] == vids
        assert all(a["outcome"] == "ok" for a in res["actions"]), res
        st = c.master.convert.status()
        assert st["converted"] == len(vids) and not st["queued"]
        for v in vids:
            base = vs.store.get_volume(v)._base
            got = _shard_bytes(base)
            assert sorted(got) == list(range(layout.TOTAL_SHARDS)), v
            assert os.path.exists(base + ".ecx")
            assert ec_files.read_vif(base) is not None
        # the orchestration hop booked as class=convert on the ledger
        assert netflow.class_total("recv", "convert") > recv0
        for fid, data in blobs.items():
            assert client.download(fid) == data
    finally:
        c.stop()


async def _enqueue_and_tick(master, vids):
    accepted = master.convert.enqueue(vids)
    actions = await master.convert.tick()
    return {"accepted": accepted, "actions": actions}


def test_fleet_convert_partial_failure_settles_freeze(tmp_path,
                                                      monkeypatch):
    """A run that dies after SOME volumes committed keeps those frozen
    read-only with their .ecx (the EC set is their copy of record) and
    thaws only the rolled-back ones — a thawed-but-committed volume
    would take writes the shard set silently lacks."""
    import urllib.request
    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.ops import fleet_convert as fc
    c = Cluster(tmp_path, n_volume_servers=1).start()
    try:
        c.wait_heartbeats()
        client = WeedClient(c.master.url)
        rng = np.random.default_rng(11)
        for i in range(10):
            client.upload(rng.integers(0, 256, 20_000,
                                       dtype=np.uint8).tobytes(),
                          name=f"x{i}.bin")
        vs = c.volume_servers[0]
        # a second volume via an assign in another collection, so the
        # batch spans a committed volume AND a rolled-back one
        with urllib.request.urlopen(
                f"http://{c.master.url}/dir/assign?collection=cx",
                timeout=10) as r:
            a = json.load(r)
        urllib.request.urlopen(urllib.request.Request(
            f"http://{a['url']}/{a['fid']}",
            data=rng.integers(0, 256, 30_000, dtype=np.uint8).tobytes(),
            method="PUT"), timeout=10).read()
        vids = sorted({vid for loc in vs.store.locations
                       for vid in loc.volumes})
        assert len(vids) >= 2
        for v in vids:
            vs.store.get_volume(v).nm.flush()

        real = fc.convert_volumes

        def first_commits_then_dies(bases, **kw):
            real(bases[:1], **kw)
            raise RuntimeError("disk died after the first commit")

        monkeypatch.setattr(fc, "convert_volumes",
                            first_commits_then_dies)
        req = urllib.request.Request(
            f"http://{vs.url}/admin/ec/fleet_convert",
            data=json.dumps({"volumes": vids}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        assert ei.value.code == 500
        committed, rest = vids[0], vids[1:]
        v0 = vs.store.get_volume(committed)
        assert v0.read_only  # stays frozen: shards are the copy of record
        assert sorted(_shard_bytes(v0._base)) == \
            list(range(layout.TOTAL_SHARDS))
        assert os.path.exists(v0._base + ".ecx")
        for vid in rest:
            v = vs.store.get_volume(vid)
            assert not v.read_only  # rolled back -> thawed, writable
            assert _shard_bytes(v._base) == {}
    finally:
        c.stop()


def test_scheduler_token_bucket_paces():
    from seaweedfs_tpu.maintenance.convert import ConvertScheduler
    master = _StubMaster({"n1:80": [1, 2, 3, 4]})
    sched = ConvertScheduler(master, rate=0.0001, burst=2.0, node_batch=4)
    sched.enqueue([1, 2, 3, 4])
    actions = _tick(sched)
    # burst grants exactly 2; the rest wait for tokens, still queued
    assert actions[0]["volumes"] == [1, 2]
    assert sorted(sched.queued) == [3, 4]
