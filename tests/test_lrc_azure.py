"""Azure LRC(12,2,2) as a code a volume can be under (tag `lrc_12_2_2`).

The plain reference is `seaweedfs_tpu/models/lrc.py` (Huang et al., USENIX
ATC 2012: the generator written out, elimination over whatever survives).
Held to it here, on the CPU at small sizes: the program's code object
(decodability, exhaustively: 560 of 560 three-loss and 1,568 of 1,820
four-loss patterns, the others refused), the XLA and Pallas-interpret
codecs and the reconstruct seam at k = 6 and k = 12, the EC file engines
12 wide with 16 shard files (encode, a one-lost rebuild that opens the 6
files of one local group, the global fallback), degraded reads through
`EcVolume`, and the one codec resolution of `ops/codecs.py` with its
refusals (a tag the backend does not carry answers 400, and hangs
nothing).  On the chip the same comparison decides the benchmark cell's
`correct` (`benchmark/reference_lrc.py`).
"""

import asyncio
import itertools
import json
import os
import threading
import types

import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.models import lrc as ref
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import (codecs, dispatch, fleet_convert, gfmat_jax,
                               lrc, pallas_gf)
from seaweedfs_tpu.stats import pipeline
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume

TAG = "lrc_12_2_2"
N, K = 16, 12
GROUP0 = {0, 1, 2, 3, 4, 5, 12}   # data fragments 0-5 and their XOR
GROUP1 = {6, 7, 8, 9, 10, 11, 13}
KINDS = ["jax", "numpy"] + (["cpp"] if native.available() else [])


@pytest.fixture(scope="module")
def code():
    return lrc.get_code(12, 2, 2)


@pytest.fixture(scope="module")
def fragments():
    """[16, 700] seeded fragments by the plain reference."""
    data = np.random.default_rng(2012).integers(0, 256, (K, 700),
                                                dtype=np.uint8)
    return ref.encode(data)


# ---- the generator, and what decodes -----------------------------------


def test_generator_is_the_papers_form(code):
    assert code.tag == TAG and (code.k, code.m, code.n, code.r) == (12, 4,
                                                                    16, 6)
    assert np.array_equal(code.matrix, ref.GENERATOR)
    assert np.array_equal(code.parity_matrix, ref.PARITY)
    # every lrc_<k>_2_2 is the same form; another (l, g) keeps Cauchy rows
    ten = lrc.get_code(10, 2, 2).parity_matrix
    assert ten[2].tolist() == [1, 2, 3, 4, 5, 16, 32, 48, 64, 80]
    assert lrc.LRCCode(12, 3, 2).parity_matrix[3, 0] not in (0, 1)


@pytest.mark.parametrize("lost_n, decodable_n, of", [
    (1, 16, 16), (2, 120, 120), (3, 560, 560), (4, 1568, 1820)])
def test_exhaustive_decodability(code, fragments, lost_n, decodable_n, of):
    """Maximally recoverable: every pattern decodable in principle
    decodes, and the program refuses the others with an error, not with
    bytes."""
    patterns = list(itertools.combinations(range(N), lost_n))
    assert len(patterns) == of
    ok = [p for p in patterns if code.decodable(list(p))]
    assert len(ok) == decodable_n
    assert ok == [p for p in patterns if ref.decodable(p)]
    shell = codecs.resolve(TAG, "numpy")
    for p in set(patterns) - set(ok):
        have = {i: fragments[i] for i in range(N) if i not in p}
        with pytest.raises(ValueError, match="undecodable|cannot"):
            code.decode_matrix(sorted(have), list(p))
        with pytest.raises(ValueError):
            shell.reconstruct(have, list(p))
        with pytest.raises(ValueError):
            ref.reconstruct(have, list(p))


def _codec(kind: str, code):
    if kind == "numpy_code":
        return types.SimpleNamespace(
            reconstruct=lambda have, lost: code.reconstruct_numpy(have,
                                                                  lost),
            encode_parity=lambda d: code.encode_numpy(d)[K:])
    if kind == "xla":
        return gfmat_jax.JaxRSCodec(code)
    return pallas_gf.PallasRSCodec(code, tile=256, interpret=True)


@pytest.mark.parametrize("lost_n", [1, 2, 3])
@pytest.mark.parametrize("kind", ["numpy_code", "xla", "pallas_interpret"])
def test_byte_identity_every_loss_pattern(code, fragments, kind, lost_n):
    """`ops/lrc.LRCCode(12, 2, 2)` and the XLA and Pallas-interpret codecs
    over it rebuild, for every one-, two- and three-loss pattern, the
    bytes the plain reference's elimination gives; the parity they encode
    is the reference's."""
    codec = _codec(kind, code)
    assert np.array_equal(np.asarray(codec.encode_parity(fragments[:K])),
                          fragments[K:])
    for p in itertools.combinations(range(N), lost_n):
        have = {i: fragments[i] for i in range(N) if i not in p}
        want = ref.reconstruct(have, list(p))
        got = codec.reconstruct(have, list(p))
        for i in p:
            assert np.array_equal(want[i], fragments[i])
            assert np.array_equal(np.asarray(got[i]), fragments[i]), (p, i)


@pytest.mark.parametrize("lost, basis_n", [((3,), 6), ((9,), 6),
                                           ((12,), 6), ((14,), 12),
                                           ((0, 1), 12), ((0, 6), 12)])
def test_decode_basis_is_chosen_not_first_k(code, lost, basis_n):
    """One lost fragment of a group is the XOR of the group's other six;
    anything else goes to a basis over the whole set."""
    have = [i for i in range(N) if i not in lost]
    basis = code.decode_select(have, list(lost))
    assert len(basis) == basis_n
    if basis_n == 6:
        assert set(basis) | set(lost) in (GROUP0, GROUP1)
        assert code.decode_matrix(have, list(lost)).tolist() == [[1] * 6]
        assert ec_files.basis_kind(code, basis) == "local"
    else:
        assert ec_files.basis_kind(code, basis) == "global"
    assert ec_files.basis_kind(rs.get_code(10, 4), list(range(10))) == \
        "global"


@pytest.mark.parametrize("row_puts", [True, False],
                         ids=["rows_put_one_by_one", "one_flat_put"])
@pytest.mark.parametrize("kind", ["xla", "pallas_interpret"])
def test_reconstruct_seam_at_six_rows(code, fragments, monkeypatch, kind,
                                      row_puts):
    """`dispatch.reconstruct_batch` with a [6, W] batch in basis order:
    one program, 1-D transfers either way of the row-put rule, h2d bytes
    of six rows at the bucket's width on /perf's `reconstruct` rows."""
    from seaweedfs_tpu.stats.profile import KERNELS
    codec = _codec(kind, code)
    use = [0, 1, 2, 4, 5, 12]
    stage = np.ascontiguousarray(fragments[use])
    width = pallas_gf.codec_base.bucket(stage.shape[1], codec.tile)
    monkeypatch.setattr(dispatch, "ROW_PUTS_FROM",
                        width if row_puts else width + 1)

    def moved():
        row = KERNELS.snapshot().get("reconstruct[device]", {})
        return row.get("calls", 0), row.get("h2d_bytes", 0)
    calls0, h2d0 = moved()
    out = dispatch.reconstruct_batch(codec, stage, use, [3])
    assert np.array_equal(out[3], fragments[3])
    calls1, h2d1 = moved()
    assert calls1 - calls0 == 1
    assert h2d1 - h2d0 == 6 * width


# ---- the EC file engines, 12 wide ----------------------------------------

LARGE, SMALL = 1024, 256


def reference_files(raw: bytes, large: int, small: int) -> np.ndarray:
    """The 16 shard files `raw` must encode to: upstream's row-major
    striping 12 wide (large rows while more than one large row's bytes
    remain, then small rows, the last zero-padded), by hand, under the
    plain reference's generator."""
    files = [bytearray() for _ in range(K)]
    at = 0
    while len(raw) - at > K * large:
        for j in range(K):
            files[j] += raw[at:at + large]
            at += large
    while at < len(raw):
        for j in range(K):
            files[j] += raw[at:at + small].ljust(small, b"\0")
            at += small
    return ref.encode(np.array([np.frombuffer(bytes(f), dtype=np.uint8)
                                for f in files]))


def _shard_files(base: str, n: int = N) -> list[bytes]:
    out = []
    for i in range(n):
        with open(base + layout.to_ext(i), "rb") as f:
            out.append(f.read())
    return out


@pytest.fixture
def sealed(tmp_path):
    """A seeded `.dat`: one large row (12 x 1,024), then four small rows
    of 12 x 256, the last padded."""
    raw = np.random.default_rng(28).bytes(K * LARGE + 3 * K * SMALL + 777)
    base = str(tmp_path / "5")
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    return base, reference_files(raw, LARGE, SMALL)


@pytest.mark.parametrize("kind", KINDS)
def test_write_and_rebuild_ec_files(sealed, monkeypatch, kind):
    """16 files equal the reference's; a one-lost rebuild opens exactly
    the 6 files of the local group; two lost in one group fall to the
    global basis; both rebuild the reference's bytes."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base, want = sealed
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=SMALL, codec_tag=TAG)
    assert not os.path.exists(base + layout.to_ext(N))
    assert ec_files.read_vif(base)["codec"] == TAG
    assert want.shape == (N, LARGE + 4 * SMALL)
    for i, got in enumerate(_shard_files(base)):
        assert got == want[i].tobytes(), f"shard file {i}"

    opened: list[str] = []

    def spy(path, *a, **kw):
        if ".ec" in os.path.basename(path):  # not the .vif sidecar
            opened.append(os.path.basename(path))
        return open(path, *a, **kw)
    monkeypatch.setattr(ec_files, "open", spy, raising=False)
    os.remove(base + layout.to_ext(3))
    stats: dict = {}
    assert ec_files.rebuild_ec_files(base, batch_size=1000,
                                     stats=stats) == [3]
    assert (stats["survivors"], stats["basis"], stats["codec"]) == \
        (6, "local", TAG)
    assert sorted(opened) == [f"5.ec{i:02d}" for i in (0, 1, 2, 4, 5, 12)]

    del opened[:]
    for i in (7, 10):
        os.remove(base + layout.to_ext(i))
    stats = {}
    assert ec_files.rebuild_ec_files(base, stats=stats) == [7, 10]
    assert (stats["survivors"], stats["basis"]) == (12, "global")
    assert len(opened) == 12
    for i, got in enumerate(_shard_files(base)):
        assert got == want[i].tobytes(), f"shard file {i} after rebuild"


def test_rebuild_refuses_an_undecodable_loss(sealed, monkeypatch):
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "numpy")
    base, _want = sealed
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=SMALL, codec_tag=TAG)
    for i in (0, 1, 2, 14):  # three of a group and a global: not decodable
        os.remove(base + layout.to_ext(i))
    with pytest.raises(ValueError, match="undecodable"):
        ec_files.rebuild_ec_files(base)
    assert not [f for f in os.listdir(os.path.dirname(base))
                if f.endswith(".tmp")]


def _needle_volume(tmp_path, large=1 << 20, small=4096, n=60):
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(12)
    blobs = {}
    for i in range(1, n + 1):
        data = rng.integers(0, 256, int(rng.integers(1, 4000)),
                            dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=large, small_block=small,
                            batch_size=small * 10, codec_tag=TAG)
    ec_files.write_sorted_ecx(base + ".idx")
    return base, blobs


@pytest.mark.parametrize("lost, groups", [((3,), [GROUP0]),
                                          ((0, 6), [GROUP0, GROUP1])],
                         ids=["one_lost", "one_lost_in_each_group"])
@pytest.mark.parametrize("kind", ["jax", "numpy"])
def test_degraded_read_gathers_one_local_group(tmp_path, monkeypatch, kind,
                                               lost, groups):
    """A needle read on a mounted LRC volume with a data shard lost
    returns the bytes written, and each reconstruction gathers the six
    survivors of one local group, 12-wide striping and all (a needle is
    shorter than a block here, as in the benchmark's volume, so no read
    wants a shard of each group at once)."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base, blobs = _needle_volume(tmp_path)
    for sid in lost:
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    assert (ev.codec_tag, ev.spec.k, ev.spec.n) == (TAG, 12, 16)
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
    assert gathered, "no read reconstructed"
    for got in gathered:
        assert len(got) == 6 and any(got < g for g in groups), got


# ---- one codec resolution --------------------------------------------------


@pytest.mark.parametrize("tag, kind, platform, devices, fleet, backend", [
    ("lrc_12_2_2", "auto", "tpu", 1, False, "pallas"),
    ("lrc_12_2_2", "tpu", None, 1, False, "pallas"),
    ("lrc_12_2_2", "jax", None, 1, False, "xla"),
    ("lrc_12_2_2", "auto", "native", 1, False, "native"),
    ("lrc_12_2_2", "auto", "cpu", 1, False, "xla"),
    ("rs_10_4", "auto", "tpu", 1, False, "pallas"),
    ("rs_6_3", "auto", "tpu", 1, False, "pallas"),
    ("rs_6_3", "cpp", None, 1, False, "native"),
    ("rs_10_4", "numpy", None, 1, False, "numpy"),
    ("rs_10_4", "mesh", None, 1, False, "mesh"),
    ("msr_9_16", "auto", "tpu", 1, False, "pallas"),
    ("rs_10_4", "auto", "tpu", 4, True, "fleet"),
    ("rs_10_4", "auto", "tpu", 1, True, "pallas"),
    ("rs_10_4", "cpp", None, 1, True, "native"),
    ("lrc_12_2_2", "mesh", None, 1, False, None),
    # the conversion stream is under the volumes' code (PR 34): what was
    # refused for the fleet resolves as it does for a single volume
    ("rs_6_3", "auto", "tpu", 4, True, "fleet"),
    ("lrc_12_2_2", "cpp", None, 1, True, "native"),
    ("rs_40_8", "jax", None, 1, False, None),
    ("msr_9_16", "auto", "tpu", 4, True, "fleet"),
    ("msr_9_16", "auto", "tpu", 1, True, "pallas"),
    ("lrc_12_2_2", "mesh", None, 1, True, "fleet"),
    ("lrc_12_2_2", "auto", "native", 1, True, "native"),
    ("rs_40_8", "auto", "tpu", 4, True, None),
])
def test_backend_is_a_pure_function_of_tag_kind_and_platform(
        tag, kind, platform, devices, fleet, backend):
    spec = codecs.parse_tag(tag)
    assert spec.tag == tag
    if backend is None:
        with pytest.raises(codecs.CodecUnsupported, match=tag):
            codecs.backend_for(spec, kind, platform, devices, fleet)
    else:
        assert codecs.backend_for(spec, kind, platform, devices,
                                  fleet) == backend


def test_one_object_from_all_three_former_entry_points(monkeypatch):
    """`ec_files._get_codec`, `codecs.make_codec` and
    `fleet_convert.fleet_codec` are callers of `codecs.resolve`: one
    object for one (tag, kind), over the tag's own k and m."""
    for kind, shell in (("jax", "JaxRSCodec"), ("numpy", "_NumpyShell")):
        a = ec_files._get_codec(kind, TAG)
        assert a is codecs.make_codec(TAG, kind) is codecs.resolve(TAG, kind)
        assert type(a).__name__ == shell and (a.k, a.m) == (12, 4)
        assert a.code is lrc.get_code(12, 2, 2)
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    monkeypatch.delenv("WEEDTPU_CONVERT_CODEC", raising=False)
    assert fleet_convert.fleet_codec() is ec_files._get_codec() is \
        codecs.make_codec("rs_10_4")
    six = ec_files._get_codec("jax", "rs_6_3")
    assert (six.k, six.m) == (6, 3) and six is codecs.make_codec("rs_6_3",
                                                                 "jax")
    assert np.array_equal(six.code.matrix, rs.get_code(6, 3).matrix)
    # the fleet's resolution carries the tag too, over the same object
    assert fleet_convert.fleet_codec("jax", "rs_6_3") is six
    assert fleet_convert.fleet_codec("jax", TAG) is codecs.resolve(TAG, "jax")
    with pytest.raises(codecs.CodecUnsupported, match="rs_40_8"):
        fleet_convert.fleet_codec("jax", "rs_40_8")
    with pytest.raises(codecs.CodecUnsupported, match="no such code"):
        codecs.resolve("lrc_12_5_2", "jax")  # 12 data in 5 groups
    # the selection rides /perf `codecs`
    noted = [c for c in pipeline.local_snapshot()["codecs"]
             if c["tag"] == TAG and c["asked"] == "jax"]
    assert noted and noted[0]["codec"] == "JaxRSCodec"
    assert noted[0]["platform"] == "cpu" and noted[0]["tile"] == 32768
    # no ladder left behind
    assert not hasattr(ec_files, "_select_codec")
    assert not hasattr(codecs, "_shell_for")


# ---- the served entry points -------------------------------------------------


def _call(handler, body: dict, limit_s: float = 120.0):
    """One request to a volume-server handler, on a thread of its own
    with a time limit: a request that never answers fails the test
    instead of hanging the run."""
    async def _json():
        return body
    box: dict = {}

    def run():
        try:
            box["resp"] = asyncio.run(
                handler(types.SimpleNamespace(json=_json, query=body)))
        except BaseException as e:  # shown by the assert below
            box["error"] = e
    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(limit_s)
    assert not t.is_alive(), f"no answer within {limit_s} s"
    assert "error" not in box, box.get("error")
    return box["resp"].status, json.loads(box["resp"].body)


@pytest.fixture
def server(tmp_path, monkeypatch):
    """A volume server (never started: handlers are called directly) on a
    directory with one sealed volume, id 3."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    monkeypatch.delenv("WEEDTPU_CONVERT_CODEC", raising=False)
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(3)
    for i in range(1, 30):
        vol.append_needle(ndl.Needle(
            cookie=0x9, id=i, data=rng.bytes(int(rng.integers(100, 90000)))))
    vol.close()
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:0", port=18997)

    async def no_beat():
        return None
    monkeypatch.setattr(vs, "_heartbeat_once", no_beat)
    yield vs, str(tmp_path / "3")
    vs.store.close()


def _no_leftovers(base: str) -> None:
    assert not [f for f in os.listdir(os.path.dirname(base))
                if f.endswith(".tmp")]
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(("ec-writer", "ec-reader", "ec-drain",
                                      "fleet-"))]


def test_generate_and_rebuild_under_the_tag(server):
    """/admin/ec/generate with the tag writes .ec00-.ec15 and the tag into
    the .vif; /admin/ec/rebuild reads it there, stages 6 files for one
    lost data shard and says so on /admin/ec/progress."""
    vs, base = server
    status, out = _call(vs.handle_ec_generate, {"volume": 3, "codec": TAG})
    assert (status, out) == (200, {
        "shards": list(range(16)), "codec": TAG,
        "large_block_bytes": layout.LARGE_BLOCK_SIZE,
        "small_block_bytes": layout.SMALL_BLOCK_SIZE})
    assert ec_files.read_vif(base)["codec"] == TAG
    with open(base + ".dat", "rb") as f:
        want = reference_files(f.read(), layout.LARGE_BLOCK_SIZE,
                               layout.SMALL_BLOCK_SIZE)
    before = _shard_files(base)
    assert [len(b) for b in before] == [want.shape[1]] * 16
    assert before == [w.tobytes() for w in want]
    os.remove(base + layout.to_ext(3))
    status, out = _call(vs.handle_ec_rebuild, {"volume": 3})
    assert (status, out) == (200, {"rebuilt": [3]})
    assert _shard_files(base) == before
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert status == 200 and job["kind"] == "rebuild" and job["codec"] == TAG
    assert job["stages"]["survivors"] == 6
    assert job["stages"]["basis"] == "local"
    # one batch, narrower than a row goes up alone: stacked on the host
    assert job["stages"]["rows_staged"] == 6
    _no_leftovers(base)


def test_rs_6_3_builds_six_plus_three(server):
    """The tag's own geometry, where every RS tag used to get 10 + 4."""
    vs, base = server
    status, out = _call(vs.handle_ec_generate,
                        {"volume": 3, "codec": "rs_6_3"})
    assert (status, out["shards"]) == (200, list(range(9)))
    assert not os.path.exists(base + layout.to_ext(9))
    with open(base + ".dat", "rb") as f:
        raw = f.read()
    size = layout.shard_file_size(len(raw), data_shards=6)
    data = np.zeros((6, size), dtype=np.uint8)
    flat = np.frombuffer(raw, dtype=np.uint8)
    for b in range(-(-len(raw) // layout.SMALL_BLOCK_SIZE)):
        blk = flat[b * layout.SMALL_BLOCK_SIZE:
                   (b + 1) * layout.SMALL_BLOCK_SIZE]
        at = b // 6 * layout.SMALL_BLOCK_SIZE
        data[b % 6, at:at + len(blk)] = blk
    want = rs.get_code(6, 3).encode_numpy(data)
    assert _shard_files(base, 9) == [w.tobytes() for w in want]
    _no_leftovers(base)


@pytest.mark.parametrize("tag, n", [("rs_6_3", 9), (TAG, 16),
                                    ("msr_9_16", 18), (None, 14)])
def test_fleet_convert_under_the_tag_equals_generate(server, tag, n):
    """What `/admin/ec/fleet_convert` answered 400 to until PR 34: with a
    `codec` the volume goes under that code, n files and the tag in the
    `.vif`, byte for byte what `/admin/ec/generate` under the tag leaves;
    `/admin/ec/progress` says the code, `/perf` the matrix.  Without a
    tag: rs_10_4, as before."""
    from seaweedfs_tpu.stats.profile import KERNELS
    vs, base = server
    KERNELS.reset()
    body = {"volumes": [3]} if tag is None else {"volumes": [3],
                                                 "codec": tag}
    status, out = _call(vs.handle_ec_fleet_convert, body)
    assert status == 200 and out["converted"] == [3], out
    spec = codecs.parse_tag(tag)
    got = _shard_files(base, n)
    assert not os.path.exists(base + layout.to_ext(n))
    assert ec_files.read_vif(base)["codec"] == spec.tag
    assert vs.store.get_volume(3).read_only  # the set is the copy of record
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert status == 200 and job["kind"] == "fleet_convert"
    assert (job["stages"]["codec"], job["stages"]["shard_files"],
            job["stages"]["alpha"]) == (spec.tag, n, spec.alpha)
    # one device: each unit of the stream is the single-volume encode's
    # program, on its /perf row
    row = next(r for r in pipeline.local_snapshot()["roofline"]["rows"]
               if r["kernel"] == "encode_parity")
    assert (row["backend"], row["rows_in"], row["rows_out"], row["alpha"],
            row["tile"]) == ("device", spec.k * spec.alpha,
                             spec.m * spec.alpha, spec.alpha, 32768)
    for i in range(n):
        os.remove(base + layout.to_ext(i))
    gen = {"volume": 3} if tag is None else {"volume": 3, "codec": tag}
    status, out = _call(vs.handle_ec_generate, gen)
    assert (status, out["shards"]) == (200, list(range(n)))
    assert _shard_files(base, n) == got
    _no_leftovers(base)


@pytest.mark.parametrize("path, body, why", [
    ("fleet_convert", {"volumes": [3], "codec": "rs_40_8"}, "at most 32"),
    ("fleet_convert", {"volumes": [3], "codec": "lrc_12_5_2"},
     "no such code"),
    ("generate", {"volume": 3, "codec": "rs_40_8"}, "at most 32"),
    ("generate", {"volume": 3, "codec": "lrc_12_5_2"}, "no such code"),
])
def test_a_tag_the_backend_cannot_honour_answers_400(server, path, body, why):
    """Refused with the reason before any `.tmp` file exists, inside the
    time limit, no thread left waiting (PR 27: `rs_6_3` never answered on
    the chip; four writer threads had died on an IndexError)."""
    vs, base = server
    handler = {"generate": vs.handle_ec_generate,
               "fleet_convert": vs.handle_ec_fleet_convert}[path]
    status, out = _call(handler, body, limit_s=60)
    assert status == 400 and body["codec"] in out["error"]
    assert why in out["error"]
    assert not os.path.exists(base + layout.to_ext(0))
    assert not vs.store.get_volume(3).read_only
    _no_leftovers(base)


def test_a_writer_pool_outlives_a_shard_it_has_no_file_for(tmp_path):
    """The hang itself: a codec wider than the set made `_write_batch`
    raise outside its error handling, the worker died and its bounded
    queue filled for ever.  Now it is an error of the run."""
    fds = [os.open(str(tmp_path / f"f{i}"), os.O_RDWR | os.O_CREAT)
           for i in range(2)]
    pool = ec_files._ShardWriterPool(fds, depth=1, workers=2)
    row = np.zeros(64, dtype=np.uint8)

    def feed():
        for _ in range(64):  # far past the queue's bound
            pool.put(5, row, 0)
        pool.close()
    t = threading.Thread(target=feed, daemon=True)
    t.start()
    t.join(60)
    assert not t.is_alive(), "the producer is still waiting on the pool"
    assert pool.failed and isinstance(pool.errors[0], IndexError)
    for fd in fds:
        os.close(fd)
