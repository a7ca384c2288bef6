"""PM-MSR(9,16) as a code a volume can be under (tag `msr_9_16`).

The plain reference is `seaweedfs_tpu/models/msr.py` (Rashmi, Shah, Kumar,
arXiv:1005.4178 section V in the paper's own form: Psi = [Phi Lambda Phi],
M = [S1; S2], node i stores psi_i M, theorem 4's repair, elimination over
any nine nodes).  Held to it here, on the CPU at small sizes: the code
object, the XLA and Pallas-interpret codecs through the dispatch seam's
linear surface (18 files of an encode whose last row is short, whole-node
decodes from one to nine lost, eight survivors refused, the 16-helper
regenerating repair), the EC file engines 9 wide with 18 shard files, a
degraded read on a mounted volume, the volume server's handlers under the
tag, and the kernel's tile rule.  On the chip the same comparison decides
the benchmark cell's `correct` (`benchmark/reference_msr.py`).
"""

import itertools
import os

import jax.numpy as jnp
import numpy as np
import pytest

from seaweedfs_tpu.models import msr as ref
from seaweedfs_tpu.ops import (codec_base, codecs, dispatch, gf, msr,
                               pallas_gf)
from seaweedfs_tpu.stats import pipeline
from seaweedfs_tpu.stats.profile import KERNELS
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume

from test_lrc_azure import _call, _no_leftovers, _shard_files, server  # noqa: F401

TAG = "msr_9_16"
N, K, D, ALPHA = 18, 9, 16, 8
KINDS = ["xla", "pallas_interpret"]


@pytest.fixture(scope="module")
def code():
    return msr.get_code(9, 16)


@pytest.fixture(scope="module")
def nodes():
    """[18, 8 * 131] seeded node files by the plain reference."""
    data = np.random.default_rng(1412).integers(0, 256, (K, ALPHA * 131),
                                                dtype=np.uint8)
    return ref.encode(data)


def _codec(kind: str):
    if kind == "xla":
        return codecs.resolve(TAG, "jax")
    return msr.MSRFileCodec(pallas_gf.PallasRSCodec(
        msr.get_code(9, 16), tile=256, interpret=True))


# ---- the code object against the paper's form ------------------------------


def test_the_programs_generator_is_the_papers_code(code, nodes):
    """ops/msr.py's systematised generator stores what psi_i M stores."""
    assert (code.k, code.m, code.alpha, code.n_nodes, code.d) == \
        (72, 72, ALPHA, N, D)
    virt = msr.interleave_split(nodes[:K], K, ALPHA)
    parity = msr.interleave_merge(gf.gf_matmul(code.parity_matrix, virt),
                                  N - K, ALPHA)
    assert np.array_equal(parity, nodes[K:])
    assert np.array_equal(code.psi, ref.PSI)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("lost_n", range(1, 10))
def test_whole_node_decode(nodes, kind, lost_n):
    """Every one-lost pattern, and a seeded sample of each count up to the
    nine the code survives, through the reconstruct seam (`MSRFileCodec`'s
    linear surface: the stack path, not the dict branch)."""
    codec = _codec(kind)
    assert hasattr(codec, "reconstruct_stack")
    every = list(itertools.combinations(range(N), lost_n))
    rng = np.random.default_rng(lost_n)
    picks = every if lost_n == 1 else \
        [every[i] for i in rng.choice(len(every), 3, replace=False)]
    for lost in picks:
        have = [i for i in range(N) if i not in lost]
        out = dispatch.reconstruct_batch(codec, [nodes[i] for i in have],
                                         have, list(lost))
        want = ref.reconstruct({i: nodes[i] for i in have}, list(lost))
        for w in lost:
            assert np.array_equal(out[w], want[w]), (lost, w)
            assert np.array_equal(out[w], nodes[w]), (lost, w)


@pytest.mark.parametrize("kind", KINDS)
def test_eight_survivors_are_refused(nodes, kind):
    codec = _codec(kind)
    have = list(range(3, 11))
    with pytest.raises(ValueError, match="need 9"):
        dispatch.reconstruct_batch(codec, [nodes[i] for i in have], have,
                                   [0])
    with pytest.raises(ValueError, match="any 9 decode"):
        ref.reconstruct({i: nodes[i] for i in have}, [0])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("lost", range(N))
def test_regenerating_repair_of_every_node(code, nodes, kind, lost):
    """16 helpers each apply [1, 8] to their own sub-rows and ship an
    eighth of their bytes; [8, 16] at the rebuilder gives the node: the
    program's two matrices through the seam's `apply_matrix`, against
    theorem 4 as the plain reference states it.  2 shard-equivalents
    cross where a whole-node decode reads 9."""
    codec = _codec(kind)
    helpers = [j for j in range(N) if j != lost][(lost % 2):][:D]
    coeff = codec.repair_coeff(lost)
    assert coeff.shape == (1, ALPHA)
    sent = [dispatch.apply_matrix(
        codec, coeff, msr.interleave_split(nodes[j][None, :], 1, ALPHA))
        for j in helpers]
    moved = sum(s.size for s in sent)
    assert moved * ALPHA == D * nodes.shape[1]          # 16 / 8 = 2 files
    rebuilt = dispatch.apply_matrix(
        codec, codec.repair_matrix(lost, helpers), np.concatenate(sent))
    got = msr.interleave_merge(np.asarray(rebuilt), 1, ALPHA)[0]
    want, ref_moved = ref.repair({j: nodes[j] for j in helpers}, lost,
                                 helpers)
    assert np.array_equal(want, nodes[lost])
    assert np.array_equal(got, want) and moved == ref_moved
    assert code.repair_ratio() == pytest.approx(D / (K * ALPHA))


# ---- the linear surface ----------------------------------------------------


def _unit(rng, stripes: int, block: int):
    """A unit as the encode engine selects it (`stripes` rows of 9 blocks,
    one after the other) and the [9, stripes * block] files it holds."""
    dat = rng.integers(0, 256, stripes * K * block, dtype=np.uint8)
    files = dat.reshape(stripes, K, block).transpose(1, 0, 2).reshape(K, -1)
    return dat, files


@pytest.mark.parametrize("block", [ALPHA * 128 * 2, ALPHA * 37],
                         ids=["lane_runs", "own_order"])
@pytest.mark.parametrize("kind", KINDS)
def test_encode_parity_linear_equals_encode_parity(kind, block):
    """One program a unit: rows laid out, split into 72 sub-rows (in runs
    of `codec_base.LANES` columns where the width allows, in the file's
    own order else), the [72, 72] apply, merged back, nine 1-D runs out;
    the same bytes as the [9, L] surface and as the plain reference."""
    codec = _codec(kind)
    dat, files = _unit(np.random.default_rng(block), 3, block)
    want = ref.encode(files)[K:]
    assert np.array_equal(np.asarray(codec.encode_parity(files)), want)
    for spans in ([dat], np.split(dat, 3)):
        runs = codec.encode_parity_linear(
            tuple(jnp.asarray(s) for s in spans), 3)
        assert len(runs) == N - K and all(r.ndim == 1 for r in runs)
        assert np.array_equal(np.stack([np.asarray(r) for r in runs]), want)


@pytest.mark.parametrize("kind", KINDS)
def test_the_seam_takes_the_unit_linear_and_stages_nothing(kind):
    codec = _codec(kind)
    dat, files = _unit(np.random.default_rng(5), 2, ALPHA * 128)
    stats: dict = {}
    job = pipeline.track("ec_encode", stats, dat.nbytes)
    KERNELS.reset()
    parity = dispatch.materialize(
        dispatch.dispatch_parity(codec, [dat], job=job, stripes=2), job=job)
    job.finish()
    assert isinstance(parity, list) and len(parity) == N - K
    assert np.array_equal(np.stack(parity), ref.encode(files)[K:])
    assert "rows_staged" not in stats
    assert KERNELS.notes("encode_parity[device]") == {
        "rows_in": 72, "rows_out": 72, "alpha": ALPHA, "stripes": [2],
        "tile": codec.inner.tile}


def test_subrow_columns_are_a_common_permutation():
    """`_subrows` may order a sub-row's columns as it likes (an apply is
    column-local) as long as every row shares the order and `_files`
    undoes it."""
    x = np.random.default_rng(8).integers(0, 256, (3, ALPHA * 128 * 5),
                                          dtype=np.uint8)
    v = np.asarray(codec_base._subrows(jnp.asarray(x), ALPHA))
    natural = msr.interleave_split(x, 3, ALPHA)
    order = [int(np.flatnonzero((natural == v[:, c:c + 1]).all(axis=0))[0])
             for c in range(v.shape[1])]
    assert sorted(order) == list(range(v.shape[1]))
    assert np.array_equal(natural[:, order], v)
    assert np.array_equal(np.asarray(codec_base._files(jnp.asarray(v),
                                                       ALPHA)), x)


# ---- the tile rule ---------------------------------------------------------


@pytest.mark.parametrize("m, k, tile", [
    (4, 10, 131072), (1, 10, 131072), (2, 10, 131072), (4, 12, 131072),
    (1, 6, 131072), (2, 12, 131072),              # RS(10,4), LRC(12,2,2)
    (72, 72, 8192), (8, 72, 32768), (16, 72, 16384), (8, 16, 65536),
    (1, 8, 131072),                               # PM-MSR(9,16)
])
def test_tile_follows_from_the_matrix(m, k, tile):
    kpad = max(pallas_gf.PLANE_PAD, -(-k // pallas_gf.PLANE_PAD) *
               pallas_gf.PLANE_PAD)
    assert pallas_gf.matrix_tile(m, kpad, pallas_gf.TPU_TILE) == tile
    # the interpreter's default is narrow enough for every one of them
    assert pallas_gf.matrix_tile(m, kpad, 256) == 256


# ---- the EC file engines, 9 wide -------------------------------------------

LARGE, SMALL = ALPHA * 128 * 4, ALPHA * 128


def reference_files(raw: bytes, large: int, small: int) -> np.ndarray:
    """The 18 shard files `raw` must encode to: upstream's row-major
    striping 9 wide, by hand, under the plain reference."""
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


@pytest.fixture
def sealed(tmp_path):
    """A seeded `.dat`: one large row, then small rows, the last short."""
    raw = np.random.default_rng(32).bytes(K * LARGE + 5 * K * SMALL + 1234)
    base = str(tmp_path / "7")
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    return base, reference_files(raw, LARGE, SMALL)


@pytest.mark.parametrize("kind", ["jax", "numpy"])
def test_write_and_rebuild_ec_files(sealed, monkeypatch, kind):
    """18 files equal the reference's, the last row short; a rebuild maps
    the nine files `decode_select` picks; `rows_staged` is the last row's
    1 under a device codec (the seam took every unit linear)."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base, want = sealed
    stats: dict = {}
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=4 * SMALL, codec_tag=TAG, stats=stats)
    assert not os.path.exists(base + layout.to_ext(N))
    assert ec_files.read_vif(base)["codec"] == TAG
    assert want.shape == (N, LARGE + 6 * SMALL)
    for i, got in enumerate(_shard_files(base, N)):
        assert got == want[i].tobytes(), f"shard file {i}"
    if kind == "jax":
        assert stats["rows_staged"] == 1
    for lost in ([4], [0, 17], list(range(9))):
        for i in lost:
            os.remove(base + layout.to_ext(i))
        stats = {}
        assert ec_files.rebuild_ec_files(base, batch_size=ALPHA * 128 * 3,
                                         stats=stats) == lost
        assert (stats["survivors"], stats["basis"]) == (K, "global")
        for i, got in enumerate(_shard_files(base, N)):
            assert got == want[i].tobytes(), f"shard file {i} after {lost}"
    for i in range(10):
        os.remove(base + layout.to_ext(i))
    with pytest.raises(ValueError, match="need >= 9"):
        ec_files.rebuild_ec_files(base)
    assert not [f for f in os.listdir(os.path.dirname(base))
                if f.endswith(".tmp")]


@pytest.mark.parametrize("lost", [(2,), (0, 1)], ids=["one", "two"])
def test_degraded_read_on_a_mounted_volume(tmp_path, monkeypatch, lost):
    """Every needle of a mounted volume with data files lost reads back
    as written: nine survivors gathered, alpha-aligned ranges, the decode
    through the seam's stack path."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
    vol = Volume(str(tmp_path), "", 3)
    rng = np.random.default_rng(9)
    blobs = {}
    for i in range(1, 50):
        data = rng.bytes(int(rng.integers(1, 4000)))
        vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
        blobs[i] = data
    vol.close()
    base = str(tmp_path / "3")
    ec_files.write_ec_files(base, large_block=1 << 20, small_block=4096,
                            batch_size=40960, codec_tag=TAG)
    ec_files.write_sorted_ecx(base + ".idx")
    for sid in lost:
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    assert (ev.codec_tag, ev.spec.k, ev.spec.n, ev.spec.alpha) == \
        (TAG, K, N, ALPHA)
    try:
        for nid, data in blobs.items():
            assert ev.read_needle(nid).data == data, nid
        assert ev.read_stats["reconstruct_batches"] > 0
    finally:
        ev.close()


# ---- the volume server under the tag ---------------------------------------


def test_generate_rebuild_and_progress_under_the_tag(server):
    """/admin/ec/generate with the tag writes .ec00-.ec17 and the tag into
    the .vif; /admin/ec/rebuild reads it there and stages 9 files;
    /admin/ec/progress and /perf say the geometry."""
    vs, base = server
    KERNELS.reset()
    status, out = _call(vs.handle_ec_generate, {"volume": 3, "codec": TAG})
    assert (status, out) == (200, {
        "shards": list(range(N)), "codec": TAG,
        "large_block_bytes": layout.LARGE_BLOCK_SIZE,
        "small_block_bytes": layout.SMALL_BLOCK_SIZE})
    with open(base + ".dat", "rb") as f:
        want = reference_files(f.read(), layout.LARGE_BLOCK_SIZE,
                               layout.SMALL_BLOCK_SIZE)
    before = _shard_files(base, N)
    assert before == [w.tobytes() for w in want]
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert status == 200 and job["codec"] == TAG
    assert job["stages"]["rows_staged"] == 1   # the volume's last row
    perf = pipeline.local_snapshot()
    block = next(b for b in perf["codecs"] if b["tag"] == TAG)
    assert (block["codec"], block["rows_in"], block["rows_out"],
            block["alpha"]) == ("JaxRSCodec", 72, 72, ALPHA)
    row = next(r for r in perf["roofline"]["rows"]
               if r["kernel"] == "encode_parity")
    assert (row["backend"], row["rows_in"], row["rows_out"], row["alpha"],
            row["tile"]) == ("device", 72, 72, ALPHA, block["tile"])
    os.remove(base + layout.to_ext(11))
    status, out = _call(vs.handle_ec_rebuild, {"volume": 3})
    assert (status, out) == (200, {"rebuilt": [11]})
    assert _shard_files(base, N) == before
    status, job = _call(vs.handle_ec_progress, {"volumeId": "3"})
    assert job["kind"] == "rebuild" and job["stages"]["survivors"] == K
    _no_leftovers(base)


@pytest.mark.parametrize("tag, why", [("msr_9_15", "no such code"),
                                      ("msr_20_38", "at most 32"),
                                      ("msr_40_78", "at most 32")])
def test_a_tag_whose_kernel_cannot_be_built_answers_400(server, tag, why):
    vs, base = server
    status, out = _call(vs.handle_ec_generate, {"volume": 3, "codec": tag},
                        limit_s=60)
    assert status == 400 and why in out["error"]
    assert not os.path.exists(base + layout.to_ext(0))
    _no_leftovers(base)
