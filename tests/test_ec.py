"""EC pipeline tests, modelled on the reference's ec_test.go: encode a
volume with tiny block sizes (large=10000, small=100), verify every needle
byte-equal when read back from shards, including via reconstruction from
k-of-n subsets; plus layout-math unit tests and the full
encode->rebuild->decode cycle on the reference's checked-in fixture volume."""

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
from seaweedfs_tpu.storage.volume import Volume

from conftest import reference_fixture

LARGE, SMALL = 10000, 100  # test block sizes (reference ec_test.go:16-19)


# ---- layout math ------------------------------------------------------

def test_locate_small_only():
    dat_size = 9971  # < one large row
    ivs = layout.locate_data(LARGE, SMALL, dat_size, 0, dat_size)
    # 9971 bytes = 99 full small blocks + 71
    assert sum(iv.size for iv in ivs) == dat_size
    assert all(not iv.is_large_block for iv in ivs)
    assert len(ivs) == 100


def test_locate_straddles_large_to_small():
    dat_size = LARGE * layout.DATA_SHARDS + 500  # 1 large row + change
    # the byte range crossing the large/small boundary
    ivs = layout.locate_data(LARGE, SMALL, dat_size, LARGE * 10 - 50, 100)
    assert sum(iv.size for iv in ivs) == 100
    assert ivs[0].is_large_block and not ivs[1].is_large_block
    assert ivs[0].size == 50
    sid0, off0 = ivs[0].to_shard_id_and_offset(LARGE, SMALL)
    sid1, off1 = ivs[1].to_shard_id_and_offset(LARGE, SMALL)
    assert sid0 == 9 and off0 == LARGE - 50
    assert sid1 == 0 and off1 == LARGE  # first small block sits after larges


def test_locate_consistent_with_encode_loop_everywhere():
    """Property: for any dat size — including the window where the
    reference's own nLargeBlockRows formula disagrees with its encode loop —
    locate_data maps every sampled byte to the exact (shard, offset) the
    encode loop would have written it to."""
    rng = np.random.default_rng(99)
    sizes = [1, SMALL * 10, LARGE * 10, LARGE * 10 + 1,
             LARGE * 10 + (LARGE - SMALL) * 10,       # reference-bug boundary
             LARGE * 10 + (LARGE - SMALL) * 10 + 7,   # inside the bug window
             LARGE * 20 - SMALL * 3,                   # inside the bug window
             LARGE * 25 + 12345]
    for dat_size in sizes:
        # simulate the encode loop: byte offset -> (shard, shard_offset)
        def encoded_location(off):
            remaining, row_start, shard_off = dat_size, 0, 0
            while remaining > LARGE * 10:
                if off < row_start + LARGE * 10:
                    j = (off - row_start) // LARGE
                    return j, shard_off + (off - row_start) % LARGE
                remaining -= LARGE * 10
                row_start += LARGE * 10
                shard_off += LARGE
            while True:
                if off < row_start + SMALL * 10:
                    j = (off - row_start) // SMALL
                    return j, shard_off + (off - row_start) % SMALL
                row_start += SMALL * 10
                shard_off += SMALL

        for off in sorted(set(
                [0, dat_size - 1] +
                list(rng.integers(0, dat_size, 20).tolist()))):
            ivs = layout.locate_data(LARGE, SMALL, dat_size, off, 1)
            got = ivs[0].to_shard_id_and_offset(LARGE, SMALL)
            assert got == encoded_location(off), (dat_size, off)


def test_shard_file_size_matches_encode_loop():
    for dat_size in (0, 1, 999, SMALL * 10, LARGE * 10, LARGE * 10 + 1,
                     LARGE * 20 - SMALL * 3, LARGE * 25 + 12345):
        # emulate the reference loop
        remaining, large_rows = dat_size, 0
        while remaining > LARGE * 10:
            large_rows += 1
            remaining -= LARGE * 10
        small_rows = 0
        while remaining > 0:
            small_rows += 1
            remaining -= SMALL * 10
        want = large_rows * LARGE + small_rows * SMALL
        assert layout.shard_file_size(dat_size, LARGE, SMALL) == want, dat_size


# ---- full pipeline ----------------------------------------------------

@pytest.fixture()
def small_volume(tmp_path):
    """A volume with a few hundred mixed-size needles."""
    vol = Volume(str(tmp_path), "", 7)
    rng = np.random.default_rng(7)
    blobs = {}
    for i in range(1, 200):
        size = int(rng.integers(1, 2000)) if i % 7 else int(rng.integers(2000, 9000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        vol.append_needle(ndl.Needle(cookie=0x1234, id=i, data=data))
        blobs[i] = data
    vol.close()
    return tmp_path, blobs


def encode_small(base):
    ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                            batch_size=SMALL * 10)
    ec_files.write_sorted_ecx(base + ".idx")


def test_ec_encode_roundtrip_all_needles(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    for i in range(layout.TOTAL_SHARDS):
        assert os.path.getsize(base + layout.to_ext(i)) == \
            layout.shard_file_size(os.path.getsize(base + ".dat"), LARGE, SMALL)

    ev = ec_volume.EcVolume(base)
    for nid, data in blobs.items():
        n = ev.read_needle(nid)
        assert n.data == data, nid
    ev.close()


def test_ec_degraded_read_with_missing_shards(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    # lose 4 shards (2 data + 2 parity)
    for sid in (1, 7, 10, 13):
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    assert ev.shard_ids() == [0, 2, 3, 4, 5, 6, 8, 9, 11, 12]
    for nid, data in blobs.items():
        assert ev.read_needle(nid).data == data, nid
    ev.close()


def test_ec_read_fails_below_k_shards(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    for sid in (0, 1, 2, 3, 10):
        os.remove(base + layout.to_ext(sid))
    ev = ec_volume.EcVolume(base)
    with pytest.raises(IOError, match="shards readable"):
        # any needle hitting shard 0..3 must fail with 9 shards left
        for nid in blobs:
            ev.read_needle(nid)
    ev.close()


def test_ec_rebuild_missing_shards(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    golden = {sid: open(base + layout.to_ext(sid), "rb").read()
              for sid in (2, 11)}
    for sid in (2, 11):
        os.remove(base + layout.to_ext(sid))
    rebuilt = ec_files.rebuild_ec_files(base, batch_size=SMALL * 10)
    assert sorted(rebuilt) == [2, 11]
    for sid, want in golden.items():
        assert open(base + layout.to_ext(sid), "rb").read() == want, sid


def test_ec_delete_and_journal_replay(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    ev = ec_volume.EcVolume(base)
    ev.delete_needle(5)
    ev.delete_needle(6)
    with pytest.raises(KeyError):
        ev.read_needle(5)
    ev.close()
    assert ec_files.read_ecj(base + ".ecj") == [5, 6]
    # remount replays the journal and removes it
    ev2 = ec_volume.EcVolume(base)
    assert not os.path.exists(base + ".ecj")
    with pytest.raises(KeyError):
        ev2.read_needle(6)
    assert ev2.read_needle(7).data == blobs[7]
    ev2.close()


def test_ec_decode_back_to_volume(small_volume):
    tmp_path, blobs = small_volume
    base = str(tmp_path / "7")
    golden_dat = open(base + ".dat", "rb").read()
    encode_small(base)
    dat_size = ec_files.find_dat_file_size(base)
    assert dat_size == len(golden_dat)
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    ec_files.write_dat_file(base, dat_size)
    ec_files.write_idx_from_ecx(base + ".ecx")
    assert open(base + ".dat", "rb").read() == golden_dat
    # reload as a normal volume and read everything
    vol = Volume(str(tmp_path), "", 7)
    for nid, data in blobs.items():
        assert vol.read_needle(nid).data == data
    vol.close()


# ---- encode strategies ------------------------------------------------

@pytest.mark.parametrize("batch", [50, SMALL * 10])
@pytest.mark.parametrize("dat_size", [LARGE * 10 + SMALL * 23 + 37,
                                      SMALL * 4 + 1])
def test_pipelined_and_serial_encode_byte_identical(tmp_path, monkeypatch,
                                                    batch, dat_size):
    """The native host codec (zero-copy, off the mmap) and the numpy codec
    (staged through the read pool) must cut byte-identical .ec00-.ec13
    shard files from the same .dat through the one encode pipeline — the
    overlapped writer pool reorders I/O, never contents."""
    from seaweedfs_tpu import native
    rng = np.random.default_rng(11)
    dat = rng.integers(0, 256, dat_size, dtype=np.uint8).tobytes()
    shards: dict[str, list[bytes]] = {}
    for codec in ("cpp", "numpy"):
        if codec == "cpp" and not native.available():
            continue
        d = tmp_path / codec
        d.mkdir()
        base = str(d / "v")
        with open(base + ".dat", "wb") as f:
            f.write(dat)
        monkeypatch.setenv("WEEDTPU_EC_CODEC", codec)
        stats: dict = {}
        ec_files.write_ec_files(base, large_block=LARGE, small_block=SMALL,
                                batch_size=batch, stats=stats)
        assert stats["mode"] == "pipelined", (codec, stats)
        shards[codec] = [open(base + layout.to_ext(i), "rb").read()
                         for i in range(layout.TOTAL_SHARDS)]
    golden = shards["numpy"]
    for name, got in shards.items():
        for i in range(layout.TOTAL_SHARDS):
            assert got[i] == golden[i], (name, i)


def test_rebuild_stats_report_overlap(small_volume):
    """rebuild_ec_files drives the same writer-pool machinery: stats must
    carry per-stage seconds and the rebuilt bytes."""
    tmp_path, _ = small_volume
    base = str(tmp_path / "7")
    encode_small(base)
    for sid in (0, 10, 12, 13):
        os.remove(base + layout.to_ext(sid))
    stats: dict = {}
    rebuilt = ec_files.rebuild_ec_files(base, batch_size=SMALL * 10,
                                        stats=stats)
    assert sorted(rebuilt) == [0, 10, 12, 13]
    assert stats["bytes"] > 0
    assert "reconstruct_s" in stats and "write_s" in stats
    assert "wall_s" in stats


# ---- the encode unit: a span of the .dat's map --------------------------

def _lrc_reference(data):
    from seaweedfs_tpu.models import lrc
    return lrc.encode(data)


def _rs_reference(data):
    from seaweedfs_tpu.models import rs
    return rs.get_code(10, 4).encode_numpy(data)


UNIT_TAGS = {"rs_10_4": (10, 14, _rs_reference),
             "lrc_12_2_2": (12, 16, _lrc_reference)}
UNIT_TILE = 256
# (large, small, batch, .dat bytes as a function of k, units, rows_staged):
# a unit is batch // block consecutive whole rows (R = 4 small rows but
# where stated), one dispatch each
UNIT_CASES = {
    "dat_shorter_than_one_row":
        (4096, 256, 1024, lambda k: 300, 1, 1),
    "exactly_R_rows":
        (4096, 256, 1024, lambda k: 4 * k * 256, 1, 0),
    "R_rows_and_a_partial_last_row":
        (4096, 256, 1024, lambda k: 4 * k * 256 + 77, 2, 1),
    "last_unit_of_fewer_than_R_rows":
        (4096, 256, 1024, lambda k: 6 * k * 256, 2, 0),
    # three large rows, two to a unit, then one small row and a partial
    "large_rows_then_small_rows":
        (512, 256, 1024, lambda k: 3 * k * 512 + k * 256 + 9, 3, 1),
    # R * small = 400 bytes: the Pallas shell pads to 512 in its program
    "small_block_no_tile_multiple":
        (10000, 100, 400, lambda k: 5 * k * 100 + 1, 2, 1),
    # a block wider than the batch stays cut in columns: 2 rows x 2 cuts
    "block_wider_than_the_batch":
        (4096, 256, 128, lambda k: 2 * k * 256, 4, 0),
}


def _unit_reference(raw: bytes, k: int, large: int, small: int, encode):
    """The shard files `raw` must encode to: upstream's row-major
    striping k wide (large rows while more than one large row's bytes
    remain, then small rows, the last zero-padded), by hand, under the
    plain reference's generator."""
    files = [bytearray() for _ in range(k)]
    at = 0
    while len(raw) - at > k * large:
        for j in range(k):
            files[j] += raw[at:at + large]
            at += large
    while at < len(raw):
        for j in range(k):
            files[j] += raw[at:at + small].ljust(small, b"\0")
            at += small
    return encode(np.array([np.frombuffer(bytes(f), dtype=np.uint8)
                            for f in files]))


def _use_codec(monkeypatch, kind: str, tag: str) -> None:
    """`jax`: the XLA shell, as resolved; `pallas_interpret`: the fused
    kernel under the Pallas interpreter at a 256-byte tile."""
    if kind == "jax":
        monkeypatch.setenv("WEEDTPU_EC_CODEC", "jax")
        return
    from seaweedfs_tpu.ops import codecs, pallas_gf
    codec = pallas_gf.PallasRSCodec(
        codecs._code_for(codecs.parse_tag(tag)), tile=UNIT_TILE,
        interpret=True)
    monkeypatch.setattr(ec_files, "_get_codec", lambda *a, **kw: codec)


@pytest.mark.parametrize("case", list(UNIT_CASES))
@pytest.mark.parametrize("kind", ["jax", "pallas_interpret"])
@pytest.mark.parametrize("tag", list(UNIT_TAGS))
def test_encode_unit_is_a_span_of_the_dat_map(tmp_path, monkeypatch, tag,
                                              kind, case):
    """A device codec is handed the .dat by spans of its map, up to
    batch // block stripe rows at once: the shard set is the plain
    reference's byte for byte, one dispatch a unit, and no row is copied
    on the host but a last one that runs past the end of the .dat."""
    k, n, encode = UNIT_TAGS[tag]
    large, small, batch, size, units, staged = UNIT_CASES[case]
    raw = np.random.default_rng(31).bytes(size(k))
    base = str(tmp_path / "9")
    with open(base + ".dat", "wb") as f:
        f.write(raw)
    _use_codec(monkeypatch, kind, tag)
    seen: list[int] = []
    real = ec_files._dispatch_parity

    def spy(codec, spans, job=None, unit=None, stripes=0, block=0):
        # 1-D spans that hold `stripes` rows of k blocks, nothing 2-D
        assert all(s.ndim == 1 and s.dtype == np.uint8 for s in spans)
        step, rest = divmod(sum(s.nbytes for s in spans), stripes * k)
        assert rest == 0 and step in (small, large, batch), (step, rest)
        # the rows' block size rides along for the stage events
        assert block in (small, large) and step <= block
        seen.append(stripes)
        return real(codec, spans, job=job, unit=unit, stripes=stripes,
                    block=block)
    monkeypatch.setattr(ec_files, "_dispatch_parity", spy)
    stats: dict = {}
    ec_files.write_ec_files(base, large_block=large, small_block=small,
                            batch_size=batch, stats=stats, codec_tag=tag)
    want = _unit_reference(raw, k, large, small, encode)
    assert want.shape[0] == n
    for i in range(n):
        with open(base + layout.to_ext(i), "rb") as f:
            assert f.read() == want[i].tobytes(), f"shard file {i}"
    assert not os.path.exists(base + layout.to_ext(n))
    assert len(seen) == units, seen
    assert max(seen) <= max(1, batch // small, batch // large)
    assert stats["rows_staged"] == staged
    assert {"read_s", "stall_s", "h2d_s", "device_wait_s",
            "d2h_copy_s"} <= set(stats), sorted(stats)
    assert stats["mode"] == "pipelined"


@pytest.mark.parametrize("kind", ["jax", "numpy"])
def test_cancel_between_units_leaves_the_previous_shard_set(tmp_path,
                                                            monkeypatch,
                                                            kind):
    """`cancel()` is asked once a unit: a generate cancelled after its
    first unit raises, leaves no `.tmp` behind and every file of the set
    that was there as it was."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", kind)
    base = str(tmp_path / "4")
    rng = np.random.default_rng(4)
    with open(base + ".dat", "wb") as f:
        f.write(rng.bytes(9 * 10 * 256 + 5))  # ten rows: three units of 4
    ec_files.write_ec_files(base, large_block=8192, small_block=256,
                            batch_size=1024)
    before = {i: open(base + layout.to_ext(i), "rb").read()
              for i in range(layout.TOTAL_SHARDS)}
    with open(base + ".dat", "wb") as f:  # another volume's bytes
        f.write(rng.bytes(9 * 10 * 256 + 5))
    asked: list[int] = []
    progressed: list[int] = []

    def cancel() -> bool:
        asked.append(len(progressed))
        return len(progressed) >= 1

    with pytest.raises(ec_files.EncodeCancelled):
        ec_files.write_ec_files(base, large_block=8192, small_block=256,
                                batch_size=1024, cancel=cancel,
                                progress=progressed.append)
    assert asked == [0, 1]  # before each unit, not each row
    assert progressed == [4 * 10 * 256]
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    for i, was in before.items():
        assert open(base + layout.to_ext(i), "rb").read() == was, i


# ---- golden fixture ---------------------------------------------------

@pytest.mark.skipif(reference_fixture("weed/storage/erasure_coding/1.dat") is None,
                    reason="reference mount absent")
def test_ec_reference_fixture_end_to_end(tmp_path):
    """Encode the reference's fixture volume with OUR pipeline at the same
    test block sizes ec_test.go uses, then read every live needle back from
    shards with two shards missing."""
    shutil.copy(reference_fixture("weed/storage/erasure_coding/1.dat"), tmp_path / "1.dat")
    shutil.copy(reference_fixture("weed/storage/erasure_coding/1.idx"), tmp_path / "1.idx")
    os.chmod(tmp_path / "1.dat", 0o644)
    os.chmod(tmp_path / "1.idx", 0o644)
    base = str(tmp_path / "1")
    encode_small(base)
    for sid in (3, 12):
        os.remove(base + layout.to_ext(sid))
    vol = Volume(str(tmp_path), "", 1)
    live = {nid: vol.read_needle(nid).data
            for nid, (off, sz) in vol.nm.items() if t.size_is_valid(sz)}
    vol.close()
    ev = ec_volume.EcVolume(base)
    for nid, data in live.items():
        assert ev.read_needle(nid).data == data, nid
    ev.close()
