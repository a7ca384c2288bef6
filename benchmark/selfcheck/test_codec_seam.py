"""The seam between the harness and a configuration's reference module:
a module of another family and shard count (fixture_xor_2_1.py: 2 + 1
files) goes through `describe_volume`, `compare_shards`, the read
classifier and the volume cache, so that what they know of a shard set
is what the module and the `codec` block tell them."""

import os
import types

import pytest

import fixture_xor_2_1 as fixture
import harness
import reference
import run
from conftest import hand_made_volume

CODEC = fixture.CODEC
BLOCK = CODEC["small_block_bytes"]


@pytest.fixture
def sealed(tmp_path):
    """A hand-made sealed volume `c_7`: three needles in a `.dat` of
    20,000 bytes (five blocks of 4,096: three rows of two), its `.idx`,
    and what the write path would have acknowledged."""
    raw = bytes(range(256)) * 78 + bytes(32)
    assert len(raw) == 20000
    # id, offset (a multiple of 8), body size: the first inside block 0,
    # the second astride blocks 1 | 2, the third inside block 3
    placed = [(1, 8, 100), (2, 8000, 500), (3, 3 * BLOCK + 8, 1000)]
    srv, base, loaded, _bodies = hand_made_volume(tmp_path, raw, placed)
    return srv, base, raw, loaded


def write_set(base, raw):
    """The three shard files of `raw`, by hand: blocks alternate between
    files 0 and 1, file 2 is their XOR."""
    pad = raw + bytes(-len(raw) % (2 * BLOCK))
    blocks = [pad[i:i + BLOCK] for i in range(0, len(pad), BLOCK)]
    a, b = b"".join(blocks[0::2]), b"".join(blocks[1::2])
    for i, data in enumerate((a, b, bytes(x ^ y for x, y in zip(a, b)))):
        with open(f"{base}.ec{i:02d}", "wb") as f:
            f.write(data)


def test_describe_and_compare_take_the_modules_shard_count(sealed):
    srv, base, raw, loaded = sealed
    volume = harness.describe_volume(srv, loaded, fixture, CODEC)
    assert len(volume["shards_sha256"]) == 3
    assert volume["shard_size"] == 3 * BLOCK and volume["dat_bytes"] == 20000
    assert [n[3:] for n in volume["needles"]] == [
        [8, reference.record_length(100)], [8000, reference.record_length(500)],
        [3 * BLOCK + 8, reference.record_length(1000)]]
    write_set(base, raw)
    assert harness.compare_shards(base, volume) == []
    # a flipped byte is named by its file
    with open(base + ".ec02", "r+b") as f:
        f.seek(5000)
        byte = f.read(1)
        f.seek(5000)
        f.write(bytes([byte[0] ^ 1]))
    assert harness.compare_shards(base, volume) == [
        f"{base}.ec02 differs from the reference"]
    assert harness.compare_shards(base, volume, only=[0, 1]) == []
    # a file of another size says both sizes
    with open(base + ".ec00", "ab") as f:
        f.write(b"\0")
    wrong = harness.compare_shards(base, volume, only=[0])
    assert len(wrong) == 1 and f"{3 * BLOCK + 1} bytes" in wrong[0]
    # a missing file is named, and nothing else is looked at
    os.remove(base + ".ec01")
    assert harness.compare_shards(base, volume) == [
        f"{base}.ec01 is missing"]


def test_a_reference_that_miscounts_its_set_is_refused(sealed):
    srv, _base, _raw, loaded = sealed
    liar = types.SimpleNamespace(**{
        name: getattr(fixture, name) for name in
        ("read_idx", "needle_id_of", "record_length", "reference_shards")},
        shard_count=lambda codec: 4)
    with pytest.raises(harness.BenchFailure, match="3 shard hashes"):
        harness.describe_volume(srv, loaded, liar, CODEC)


def test_reads_are_classed_by_the_modules_layout_rule(sealed):
    srv, _base, _raw, loaded = sealed
    volume = harness.describe_volume(srv, loaded, fixture, CODEC)
    driver = run.load_module("drivers", "closed_loop_reads")
    first, astride, third = volume["needles"]
    # block b lives in file b % 2: blocks 0, 1 | 2, 3
    lost_1 = driver.classifier(fixture, CODEC, volume, [1])
    assert [lost_1(n) for n in (first, astride, third)] == \
        ["healthy", "degraded", "degraded"]
    lost_0 = driver.classifier(fixture, CODEC, volume, [0])
    assert [lost_0(n) for n in (first, astride, third)] == \
        ["degraded", "degraded", "healthy"]
    # the parity file holds no byte of the `.dat`
    lost_2 = driver.classifier(fixture, CODEC, volume, [2])
    assert {lost_2(n) for n in volume["needles"]} == {"healthy"}


def test_the_cache_rebuilds_an_entry_built_under_another_block(
        sealed, tmp_path, monkeypatch):
    srv, base, _raw, loaded = sealed
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    volume = harness.describe_volume(srv, loaded, fixture, CODEC)
    cache = harness.VolumeCache("cfg", CODEC, 5, False)
    assert cache.lookup() is None
    cache.store([volume], os.path.dirname(base))
    assert harness.VolumeCache("cfg", dict(CODEC), 5, False).lookup() == \
        [volume]
    for other in (dict(CODEC, small_block_bytes=8192),
                  dict(CODEC, reference="reference")):
        cache.store([volume], os.path.dirname(base))
        assert harness.VolumeCache("cfg", other, 5, False).lookup() is None
        assert not os.path.exists(cache.dir)


def test_reference_of_holds_the_block_to_the_modules_word():
    good = {"reference": "reference", "tag": "rs_6_3", "family": "rs",
            "data_shards": 6, "parity_shards": 3,
            "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
    assert harness.reference_of(good) is reference
    with pytest.raises(harness.BenchFailure, match="rs_6_3"):
        harness.reference_of(dict(good, data_shards=10, parity_shards=4))
    with pytest.raises(harness.BenchFailure, match="is missing"):
        harness.reference_of(dict(good, reference="reference_of_no_one"))


def test_fill_offers_the_codec_tag():
    body = harness.fill({"volume": "{vid}", "codec": "{codec}"},
                        vid=3, codec="lrc_12_2_2")
    assert body == {"volume": 3, "codec": "lrc_12_2_2"}
