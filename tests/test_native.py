"""Native C++ runtime library tests: GF(2^8) kernels cross-checked against
the numpy reference field, CRC32C and AES-256-GCM against known-answer
vectors, and the native RS codec against the slow codec the same way the
reference's ec_test.go cross-checks shards."""

import secrets

import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import gf

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native lib unavailable: {native.load_error()}")


def test_gf_mul_matches_numpy_tables():
    lib = native._load()
    rng = np.random.default_rng(1)
    for a, b in rng.integers(0, 256, (200, 2)):
        assert lib.wn_gf_mul(int(a), int(b)) == gf.GF_MUL_TABLE[a, b]


def test_gf_matmul_matches_reference():
    rng = np.random.default_rng(2)
    mat = rng.integers(0, 256, (4, 10), dtype=np.uint8)
    data = rng.integers(0, 256, (10, 4097), dtype=np.uint8)
    got = native.gf_matmul(mat, data)
    want = gf.gf_matmul(mat, data)
    assert (got == want).all()


def test_gf_matmul_impls_agree():
    """Every compiled kernel (scalar / AVX2 / GFNI where the host has it)
    produces identical output — the GFNI affine-matrix construction is
    cross-checked against the split-table path, not just the field axioms."""
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 256, (5, 12), dtype=np.uint8)
    data = rng.integers(0, 256, (12, 8192 + 77), dtype=np.uint8)
    auto_impl = native.gf_impl()
    results = {}
    try:
        for impl in (native.GF_IMPL_SCALAR, native.GF_IMPL_AVX2,
                     native.GF_IMPL_AUTO):
            native.set_gf_impl(impl)
            results[impl] = native.gf_matmul(mat, data)
    finally:
        native.set_gf_impl(native.GF_IMPL_AUTO)
    want = gf.gf_matmul(mat, data)
    for impl, got in results.items():
        assert (got == want).all(), (impl, auto_impl)


def test_gf_mul_slice_accumulate():
    rng = np.random.default_rng(3)
    src = rng.integers(0, 256, 1000, dtype=np.uint8)
    dst = rng.integers(0, 256, 1000, dtype=np.uint8)
    want = dst ^ gf.GF_MUL_TABLE[0x1D, src]
    native.gf_mul_slice(0x1D, src, dst, accumulate=True)
    assert (dst == want).all()


def test_native_codec_roundtrip():
    from seaweedfs_tpu.ops import native_codec
    codec = native_codec.get_codec(10, 4)
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (10, 513), dtype=np.uint8)
    shards = codec.encode(data)
    assert (shards[:10] == data).all()
    # reference cross-check
    assert (shards == codec.code.encode_numpy(data)).all()
    # drop any 4, rebuild
    survivors = {i: shards[i] for i in (0, 2, 3, 5, 6, 8, 9, 10, 12, 13)}
    rebuilt = codec.reconstruct(survivors)
    for i in (1, 4, 7, 11):
        assert (rebuilt[i] == shards[i]).all(), i


# (large block, small block, batch, .dat bytes over k, the unit: its place
# in `_iter_spans`' walk) for the native shell's span apply
SPAN_UNITS = {
    # four whole small rows, one span of the map
    "whole_rows": (4096, 256, 1024, lambda k: 9 * k * 256, 0),
    # a large-block row's first column cut: k spans a block apart
    "column_cut": (4096, 256, 1024, lambda k: 3 * k * 4096, 0),
    # three whole rows and the short last one, staged into a zeroed buffer
    "staged_last_row": (4096, 256, 1024, lambda k: 3 * k * 256 + 77, 0),
}


@pytest.mark.parametrize("tag", ["rs_10_4", "lrc_12_2_2", "msr_9_16"])
@pytest.mark.parametrize("case", sorted(SPAN_UNITS))
def test_native_span_apply_equals_the_numpy_reference(case, tag):
    """`encode_parity_linear` of the native shell (and of the MSR file
    codec over it) on a unit as the engines select it in a `.dat`'s map:
    one `[m, W]` array whose rows are the parity files' runs, equal to
    the numpy reference's parity of the unit laid out as `[k, W]`."""
    from seaweedfs_tpu.ops import codecs, dispatch
    from seaweedfs_tpu.storage.ec import ec_files
    large, small, batch, size, at = SPAN_UNITS[case]
    codec, ref = codecs.resolve(tag, "cpp"), codecs.resolve(tag, "numpy")
    k = codec.k
    dat = np.random.default_rng(6).integers(0, 256, size(k), dtype=np.uint8)
    row_start, block, col, step, _, rows = list(ec_files._iter_spans(
        len(dat), large, small, batch, k))[at]
    spans, staged = ec_files._unit_spans(dat, len(dat), k, row_start, block,
                                         col, step, rows)
    assert (len(spans), rows, staged) == {
        "whole_rows": (1, 4, 0), "column_cut": (k, 1, 0),
        "staged_last_row": (2, 4, 1)}[case]
    got = codec.encode_parity_linear(spans, rows)
    data = np.concatenate(spans).reshape(rows, k, step).transpose(
        1, 0, 2).reshape(k, rows * step)
    want = dispatch.materialize(dispatch.dispatch_parity(ref, data))
    assert isinstance(got, np.ndarray) and got.shape == (codec.m, rows * step)
    assert np.array_equal(got, want)
    assert all(got[i].flags.c_contiguous for i in range(codec.m))


def test_native_reconstruct_reads_row_views_without_a_stack(monkeypatch):
    """`NativeRSCodec.reconstruct` over views of one buffer, as a rebuild
    hands it a batch's rows in the maps: the reference's rows, and no
    `np.stack` on the way."""
    from seaweedfs_tpu.ops import native_codec
    codec = native_codec.get_codec(10, 4)
    code = rs.get_code(10, 4)
    data = np.random.default_rng(8).integers(0, 256, (10, 3000),
                                             dtype=np.uint8)
    shards = code.encode_numpy(data)
    flat = shards.reshape(-1)  # each survivor a view of one mapping
    n = shards.shape[1]
    survivors = {i: flat[i * n:(i + 1) * n] for i in (0, 2, 3, 5, 6, 8, 9,
                                                      10, 12, 13)}
    want = code.reconstruct_numpy(dict(survivors), wanted=[1, 4, 11])

    def no_stack(*a, **kw):
        raise AssertionError("np.stack called")

    monkeypatch.setattr(np, "stack", no_stack)
    got = codec.reconstruct(survivors, wanted=[1, 4, 11])
    monkeypatch.undo()
    assert sorted(got) == [1, 4, 11]
    for i in (1, 4, 11):
        assert np.array_equal(got[i], want[i]), i
        assert np.array_equal(got[i], shards[i]), i


def test_crc32c_known_answer():
    assert native.crc32c(b"123456789") == 0xE3069283
    assert native.crc32c(b"") == 0
    # incremental == one-shot
    a = native.crc32c(b"hello, ")
    assert native.crc32c(b"world", a) == native.crc32c(b"hello, world")


def test_aes256_gcm_nist_vectors():
    # NIST SP 800-38D style known answers (all-zero key/nonce)
    assert native.aes256_gcm_seal(b"\0" * 32, b"\0" * 12, b"").hex() == \
        "530f8afbc74536b9a963b4f1c4cb738b"
    sealed = native.aes256_gcm_seal(b"\0" * 32, b"\0" * 12, b"\0" * 16)
    assert sealed.hex() == ("cea7403d4d606b6e074ec5d3baf39d18"
                            "d0d1c8a799996bf0265b98b5d48ab919")


def test_cipher_roundtrip_and_tamper():
    from seaweedfs_tpu.utils import cipher
    msg = secrets.token_bytes(100_000)
    key, sealed = cipher.encrypt(msg)
    assert cipher.decrypt(key, sealed) == msg
    bad = bytearray(sealed)
    bad[20] ^= 1
    with pytest.raises(cipher.CipherError):
        cipher.decrypt(key, bytes(bad))


def test_ec_files_cpp_codec_roundtrip(tmp_path, monkeypatch):
    """write_ec_files with WEEDTPU_EC_CODEC=cpp produces byte-identical
    shards to the numpy reference codec."""
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "cpp")
    from seaweedfs_tpu.storage.ec import ec_files, layout
    rng = np.random.default_rng(5)
    dat = rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    base = str(tmp_path / "1")
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    ec_files.write_ec_files(base, large_block=10_000, small_block=100)
    code = rs.get_code(10, 4)
    # stripe 0 (large row): rebuild parity on host and compare a slice
    row = np.frombuffer(dat[:100_000], dtype=np.uint8).reshape(10, 10_000)
    parity = code.encode_numpy(row)[10:]
    for pi in range(4):
        with open(base + layout.to_ext(10 + pi), "rb") as f:
            got = np.frombuffer(f.read(10_000), dtype=np.uint8)
        assert (got == parity[pi]).all(), pi


def test_library_is_tied_to_the_cpu_that_built_it(tmp_path, monkeypatch):
    """-march=native makes a build valid only on the CPU that made it, and
    the chip tool copies the tree (artefacts included) to another machine:
    the file name carries a key over source + flags + CPU features, so a
    library from elsewhere is never the one dlopened — it gets rebuilt."""
    import os
    import shutil
    here = native._so_name()
    assert os.path.basename(native._build()) == here
    monkeypatch.setattr(native, "_cpu_features", lambda: "another cpu")
    foreign = native._so_name()
    assert foreign != here
    # on "the other machine" the copied library is ignored and replaced
    ndir = tmp_path / "native"
    shutil.copytree(native._NATIVE_DIR, ndir)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(ndir))
    assert (ndir / here).exists()
    built = native._build()
    assert os.path.basename(built) == foreign and os.path.exists(built)
    assert not (ndir / here).exists()
