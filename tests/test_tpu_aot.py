"""The served kernels compile for a TPU v5e — checked without a chip.

`jax.experimental.topologies` describes a `v5e:2x2` host to the installed
libtpu, and `jit(...).lower(...).compile()` against its devices runs the
real Mosaic + XLA:TPU pipeline.  Tier-1 otherwise only ever sees the XLA
body (CPU `auto`) or the Pallas interpreter, so a kernel the compiler
refuses, or a mesh wrapper that fails to trace around the Pallas body,
would first show on the chip.  Compilation is all this proves: execution
and numerics at real size are `chip_smoke.py`'s.

Plus the two selection rules a CPU run can check: `WEEDTPU_EC_CODEC=tpu`
never means the interpreter, and a host-codec process never initialises a
JAX backend.
"""

import json
import pathlib
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.models import rs
from seaweedfs_tpu.ops import dispatch, pallas_gf
from seaweedfs_tpu.parallel import mesh as pmesh

MIB = 1024 * 1024
TILE = pallas_gf.TPU_TILE


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a v5e:2x2 topology (no chip needed)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU compiler here: {e}")
    assert len(topo.devices) == 4
    return topo.devices


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_trace_names(compiled, kernel: str, module: str) -> None:
    """The compiled program carries the names the benchmark sums device
    time by: a device trace names an `XLA Ops` event by the op's whole HLO
    line and an `XLA Modules` event by the module, and
    benchmark/kernels.json matches the former.  Pinned by `name=` on the
    pallas_call and codec_base.named_jit, not derived from what the Python
    functions are called."""
    table = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                        / "kernels.json").read_text())
    patterns = [re.compile(p) for p in table["kernels"][kernel]["patterns"]]
    call = _kernel_call(compiled, module)
    assert any(p.search(call) for p in patterns), call[:120]
    others = [k for k, spec in table["kernels"].items()
              if spec["patterns"] != table["kernels"][kernel]["patterns"]
              and any(re.search(p, call) for p in spec["patterns"])]
    assert not others, (call[:120], others)


def _kernel_call(compiled, module: str) -> str:
    """The HLO line of the program's one Mosaic custom call, as a device
    trace names its event; the program is module `module`."""
    text = compiled.as_text()
    assert text.startswith(f"HloModule {module},"), text[:80]
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1, calls
    return calls[0]


def _lifted(C, sharding):
    """(spec of C's plane-major lift, kpad), by the mesh seam's own rule."""
    seam = pmesh._ApplyKernel("pallas", TILE)
    bm = seam.lift(C)
    return _spec(bm.shape, bm.dtype, sharding), seam._kpad(C.shape[1])


# rows of a wide encode unit: sixteen 1 MiB stripe rows, DEFAULT_BATCH
# bytes of every shard
WIDE = 16


@pytest.mark.parametrize("tag, wanted, width, shape", [
    ("rs_10_4", None, MIB, (4, 10)),
    ("rs_10_4", [3], 16 * MIB, (1, 10)),
    ("rs_10_4", [0, 1], TILE, (2, 10)),
    ("lrc_12_2_2", None, MIB, (4, 12)),
    ("lrc_12_2_2", [3], 16 * MIB, (1, 6)),
    ("lrc_12_2_2", [0], TILE, (1, 6)),
    ("lrc_12_2_2", [0, 1], 16 * MIB, (2, 12)),
    ("lrc_12_2_2", [0, 6], TILE, (2, 12)),
    ("rs_10_4", "unit", WIDE * MIB, (4, 10)),
    ("lrc_12_2_2", "unit", WIDE * MIB, (4, 12)),
    ("rs_10_4", "column", WIDE * MIB, (4, 10)),
    ("rs_10_4", [3, 10], 16 * MIB, (2, 10)),
    ("rs_10_4", [3, 10], 10 * MIB, (2, 10)),
], ids=["encode_10_4", "rebuild_batch_1_row", "read_2_rows_smallest_bucket",
        "lrc_encode_4_12", "lrc_local_rebuild_batch_1_6",
        "lrc_local_read_smallest_bucket_1_6", "lrc_global_rebuild_2_12",
        "lrc_read_one_lost_in_each_group_2_12",
        "encode_unit_16_rows_10_4", "lrc_encode_unit_16_rows_4_12",
        "encode_unit_column_cut_10_4", "node7_rebuild_batch_2_rows",
        "node7_rebuild_short_batch_2_rows"])
def test_gf_apply_compiles_for_v5e(v5e, tag, wanted, width, shape):
    """The served single-chip programs at TPU_TILE: [k, 1 MiB] under the
    parity matrix (the scrubber's 2-D window), the wide encode unit as the
    encode seam puts it (ops/dispatch.dispatch_parity: sixteen 1 MiB
    stripe rows of a `.dat`, each row one 1-D array of k MiB, laid out
    [k, 16 MiB] inside the program, parity back as m runs of [16 MiB]), and
    the two ends of what the reconstruct seam runs
    (ops/dispatch.reconstruct_batch): a rebuild batch, the widest bucket,
    and a degraded read at the narrowest.  RS(10,4), and Azure LRC(12,2,2)
    (`lrc_12_2_2`): its [4, 12] parity, the [1, 6] of ones that rebuilds
    one lost data shard from its local group (k = 6 under PLANE_PAD 16)
    and the [2, 12] of the global fallback, each as its basis stages it.
    And the unit of a large-block row (a volume past ten large blocks: the
    north star's 30 GB one, `vol30g.encode`'s at 1/32): a column cut, one
    stripe row of ten 16 MiB pieces a block apart in the `.dat`, laid out
    [10, 16 MiB] by the `stripes=1` program, the same kernel, four runs
    back.  And the decode a server lost from a seven-server cluster leaves
    every volume with (`node7.rebuild_2lost`: data shard 3 and parity
    shard 10 of each), in place at `[2, 10]`, two rows back as one
    `[2 * W]` run: at 16 MiB, a 256 MiB volume's whole batch, and at
    10 MiB (80 tiles), its short last batch, which goes up from the maps
    at its own width (`dispatch.dispatch_reconstruct`)."""
    from seaweedfs_tpu.ops import codecs
    code = codecs._code_for(codecs.parse_tag(tag))
    column = wanted == "column"
    unit = wanted == "unit" or column
    if wanted is None or unit:
        C = code.parity_matrix
    else:
        have = [i for i in range(code.n) if i not in wanted]
        C = code.decode_matrix(have, wanted)
    assert C.shape == shape
    one = SingleDeviceSharding(v5e[0])
    bm, kpad = _lifted(C, one)
    m, k = C.shape
    # a decode as the seam runs it, 1-D in and out (codec_base.stacked):
    # a wide stack as its ten rows, a narrow one as one array; an encode
    # unit as its stripe rows
    stripes = 0
    if column:
        # dispatch.unit_pieces leaves the k spans of a column cut whole
        pieces = dispatch.unit_pieces(
            [np.empty(width, np.uint8) for _ in range(k)], 1)
        assert [len(p) for p in pieces] == [width] * k
        data = tuple(_spec((width,), jnp.uint8, one) for _ in range(k))
        stripes = 1
    elif unit:
        assert k * MIB >= dispatch.ROW_PUTS_FROM  # so: row by row
        data = tuple(_spec((k * MIB,), jnp.uint8, one) for _ in range(WIDE))
        stripes = WIDE
    elif wanted is None:
        data = _spec((k, width), jnp.uint8, one)
    elif width >= dispatch.ROW_PUTS_FROM:
        data = tuple(_spec((width,), jnp.uint8, one) for _ in range(k))
    else:
        data = _spec((k * width,), jnp.uint8, one)
    tile = pallas_gf.matrix_tile(m, kpad, TILE)
    assert tile == TILE  # the rule leaves these programs as they were
    compiled = pallas_gf._gf_apply.lower(
        bm, data, k=k, m=m, kpad=kpad, tile=tile, interpret=False,
        linear=wanted is not None, stripes=stripes).compile()
    if unit:
        assert [o.shape for o in compiled.out_info] == [(width,)] * m
    else:
        assert compiled.out_info.shape == (
            (m, width) if wanted is None else (m * width,))
    _assert_trace_names(compiled,
                        "gf_apply" if wanted is None or unit
                        else "gf_reconstruct",
                        "jit__gf_apply")
    if unit or width >= dispatch.ROW_PUTS_FROM:
        # rows put one by one are read where they lie (PR 39): no HBM
        # layout round the kernel
        assert pallas_gf.in_place_block(
            tuple(d.shape[0] for d in data), k, stripes, tile) is not None
        _assert_no_layout(compiled)


# the opcodes with which a program lays rows out in HBM: a stack, the
# stripe rows' turn, a row written into a tiled [k, W], its loop
LAYOUT_OPS = re.compile(
    r"= [^=]*? (concatenate|transpose|dynamic-update-slice|while)\(")


def _assert_no_layout(compiled) -> None:
    """The program is its kernel: no layout op outside the one Mosaic
    custom call, whose own body (its backend config) is not HLO."""
    ops = [ln.strip() for ln in compiled.as_text().splitlines()
           if 'custom_call_target="tpu_custom_call"' not in ln
           and LAYOUT_OPS.search(ln)]
    assert not ops, ops[:3]


@pytest.mark.parametrize("m, k", [(4, 10), (1, 10), (2, 10), (4, 12),
                                  (1, 6), (2, 12)])
def test_tile_rule_keeps_rs_and_lrc_at_the_platform_tile(m, k):
    """`PallasGFMatrix`'s tile follows from the matrix since PM-MSR's
    [72, 72] (pallas_gf.matrix_tile); every matrix the RS(10,4) and
    LRC(12,2,2) cells lift keeps TPU_TILE, so none of their programs
    changed."""
    kpad = pmesh._ApplyKernel("pallas", TILE)._kpad(k)
    assert kpad == pallas_gf.PLANE_PAD
    assert pallas_gf.matrix_tile(m, kpad, TILE) == TILE == 131072


# PM-MSR(9,16), `msr_9_16`: alpha = 8 sub-rows a file, so the matrices
# work on 72 virtual rows and the byte interleave is inside the program
ALPHA = 8


@pytest.mark.parametrize("what, shape, tile", [
    ("unit", (72, 72), 8192),
    ("rebuild_batch", (8, 72), 32768),
    ("read_two_lost_narrowest_bucket", (16, 72), 16384),
    ("repair_at_the_rebuilder", (8, 16), 65536),
    ("repair_at_a_helper", (1, 8), TILE),
])
def test_msr_programs_compile_for_v5e(v5e, what, shape, tile):
    """The `msr_9_16` programs as the seams put them, with the tile the
    rule gives each matrix: the sixteen-row encode unit (sixteen 1-D rows
    of 9 MiB in, split into 72 sub-rows, [72, 72], merged, nine runs of
    [16 MiB] out), a one-lost rebuild batch (nine survivor rows of 16 MiB,
    [8, 72]), a two-lost degraded read at the narrowest bucket (one array
    of nine rows of `MSRFileCodec.tile` bytes, [16, 72]), and the
    regenerating repair's two applies, 2-D as `dispatch.apply_matrix`
    puts them: [1, 8] on a helper's sub-rows, [8, 16] on what sixteen
    helpers sent."""
    from seaweedfs_tpu.ops import msr
    code = msr.get_code(9, 16)
    files = msr.MSRFileCodec(types.SimpleNamespace(code=code, tile=8192))
    one = SingleDeviceSharding(v5e[0])
    linear, stripes, alpha = True, 0, ALPHA
    if what == "unit":
        C = code.parity_matrix
        data = tuple(_spec((9 * MIB,), jnp.uint8, one) for _ in range(WIDE))
        stripes, out = WIDE, [(WIDE * MIB,)] * 9
    elif what == "rebuild_batch":
        C = code.decode_matrix(
            [r for f in range(18) if f != 3 for r in code.node_rows(f)],
            code.node_rows(3))
        data = tuple(_spec((16 * MIB,), jnp.uint8, one) for _ in range(9))
        out = (16 * MIB,)
    elif what == "read_two_lost_narrowest_bucket":
        C = code.decode_matrix(
            [r for f in range(2, 18) for r in code.node_rows(f)],
            code.node_rows(0) + code.node_rows(1))
        assert files.tile == 8 * 8192 < dispatch.ROW_PUTS_FROM
        data = _spec((9 * files.tile,), jnp.uint8, one)
        out = (2 * files.tile,)
    else:
        linear, alpha = False, 1
        C = code.repair_coeff(3) if shape == (1, 8) else \
            code.repair_matrix(3, [f for f in range(18) if f != 3][:16])
        data = _spec((shape[1], tile), jnp.uint8, one)
        out = (shape[0], tile)
    assert C.shape == shape
    bm, kpad = _lifted(C, one)
    m, k = C.shape
    assert pallas_gf.matrix_tile(m, kpad, TILE) == tile
    compiled = pallas_gf._gf_apply.lower(
        bm, data, k=k, m=m, kpad=kpad, tile=tile, interpret=False,
        linear=linear, stripes=stripes, alpha=alpha).compile()
    if what == "unit":
        assert [o.shape for o in compiled.out_info] == out
    else:
        assert compiled.out_info.shape == out
    _assert_trace_names(compiled,
                        "gf_reconstruct" if "lost" in what or
                        what == "rebuild_batch" else "gf_apply",
                        "jit__gf_apply")


def test_gf_apply_batch_compiles_for_v5e(v5e):
    """The fleet-conversion unit batch, U = 4."""
    code = rs.get_code(10, 4)
    one = SingleDeviceSharding(v5e[0])
    bm, kpad = _lifted(code.parity_matrix, one)
    compiled = pallas_gf._gf_apply_batch.lower(
        bm, _spec((4, 10, MIB), jnp.uint8, one),
        k=10, m=4, kpad=kpad, tile=TILE, interpret=False).compile()
    _assert_trace_names(compiled, "gf_apply_batch", "jit__gf_apply_batch")


def _assert_kernel_file_names(compiled, kernel: str, module: str) -> None:
    """`_assert_trace_names` for a kernel that is a file of its own,
    `benchmark/kernels/<kernel>.json` (a narrower pattern of a kernel
    kernels.json has: the events of one matrix)."""
    spec = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                       / "kernels" / f"{kernel}.json").read_text())
    assert spec["name"] == kernel
    call = _kernel_call(compiled, module)
    assert any(re.search(p, call) for p in spec["patterns"]), call[:120]


def test_gf_apply_batch_int8_compiles_for_v5e(v5e):
    """The batch kernel under PM-MSR(9,16)'s [72, 72] as the fleet unit
    program runs it on a chip: one unit of sixteen 9 MiB stripe rows split
    into 72 sub-rows, [1, 72, 2 MiB], kpad 80, at the tile the rule gives
    the matrix (8192: at the platform's 131072 the body would hold 368 MiB
    of planes and accumulator).  Its event on a device trace is what
    `benchmark/kernels/gf_apply_batch_int8.json` sums, and
    `gf_apply_batch`'s pattern takes it too (`encode_kernel_s_per_gb`)."""
    from seaweedfs_tpu.ops import msr
    code = msr.get_code(9, 16)
    one = SingleDeviceSharding(v5e[0])
    bm, kpad = _lifted(code.parity_matrix, one)
    seam = pmesh._ApplyKernel("pallas", TILE)
    tile = seam.matrix_tile(72, 72)
    assert (kpad, tile) == (80, 8192) == (
        seam._kpad(72), pallas_gf.matrix_tile(72, 80, TILE))
    compiled = pallas_gf._gf_apply_batch.lower(
        bm, _spec((1, 72, WIDE * MIB // ALPHA), jnp.uint8, one),
        k=72, m=72, kpad=kpad, tile=tile, interpret=False).compile()
    assert compiled.out_info.shape == (1, 72, WIDE * MIB // ALPHA)
    _assert_kernel_file_names(compiled, "gf_apply_batch_int8",
                              "jit__gf_apply_batch")
    _assert_trace_names(compiled, "gf_apply_batch", "jit__gf_apply_batch")


def test_mesh_encoders_trace_and_compile_with_the_pallas_body(v5e):
    """The mesh wrappers around the Pallas body, over all four devices:
    shard_map checks varying-manual-axes, and a pallas_call whose
    out_shape carries no vma fails that check at trace time."""
    code = rs.get_code(10, 4)
    fleet_mesh = Mesh(np.array(v5e), ("unit",))
    enc = pmesh.FleetUnitEncoder(code, fleet_mesh, kernel="pallas",
                                 tile=TILE)
    assert enc.kernel.kind == "pallas" and enc.n_devices == 4
    bm = _spec(enc.parity_bits.shape, jnp.int8,
               NamedSharding(fleet_mesh, P()))
    compiled = enc._encode.lower(
        bm, _spec((8, 10, MIB), jnp.uint8, enc.in_sharding)).compile()
    # [U, k, B] in and [U, m, B] out differ in shape: nothing to alias,
    # which is why the encoder donates nothing
    assert "input_output_alias" not in compiled.as_text()
    _assert_trace_names(compiled, "gf_apply_batch", "jit_batch_body")

    col_mesh = Mesh(np.array(v5e), ("data",))
    col = pmesh.ShardedRSEncoder(code, col_mesh, kernel="pallas", tile=TILE)
    col._apply_cols.lower(
        _spec(col.parity_bits.shape, jnp.int8,
              NamedSharding(col_mesh, P())),
        _spec((10, 4 * MIB), jnp.uint8,
              NamedSharding(col_mesh, P(None, "data")))).compile()


@pytest.mark.parametrize("tag, rows", [("rs_10_4", WIDE), ("rs_10_4", 10),
                                       ("msr_9_16", WIDE)])
def test_fleet_unit_program_compiles_for_v5e(v5e, tag, rows):
    """The fleet stream's unit program over all four devices, as the seam
    launches it (ops/dispatch.dispatch_parity_batch on spans): one unit a
    chip, each of its `rows` stripe rows a 1-D array of k MiB on that
    chip (a global [4 * k MiB] array sharded over the unit axis), laid
    out [1, k, rows MiB] inside the shard_map, through the batch kernel,
    parity back as one 1-D run of [rows MiB] a parity file a chip.  The
    benchmark's 256 MiB volumes cut a 16-row and a 10-row unit each under
    rs_10_4.  Under msr_9_16 (16 rows and 13; the 13-row program takes
    44 s to compile here and is left to the chip) each file's bytes are
    split into eight sub-rows inside the same program: [1, 72, 2 MiB]
    through the [72, 72] kernel at tile 8192, nine file runs back."""
    from seaweedfs_tpu.ops import codecs
    spec = codecs.parse_tag(tag)
    code = codecs._code_for(spec)
    fleet_mesh = Mesh(np.array(v5e), ("unit",))
    enc = pmesh.FleetUnitEncoder(code, fleet_mesh, kernel="pallas",
                                 tile=TILE)
    assert enc.tile == (TILE if spec.alpha == 1 else 8192)
    k, m = spec.k, spec.m
    assert k * MIB >= dispatch.ROW_PUTS_FROM  # so: row by row
    bm = _spec(enc.parity_bits.shape, jnp.int8,
               NamedSharding(fleet_mesh, P()))
    units = (tuple(_spec((4 * k * MIB,), jnp.uint8, enc.in_sharding)
                   for _ in range(rows)),)
    compiled = enc._encode_linear.lower(bm, units, stripes=rows,
                                        alpha=spec.alpha).compile()
    (runs,) = compiled.out_info
    assert [o.shape for o in runs] == [(4 * rows * MIB,)] * m
    assert all(o.sharding.is_equivalent_to(enc.in_sharding, 1)
               for o in runs)  # (rows MiB,) a device
    text = compiled.as_text()
    assert not re.search(r"all-(reduce|gather|to-all)|collective-permute",
                         text)  # parity is unit-local
    assert "input_output_alias" not in text
    _assert_trace_names(compiled, "gf_apply_batch", "jit_batch_body")
    if spec.alpha > 1:
        _assert_kernel_file_names(compiled, "gf_apply_batch_int8",
                                  "jit_batch_body")


def test_tpu_codec_off_tpu_raises_instead_of_interpreting(monkeypatch):
    from seaweedfs_tpu.storage.ec import ec_files
    assert jax.default_backend() == "cpu"
    pallas_gf._get_codec_cached.cache_clear()
    monkeypatch.setenv("WEEDTPU_EC_CODEC", "tpu")
    with pytest.raises(RuntimeError, match="backend found is 'cpu'"):
        ec_files._get_codec()
    # the interpreter stays reachable, by name only
    assert pallas_gf.PallasRSCodec(rs.get_code(10, 4), tile=256,
                                   interpret=True).interpret is True


def test_host_codec_process_initialises_no_jax_backend():
    """fleet_codec() under WEEDTPU_EC_CODEC=cpp and the /perf snapshot,
    in a fresh process: jax gets imported
    (ops.native_codec does) but no backend may come up — on a chip host
    that process would take the chip from the volume server."""
    code = (
        "import os\n"
        "os.environ['WEEDTPU_EC_CODEC'] = 'cpp'\n"
        "os.environ.pop('WEEDTPU_CONVERT_CODEC', None)\n"
        "from seaweedfs_tpu.ops import fleet_convert\n"
        "from seaweedfs_tpu.stats import pipeline, profile\n"
        "codec = fleet_convert.fleet_codec()\n"
        "assert type(codec).__name__ == 'NativeRSCodec', codec\n"
        "snap = pipeline.local_snapshot()\n"
        "assert snap['codecs'] == [{'asked': 'cpp', 'tag': 'rs_10_4',\n"
        "                           'codec': 'NativeRSCodec'}], snap\n"
        "import sys\n"
        "from jax._src import xla_bridge\n"
        "assert 'jax' in sys.modules\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    from seaweedfs_tpu import native
    if not native.available():
        pytest.skip("no native codec here")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
