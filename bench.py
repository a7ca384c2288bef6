#!/usr/bin/env python
"""EC benchmark suite — the north-star metrics (BASELINE.json / BASELINE.md).

Primary metric (unchanged across rounds): RS(10,4) erasure-encode GB/s of
volume data through the fused Pallas GF(2^8) kernel on one TPU chip, vs the
reference's CPU codec (klauspost/reedsolomon v1.12.1 AVX2 driven by
weed/storage/erasure_coding/ec_encoder.go:120-224 with 10x256KB buffers and
file I/O in the loop).

The baseline is MEASURED when possible: the repo's own C++ AVX2 codec
(native/weedtpu_native.cc — same pshufb split-nibble scheme klauspost uses)
run in the reference's exact shape (10x256KB strips, read from a .dat,
14 shard files written in the loop). When the native extension is missing
the klauspost README figure (5.0 GB/s) is used and labeled as such.

Extra metrics (all in the `extra` field of the one JSON line):
  ec_encode_rs{6_3,12_4,16_4}   kernel encode GB/s, RS(k,m) sweep — all
                                kernel metrics run at the same ~640MiB/iter
                                depth (r4 benched the sweep shallower and the
                                fixed per-iter cost skewed them low)
  ec_rebuild_rs10_4_m{1,4}      kernel reconstruct GB/s, 1 / 4 lost shards
                                (the degraded-read hot loop, store_ec.go:339-393)
  ec_encode_rs10_4_mesh         the column-parallel mesh codec on a 1-chip
                                mesh: shard_map overhead vs the plain kernel
  ec_encode_batch4_place        4 volumes batched through encode_batch_place
                                (BASELINE's multi-volume + all-to-all shard
                                placement config) — DEGENERATE single-chip
                                placement here; the 8-way sharded shape runs
                                in dryrun_multichip.  Gated: must stay
                                >= BATCH_PLACE_TOL x the single-call kernel
                                (batch_place_regression, nonzero exit)
  ec_encode_tile{,_config}      the Pallas tile re-tune sweep: every
                                SWEEP_TILES candidate measured on THIS
                                chip, winner pinned via WEEDTPU_EC_TILE
                                for every codec built afterwards
  fleet_convert_gbps            e2e multi-volume conversion through the
                                interleaved device-resident stream
                                (ops/fleet_convert), total volume bytes /
                                wall; BYTE-VERIFIED per volume against the
                                numpy reference (fleet_convert_failed gate
                                on mismatch)
  ec_encode_e2e_host_1g         file -> 14 shard files through write_ec_files
                                on the host codec at 1GiB (the primary e2e
                                number; GFNI+AVX512 when the host has it,
                                zero-copy mmap encode + copy_file_range)
  ec_encode_e2e_ceiling_1g      the same shard-file I/O with the codec
                                REMOVED — the host's measured I/O ceiling
  ec_encode_e2e_ceiling_frac    e2e / ceiling; ~1.0 == the e2e number IS the
                                host's disk bandwidth, not codec cost
  ec_encode_e2e_serial_1g       the host codec forced through the SERIAL
                                strategy (WEEDTPU_EC_PIPELINE=serial) at
                                1GiB, for comparison with the pipelined
                                default
  ec_encode_e2e_pipeline_ratio  pipelined / serial throughput (median of
                                interleaved pairs) — the regression gate:
                                below 0.90 the bench EXITS NONZERO
  ec_encode_e2e_overlap_frac    achieved stage overlap of the primary e2e
                                run: 1 - wall/(sum of stage seconds), 0 ==
                                fully serial stages
  ec_encode_e2e_host{,_40m}     legacy probe sizes (320MiB / 40MiB)
  *_detail                      per-stage seconds of the best rep (read_s /
                                encode_s / d2h_s / write_data_s /
                                write_parity_s / stall_s), wall_s,
                                overlap_frac, mode, + the cold-inode
                                first-rep GB/s
  ec_encode_e2e_device          file -> 14 shard files through
                                write_ec_files on the device codec (TPU
                                runs only; a 20MiB probe — the served-size
                                cell is ROADMAP S1/R3)
  blob_write_rps/blob_read_rps  the reference's own headline benchmark shape
                                (1KB files, c=16, weed benchmark README
                                numbers) on an in-process cluster — this
                                harness has ONE shared core vs the published
                                MacBook i7 figures
  blob_read_rps_degraded        degraded EC needle reads/s through the
                                batched read engine (all intervals planned
                                up front, coalesced per shard, survivors
                                read in parallel, ONE reconstruction
                                dispatch per needle) vs the per-interval
                                serial baseline (WEEDTPU_EC_READ=serial);
                                falling behind serial by >10% (median of
                                interleaved pairs) FAILS the bench
                                (blob_read_degraded_regression)
  filer_stream_mbps             whole-file filer streaming with the bounded
                                readahead pipeline (WEEDTPU_READAHEAD) vs
                                the serial fetch->write loop (=0), chunk
                                cache disabled so every GET pays real
                                volume fetches; same regression gate
                                (filer_stream_pipeline_regression)
  baseline_avx2_refshape        the measured baseline itself (forced to the
                                AVX2 path: the baseline is klauspost AVX2)
  baseline_avx2_kernel          pure-buffer AVX2 kernel GB/s
  host_gfni_kernel              pure-buffer GFNI+AVX512 kernel GB/s (the
                                production host codec on GFNI machines)

Timing method (TPU): kernel metrics chain iterations inside one jit via
lax.fori_loop with a data dependency (output folded into the carry),
difference two iteration counts, and subtract a baseline loop with
identical data movement but no encode.  (Replacing this with kernel time
read from a profiler trace is ROADMAP S1.)

Platform: the device cells run on whatever backend JAX initialises, in this
one process — no probe child, no fallback.  If no accelerator initialises
the run exits nonzero with JAX's error (or says that JAX came up on the
CPU).  JAX_PLATFORMS=cpu is the explicit request for the host-codec run
(backend "cpu-native"); nothing it measures is written under a device
metric's name by accident.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "backend", "baseline_gbps",
   "baseline_kind", "extra": {...}}
where backend is "tpu" | "cpu-native" | "cpu-xla" (the last two only
under an explicit JAX_PLATFORMS=cpu).
"""

import functools
import json
import os
import queue
import sys
import tempfile
import time

import numpy as np

KLAUSPOST_AVX2_GBPS = 5.0  # klauspost README single-stream 10+4 AVX2 figure

RS_SWEEP = [(6, 3), (12, 4), (16, 4)]


def free_port() -> int:
    """An OS-assigned localhost port for the in-process bench clusters."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# measured baseline: the repo's AVX2 codec in the reference's encode shape
# ---------------------------------------------------------------------------

def _bench_baseline_refshape() -> float | None:
    """ec_encoder.go:198-224 in miniature: 256KB strip buffers, parity via
    the AVX2 codec, 14 shard files written inside the timed loop."""
    from seaweedfs_tpu import native
    if not native.available():
        return None
    from seaweedfs_tpu.ops import native_codec
    codec = native_codec.get_codec(10, 4)
    strip = 256 * 1024
    strips = 16  # 40 MiB of volume data per rep
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, strips * 10 * strip, dtype=np.uint8)
    native.set_gf_impl(native.GF_IMPL_AVX2)  # the baseline IS the AVX2 path
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-bench-") as d:
            dat = os.path.join(d, "v.dat")
            payload.tofile(dat)
            batch = np.empty((10, strip), dtype=np.uint8)
            best = float("inf")
            for _ in range(3):
                outs = [open(os.path.join(d, f"v.ec{i:02d}"), "wb")
                        for i in range(14)]
                t0 = time.perf_counter()
                with open(dat, "rb") as f:
                    for _ in range(strips):
                        for j in range(10):
                            batch[j] = np.frombuffer(f.read(strip), np.uint8)
                        parity = codec.encode_parity(batch)
                        for j in range(10):
                            outs[j].write(batch[j].tobytes())
                        for i in range(4):
                            outs[10 + i].write(parity[i].tobytes())
                for o in outs:
                    o.close()
                best = min(best, time.perf_counter() - t0)
    finally:
        native.set_gf_impl(native.GF_IMPL_AUTO)
    return strips * 10 * strip / 1e9 / best


# ---------------------------------------------------------------------------
# kernel metrics (device): chained-loop differencing
# ---------------------------------------------------------------------------

def _timed(loop_fn, x, iters):
    import jax
    out = loop_fn(x, iters)  # first call compiles
    _ = np.asarray(jax.device_get(out.ravel()[:16]))
    t0 = time.perf_counter()
    out = loop_fn(x, iters)
    _ = np.asarray(jax.device_get(out.ravel()[:16]))
    return time.perf_counter() - t0


def _chained(body_fn):
    import jax

    @functools.partial(jax.jit, static_argnames=("iters",))
    def loop(x, iters):
        return jax.lax.fori_loop(0, iters, lambda i, v: body_fn(v), x)
    return loop


def _bench_chained(body_fn, data, on_tpu: bool, noop_rows: int = 0,
                   iters: int = 20, baseline_fn=None) -> float:
    """GB/s of `data` (all elements) processed per body_fn application,
    net of a same-shape data-movement-only loop (default: roll+xor on the
    leading axis; pass `baseline_fn` for other shapes). `iters` must put
    the differenced loop time well above the device-sync noise."""
    import jax.numpy as jnp
    enc_loop = _chained(body_fn)
    if baseline_fn is None:
        def baseline_fn(x):
            return jnp.concatenate(
                [x[noop_rows:], x[:noop_rows] ^ jnp.uint8(1)], axis=0)
    base_loop = _chained(baseline_fn)
    lo, hi = (2, 2 + iters) if on_tpu else (1, 5)
    best = float("inf")
    for _ in range(3):
        t_base = _timed(base_loop, data, hi) - _timed(base_loop, data, lo)
        t_enc = _timed(enc_loop, data, hi) - _timed(enc_loop, data, lo)
        net = (t_enc - t_base) / (hi - lo)
        if net > 0:
            best = min(best, net)
    if not np.isfinite(best):
        return 0.0
    return data.size / 1e9 / best


def _device_codec(k: int, m: int, on_tpu: bool):
    from seaweedfs_tpu.ops import gfmat_jax, pallas_gf
    # fused Pallas kernel on TPU; XLA bit-sliced path elsewhere (the Pallas
    # interpreter would benchmark the emulator, not the codec)
    return pallas_gf.get_codec(k, m) if on_tpu else gfmat_jax.get_codec(k, m)


def _bench_encode_kernel(k: int, m: int, n: int, on_tpu: bool,
                         iters: int = 20, codec_factory=_device_codec) -> float:
    import jax.numpy as jnp
    codec = codec_factory(k, m, on_tpu)
    parity_fn = codec.encode_parity
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (k, n), dtype=np.uint8))
    # mesh codecs H2D with their own column sharding so the chained loop
    # carry starts (and stays) sharded — an unsharded carry would pay a
    # reshard every iteration and measure the resharder, not the codec
    place = getattr(codec, "place_columns", None)
    if place is not None:
        data = place(data)
    return _bench_chained(
        lambda x: jnp.concatenate([x[m:], parity_fn(x)], axis=0),
        data, on_tpu, noop_rows=m, iters=iters)


def _bench_tile_sweep(extra: dict, n: int, on_tpu: bool,
                      iters: int = 12) -> None:
    """Re-tune the fused Pallas kernel's byte-column tile on THIS chip +
    runtime: measure every SWEEP_TILES candidate at the primary depth and
    pin the winner via WEEDTPU_EC_TILE so every codec constructed after
    this (the primary metric, the mesh paths, the fleet pipeline) runs
    the measured-best shape.  The whole sweep lands in the bench JSON,
    a refused candidate with the compiler's error text."""
    if not on_tpu:
        return  # the XLA path has no tile; CPU pallas is the emulator
    if os.environ.get("WEEDTPU_EC_TILE"):
        extra["ec_encode_tile_config"] = {
            "chosen": int(os.environ["WEEDTPU_EC_TILE"]),
            "pinned": True}
        return
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.ops import pallas_gf
    sweep: dict = {}
    best_t, best_v = None, 0.0
    for t in pallas_gf.SWEEP_TILES:
        if n % t:
            continue

        def factory(k, m, _on, t=t):
            return pallas_gf.PallasRSCodec(rs.get_code(k, m), tile=t)

        try:
            v = _bench_encode_kernel(10, 4, n, True, iters=iters,
                                     codec_factory=factory)
        except Exception as e:  # e.g. a tile whose VMEM blocks don't fit
            sweep[str(t)] = pallas_gf.sweep_failure(e)
            continue
        sweep[str(t)] = round(v, 2)
        if v > best_v:
            best_t, best_v = t, v
    if best_t is not None:
        os.environ["WEEDTPU_EC_TILE"] = str(best_t)
        extra["ec_encode_tile"] = best_t
        # persist winner + sweep table + chip fingerprint: resolved_tile
        # honours a matching pin on later plain runs, and the tile-drift
        # sentinel (stats/pipeline.py) re-validates it in the background
        try:
            pin_path = pallas_gf.save_tile_pin(best_t, best_v, sweep)
            extra["ec_encode_tile_pin"] = pin_path
            from seaweedfs_tpu.stats import profile as _profile
            _profile.set_ceiling("device", best_v)
        except Exception as e:
            print(f"bench: tile pin persist failed: {e}", file=sys.stderr)
    extra["ec_encode_tile_config"] = {"chosen": best_t, "sweep": sweep}


def _mesh_codec_factory(k: int, m: int, on_tpu: bool):
    """The column-parallel mesh codec (parallel/mesh.py) over every
    attached device — on one chip a degenerate mesh, so its number is the
    shard_map overhead vs the plain Pallas path (the 8-device scaling
    shape is exercised by __graft_entry__.dryrun_multichip)."""
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.parallel import mesh as pmesh
    return pmesh.ShardedRSEncoder(rs.get_code(k, m), pmesh.make_mesh())


def _bench_batch_place(k: int, m: int, vols: int, n: int, on_tpu: bool,
                       iters: int = 20) -> float:
    """Multi-volume batched encode + all-to-all shard placement
    (BASELINE.json's batched config; parallel/mesh.py encode_batch_place).
    Degenerate single-chip placement on this harness — the 8-way sharded
    shape runs in __graft_entry__.dryrun_multichip — so the number is the
    batched-volumes kernel path's throughput in volume bytes."""
    import jax.numpy as jnp
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.parallel import mesh as pmesh
    mesh = pmesh.make_mesh(axis_names=("vol", "data"), shape=(1, 1))
    enc = pmesh.ShardedRSEncoder(rs.get_code(k, m), mesh,
                                 col_axis="data", vol_axis="vol")
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (vols, k, n), dtype=np.uint8))

    def body(x):
        placed = enc.encode_batch_place(x)
        return jnp.concatenate([x[:, m:, :], placed[:, k:k + m, :]], axis=1)

    return _bench_chained(
        body, data, on_tpu, iters=iters,
        baseline_fn=lambda x: jnp.concatenate(
            [x[:, m:, :], x[:, :m, :] ^ jnp.uint8(1)], axis=1))


def _bench_rebuild_kernel(k: int, m: int, lost: int, n: int,
                          on_tpu: bool, iters: int = 20) -> float:
    """Reconstruct the first `lost` (data) shards from k survivors — the
    decode-matrix apply of the degraded-read loop (store_ec.go:374-393).
    GB/s is survivor bytes processed (k rows), matching how the rebuild
    path streams k survivor files."""
    import jax.numpy as jnp
    from seaweedfs_tpu.models import rs
    code = rs.get_code(k, m)
    codec = _device_codec(k, m, on_tpu)
    present = list(range(lost, k + m))
    wanted = list(range(lost))
    mat = codec._factory(code.decode_matrix(present, wanted))
    rng = np.random.default_rng(1)
    data = jnp.asarray(rng.integers(0, 256, (k, n), dtype=np.uint8))
    return _bench_chained(
        lambda x: jnp.concatenate([x[lost:], mat(x)], axis=0),
        data, on_tpu, noop_rows=lost, iters=iters)


# ---------------------------------------------------------------------------
# end-to-end: file -> 14 shard files through the pipelined write_ec_files
# ---------------------------------------------------------------------------

def _bench_e2e(size: int, batch: int, codec_env: str | None,
               reps: int = 4, detail: dict | None = None,
               pipeline_env: str | None = None,
               profile_stacks: bool = False) -> float:
    """file -> shards through write_ec_files in the production layout
    (1MB small blocks, column-batched steps), best of `reps`.

    Between reps the committed shard files are renamed back to the `.tmp`
    names write_ec_files recycles, so steady-state reps overwrite the same
    warm inodes instead of faulting fresh page cache — the benchmark
    targets the codec pipeline, not the host's page allocator (this VM
    faults never-touched memory at ~0.2 GB/s through its balloon; a
    production storage host does not).  The cold first rep (fresh inodes,
    cold page cache) is reported separately in `detail` alongside the
    per-stage attribution of the best rep.

    `pipeline_env` forces WEEDTPU_EC_PIPELINE (serial|pipelined) so the
    two strategies can be raced on the same codec and host."""
    from seaweedfs_tpu.stats import profile as _profile
    from seaweedfs_tpu.storage.ec import ec_files, layout
    old = os.environ.get("WEEDTPU_EC_CODEC")
    old_pipe = os.environ.get("WEEDTPU_EC_PIPELINE")
    if codec_env is not None:
        os.environ["WEEDTPU_EC_CODEC"] = codec_env
    if pipeline_env is not None:
        os.environ["WEEDTPU_EC_PIPELINE"] = pipeline_env
    # stack capture is opt-in (the device/XLA scenario): the sampler is
    # cheap but the host-1g numbers gate regressions and stay untaxed
    profiler = _profile.SamplingProfiler(97).start() \
        if profile_stacks and detail is not None else None
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-e2e-") as d:
            base = os.path.join(d, "v")
            rng = np.random.default_rng(2)
            rng.integers(0, 256, size, dtype=np.uint8).tofile(base + ".dat")
            best = float("inf")
            cold = None
            best_stats: dict = {}
            for _ in range(reps):
                for i in range(layout.TOTAL_SHARDS):
                    f = base + layout.to_ext(i)
                    if os.path.exists(f):
                        os.replace(f, f + ".tmp")
                stats: dict = {}
                t0 = time.perf_counter()
                ec_files.write_ec_files(
                    base, large_block=1 << 40, small_block=1024 * 1024,
                    batch_size=batch, stats=stats)
                el = time.perf_counter() - t0
                if cold is None:
                    cold = el
                if el < best:
                    best, best_stats = el, stats
        if detail is not None:
            detail["cold_gbps"] = round(size / 1e9 / cold, 3)
            for k_ in ("write_data_s", "encode_s", "write_parity_s",
                       "read_s", "d2h_s", "stall_s", "wall_s",
                       "overlap_frac", "mode"):
                if k_ in best_stats:
                    detail[k_] = (round(best_stats[k_], 4)
                                  if isinstance(best_stats[k_], float)
                                  else best_stats[k_])
            if profiler is not None:
                # where the e2e scenario actually burns its time, sampled
                # across all reps: the top-5 collapsed stacks land in the
                # bench JSON so a regressed round carries its own profile
                detail["profile_top5"] = \
                    profiler.collapsed(limit=5).splitlines()
        return size / 1e9 / best
    finally:
        if profiler is not None:
            profiler.stop()
        if codec_env is not None:
            if old is None:
                os.environ.pop("WEEDTPU_EC_CODEC", None)
            else:
                os.environ["WEEDTPU_EC_CODEC"] = old
        if pipeline_env is not None:
            if old_pipe is None:
                os.environ.pop("WEEDTPU_EC_PIPELINE", None)
            else:
                os.environ["WEEDTPU_EC_PIPELINE"] = old_pipe


def _bench_fleet_convert(extra: dict, kind: str | None = None,
                         vol_mb: int = 32, n_vols: int = 4,
                         reps: int = 2) -> None:
    """e2e fleet conversion: N volumes -> N shard sets through ONE
    interleaved device-resident stream (ops/fleet_convert).  Records
    `fleet_convert_gbps` (total volume bytes / wall) plus per-stage
    attribution, and BYTE-VERIFIES the first stripe row of every volume
    against the numpy reference codec — a fast wrong conversion must
    fail the run (fleet_convert_failed), not win the trajectory."""
    from seaweedfs_tpu.models import rs
    from seaweedfs_tpu.ops import fleet_convert
    from seaweedfs_tpu.storage.ec import layout
    size = vol_mb * 1024 * 1024
    rng = np.random.default_rng(5)
    with tempfile.TemporaryDirectory(prefix="weedtpu-fleet-") as d:
        bases = []
        for i in range(n_vols):
            base = os.path.join(d, f"v{i}")
            rng.integers(0, 256, size, dtype=np.uint8).tofile(base + ".dat")
            bases.append(base)
        codec = fleet_convert.fleet_codec(kind)
        best = float("inf")
        best_stats: dict = {}
        for _ in range(reps):
            # recycle committed shards back to .tmp names between reps so
            # steady-state reps overwrite warm inodes (same rationale as
            # _bench_e2e: measure the pipeline, not the page allocator)
            for base in bases:
                for i in range(layout.TOTAL_SHARDS):
                    f = base + layout.to_ext(i)
                    if os.path.exists(f):
                        os.replace(f, f + ".tmp")
            stats: dict = {}
            t0 = time.perf_counter()
            fleet_convert.convert_volumes(bases, codec=codec, stats=stats)
            el = time.perf_counter() - t0
            if el < best:
                best, best_stats = el, stats
        # byte-identity spot check: first stripe row of every volume vs
        # the numpy reference
        code = rs.get_code(layout.DATA_SHARDS, layout.PARITY_SHARDS)
        sb = layout.SMALL_BLOCK_SIZE
        row = layout.DATA_SHARDS * sb
        for base in bases:
            with open(base + ".dat", "rb") as f:
                head = np.frombuffer(f.read(row), np.uint8)
            if head.size < row:  # sub-row volume: the layout zero-pads
                head = np.concatenate(
                    [head, np.zeros(row - head.size, np.uint8)])
            par = code.encode_numpy(
                head.reshape(layout.DATA_SHARDS, sb))[layout.DATA_SHARDS:]
            for pi in range(layout.PARITY_SHARDS):
                with open(base + layout.to_ext(
                        layout.DATA_SHARDS + pi), "rb") as f:
                    got = np.frombuffer(f.read(sb), np.uint8)
                if not np.array_equal(got, par[pi]):
                    extra["fleet_convert_failed"] = True
                    print(f"bench: fleet conversion NOT byte-identical "
                          f"to the numpy reference ({base} parity {pi}). "
                          f"Failing the bench run.", file=sys.stderr)
                    return
        extra["fleet_convert_gbps"] = round(n_vols * size / 1e9 / best, 3)
        extra["fleet_convert_verified"] = True
        detail = {k_: (round(v, 4) if isinstance(v, float) else v)
                  for k_, v in best_stats.items()
                  if isinstance(v, (int, float, str))}
        extra["fleet_convert_detail"] = detail
        # flat numeric stage keys land in bench_history.jsonl (the
        # nested detail dict does not): the per-stage breakdown becomes
        # a round-over-round series, not a bench-day printout
        for k_, v in best_stats.items():
            if k_.endswith("_s") and k_ != "wall_s" and \
                    isinstance(v, (int, float)):
                extra[f"fleet_convert_stage_{k_[:-2]}"] = round(v, 4)


def _native_kernel_gbps(k: int, m: int, impl: int | None = None) -> float:
    """Pure host-buffer encode timing of the C++ codec (no file IO, no
    allocation in the loop).  `impl` forces a kernel (native.GF_IMPL_*):
    AVX2 is the klauspost-equivalent baseline; auto picks GFNI+AVX512
    where the host has it."""
    from seaweedfs_tpu import native
    from seaweedfs_tpu.models import rs
    code = rs.get_code(k, m)
    mat = code.parity_matrix
    n = 4 * 1024 * 1024
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    out = np.empty((m, n), dtype=np.uint8)
    rows = [data[j] for j in range(k)]
    outs = [out[r] for r in range(m)]
    if impl is not None:
        native.set_gf_impl(impl)
    try:
        native.gf_matmul_ptrs(mat, rows, outs, n)  # warm tables/caches
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            iters = 4
            for _ in range(iters):
                native.gf_matmul_ptrs(mat, rows, outs, n)
            best = min(best, (time.perf_counter() - t0) / iters)
    finally:
        if impl is not None:
            native.set_gf_impl(native.GF_IMPL_AUTO)
    return k * n / 1e9 / best


def _native_rebuild_gbps(k: int, m: int, lost: int) -> float:
    from seaweedfs_tpu.ops import native_codec
    codec = native_codec.get_codec(k, m)
    n = 4 * 1024 * 1024
    rng = np.random.default_rng(1)
    shards = {i: rng.integers(0, 256, n, dtype=np.uint8)
              for i in range(lost, k + m)}
    wanted = list(range(lost))
    codec.reconstruct(shards, wanted=wanted)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        codec.reconstruct(shards, wanted=wanted)
        best = min(best, time.perf_counter() - t0)
    return k * n / 1e9 / best


def _try(extra: dict, key: str, fn, *args, **kw) -> None:
    try:
        v = fn(*args, **kw)
        if v is not None:
            extra[key] = round(v, 3)
    except Exception as e:  # any one metric failing must not kill the line
        print(f"bench: {key} failed: {e}", file=sys.stderr)


# the measured host disk ceiling of THIS round, stamped by
# _bench_e2e_host when the probe runs: {"gbps": ..., "aio": ...} — the
# ceiling is only meaningful alongside the engine mode it was probed
# under (a buffered ceiling does not bound an io_uring data path)
_PROBED_DISK_CEILING: dict = {}


def _bench_config(backend: str) -> dict:
    """This round's measurement config: backend + resolved Pallas tile +
    chip fingerprint + host aio engine mode (and the disk ceiling probed
    under it).  Stamped into every bench_history.jsonl entry so the
    trajectory gate compares like-for-like — a CPU-fallback round (or a
    different chip generation under the same backend string, or a
    buffered-fallback round under an io_uring history) must not
    masquerade as a regression against the real thing."""
    cfg: dict = {"backend": backend}
    tile = os.environ.get("WEEDTPU_EC_TILE")
    if tile:
        try:
            cfg["tile"] = int(tile)
        except ValueError:
            pass
    if "jax" in sys.modules:  # the cpu-native path never imports jax
        try:
            from seaweedfs_tpu.ops import pallas_gf
            cfg["fingerprint"] = pallas_gf.chip_fingerprint()
        except Exception:
            pass
    try:
        from seaweedfs_tpu.storage import aio as _aio
        cfg["aio"] = _aio.engine_label()
    except Exception:
        pass
    try:
        # which erasure code untagged volumes get this round: the heal /
        # repair-traffic numbers depend on the matrix family they ran
        # under (CODEC_SCOPED_METRICS gate on this)
        from seaweedfs_tpu.ops import codecs as _codecs
        cfg["codec"] = _codecs.default_tag()
    except Exception:
        pass
    if _PROBED_DISK_CEILING:
        cfg["disk_ceiling"] = dict(_PROBED_DISK_CEILING)
    # serving-plane shape: the knee is measured through the location
    # cache / hot tier / QoS stack, so rounds with different serving
    # config are not comparable (SERVING_SCOPED_METRICS gate on this)
    try:
        from seaweedfs_tpu.utils.vid_cache import _env_float as _ef
        cfg["serving"] = {
            "hot_tier": os.environ.get("WEEDTPU_HOT_TIER", "1") != "0",
            "vid_cache_ttl": _ef("WEEDTPU_VID_CACHE_TTL", 10.0),
            "qos": _ef("WEEDTPU_S3_QOS_RATE", 0.0) > 0,
        }
    except Exception:
        pass
    return cfg


def _record_roofline(extra: dict) -> None:
    """Flatten the run's per-kernel roofline fractions (achieved GB/s /
    measured resource ceiling, stats/profile.py) into numeric extra
    keys, so they land in bench_history.jsonl next to the headline
    metrics and 'encode went D2H-bound' is visible round over round."""
    from seaweedfs_tpu.stats import profile as _profile
    snap = _profile.roofline_snapshot()
    for row in snap["rows"]:
        frac = row.get("ceiling_frac")
        if frac is not None:
            extra[f"roofline_{row['resource']}_{row['kernel']}"] = frac


def _record_trajectory(gbps: float, backend: str, extra: dict) -> None:
    """Bench trajectory tracking: append this run's headline metrics to
    bench_history.jsonl (created by the first run) and emit a
    bench_regression gate — nonzero exit — when a TRAJECTORY_GATED
    metric drops more than 10% below the best prior round.

    Comparisons are same-backend and against the best of only the most
    recent TRAJECTORY_LOOKBACK such rounds: a bounded lookback means a
    recorded outlier ages out instead of ratcheting the bar forever."""
    repo = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo, "bench_history.jsonl")
    entries: list[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        entries.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError as e:
            print(f"bench: cannot read {path}: {e}", file=sys.stderr)
    mets_now = {"ec_encode_rs10_4": round(gbps, 3)}
    for k, v in extra.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            mets_now[k] = v
    cfg = _bench_config(backend)
    fp_now = cfg.get("fingerprint")

    def like_for_like(e: dict) -> bool:
        """Same backend AND same chip fingerprint where both recorded
        one — rounds predating config stamps stay comparable by backend
        alone (excluding them would drop every existing prior)."""
        if e.get("backend") != backend:
            return False
        fp = (e.get("config") or {}).get("fingerprint")
        return fp is None or fp_now is None or fp == fp_now

    # host-I/O-bound metrics additionally compare only against rounds
    # measured under the same aio engine mode (mirroring the fingerprint
    # rule): a buffered-fallback round must not read as an io_uring
    # regression — nor set the bar an io_uring round is then judged by.
    # None-tolerant for the same reason as fingerprint: rounds predating
    # the stamp stay comparable.
    aio_now = cfg.get("aio")

    serving_now = cfg.get("serving")
    codec_now = cfg.get("codec")

    def metric_comparable(e: dict, m: str) -> bool:
        if m.startswith(SERVING_SCOPED_METRICS):
            return (e.get("config") or {}).get("serving") == serving_now
        if m.startswith(CODEC_SCOPED_METRICS):
            # like-codec rounds only (the config.aio pattern): a heal
            # measured under MSR regeneration must not set — or be
            # judged by — an RS round's repair-traffic bar
            c = (e.get("config") or {}).get("codec")
            if not (c is None or codec_now is None or c == codec_now):
                return False
        if not m.startswith(AIO_SCOPED_METRICS):
            return True
        a = (e.get("config") or {}).get("aio")
        return a is None or aio_now is None or a == aio_now

    comparable = [e for e in entries if like_for_like(e)]
    comparable = comparable[-TRAJECTORY_LOOKBACK:]
    if not comparable:
        # empty or freshly-wiped history (or a first round on a new
        # backend/chip): there is nothing to gate against, so this run
        # is RECORD-ONLY — not a vacuous pass.  Say exactly which gates
        # were skipped (the no-silent-caps rule): the next same-config
        # round gates against what we record now.
        skipped = [m for m in (*TRAJECTORY_GATED, *TRAJECTORY_GATED_MIN)
                   if m in mets_now]
        extra["bench_trajectory_record_only"] = True
        print(f"bench: trajectory gate skipped — no comparable prior "
              f"{backend} rounds in bench_history.jsonl "
              f"({len(entries)} entries total); recording only. "
              f"Ungated this run: {skipped or 'none measured'}",
              file=sys.stderr)
    regressions: dict = {}
    for m in TRAJECTORY_GATED:
        now_v = mets_now.get(m)
        if now_v is None:
            # the metric legitimately did not run on this backend/host;
            # a measured 0.0 still compares (and gates) below
            continue
        best = max((e.get("metrics", {}).get(m) or 0.0
                    for e in comparable if metric_comparable(e, m)),
                   default=0.0)
        if best > 0 and now_v < TRAJECTORY_TOL * best:
            regressions[m] = {"value": now_v, "best_prior": best,
                              "ratio": round(now_v / best, 3)}
    for m in TRAJECTORY_GATED_MIN:
        # lower-is-better (e.g. repair_network_ratio): gate on RISING
        # >10% above the best (minimum) prior recorded round
        now_v = mets_now.get(m)
        if now_v is None:
            continue
        priors = [e.get("metrics", {}).get(m) for e in comparable
                  if e.get("metrics", {}).get(m)
                  and metric_comparable(e, m)]
        best = min(priors, default=0.0)
        if best > 0 and now_v > best / TRAJECTORY_TOL:
            regressions[m] = {"value": now_v, "best_prior": best,
                              "ratio": round(now_v / best, 3)}
    extra["bench_rounds_prior"] = len(entries)
    if regressions:
        extra["bench_regression"] = regressions
        for m, r in regressions.items():
            print(f"bench: REGRESSION — {m} = {r['value']} is "
                  f"{r['ratio']:.2f}x the best prior {backend} round "
                  f"({r['best_prior']}); >10% off the trajectory best. "
                  f"Failing the bench run.", file=sys.stderr)
    entry = {"n": len(entries) + 1,
             "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
             "backend": backend, "config": cfg, "metrics": mets_now}
    if extra.get("bench_regression"):
        entry["regressed"] = sorted(regressions)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry, separators=(",", ":")) + "\n")
    except OSError as e:
        print(f"bench: cannot append {path}: {e}", file=sys.stderr)


def _emit(gbps: float, backend: str, baseline: float | None,
          extra: dict) -> None:
    base_kind = "measured-avx2-refshape" if baseline else "klauspost-readme"
    base = baseline or KLAUSPOST_AVX2_GBPS
    try:
        _record_roofline(extra)
    except Exception as e:  # roofline stamping must not eat the run
        print(f"bench: roofline recording failed: {e}", file=sys.stderr)
    try:
        _record_trajectory(gbps, backend, extra)
    except Exception as e:  # trajectory bookkeeping must not eat the run
        print(f"bench: trajectory recording failed: {e}", file=sys.stderr)
    print(json.dumps({
        "metric": "ec_encode_rs10_4",
        "value": round(gbps, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbps / base, 2),
        "backend": backend,
        "baseline_gbps": round(base, 3),
        "baseline_kind": base_kind,
        "extra": extra,
    }))


def main() -> int:
    # the canary loop would inject probe traffic into every in-process
    # bench cluster below; the flow/canary overhead bench re-enables it
    # deliberately for its ON arm
    os.environ.setdefault("WEEDTPU_CANARY_INTERVAL", "0")
    # the host-codec run is an explicit request, never a fallback
    force_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if not force_cpu:
        # fail before the (long) host cells, not after them: this raises
        # JAX's own error when the configured platform cannot initialise
        import seaweedfs_tpu  # noqa: F401  (places the compile cache)
        import jax
        if jax.default_backend() == "cpu":
            print("bench: no accelerator initialised — JAX came up on the "
                  "CPU.  Device cells do not run there; set "
                  "JAX_PLATFORMS=cpu to ask for the host-codec run.",
                  file=sys.stderr)
            return 2

    extra: dict = {}
    baseline = None
    _try(extra, "baseline_avx2_refshape", _bench_baseline_refshape)
    baseline = extra.get("baseline_avx2_refshape")
    # pure-buffer AVX2 kernel speed: shows how much of the refshape baseline
    # is file IO (i.e. the baseline codec itself is not crippled).  On GFNI
    # hosts the production host codec dispatches to GF2P8AFFINEQB instead;
    # both are reported so the host-kernel headroom over the baseline is
    # itself a measured number.
    from seaweedfs_tpu import native as _native
    if _native.available():
        _try(extra, "baseline_avx2_kernel", _native_kernel_gbps, 10, 4,
             _native.GF_IMPL_AVX2)
        try:
            if _native.gf_impl() == _native.GF_IMPL_GFNI:
                _try(extra, "host_gfni_kernel", _native_kernel_gbps, 10, 4)
        except Exception:
            pass
        # host-path e2e (and its interleaved encode/null ceiling pairing).
        # On the host-codec run (JAX_PLATFORMS=cpu) no XLA client exists
        # yet: the CPU client's resident thread pool adds scheduling
        # jitter that skews the pair ratios on narrow hosts.  A device
        # run has initialised its backend above, to fail early.
        _bench_e2e_host(extra)

    # read-path engine benches (host-codec only, no device involvement):
    # batched degraded EC reads and pipelined filer streaming raced
    # against their serial baselines, and the tracing layer raced against
    # itself disabled — each with a regression gate
    for fn in (_bench_degraded_read, _bench_codec_family,
               _bench_filer_stream,
               _bench_trace_overhead, _bench_profile_overhead,
               _bench_heal_time, _bench_scrub_overhead,
               _bench_flow_canary_overhead, _bench_heat_overhead,
               _bench_history_overhead, _bench_perf_obs_overhead,
               _bench_interference_overhead, _bench_geo_replication,
               _bench_serving_knee, _bench_serving_plane,
               _bench_chaos, _bench_autopilot, _bench_fleetsim):
        try:
            fn(extra)
        except Exception as e:
            # these three carry regression GATES: a harness crash must
            # fail the run, or a broken gate ships as a green bench
            print(f"bench: {fn.__name__} failed: {e}", file=sys.stderr)
            extra.setdefault("gated_bench_failed", []).append(fn.__name__)

    if force_cpu:
        # best CPU story first: the native AVX2 codec needs no jax at all
        from seaweedfs_tpu import native
        if native.available():
            gbps = None
            try:
                gbps = _native_kernel_gbps(10, 4)
            except Exception as e:
                print(f"bench: native codec failed ({e})", file=sys.stderr)
            if gbps is not None:
                for k, m in RS_SWEEP:
                    _try(extra, f"ec_encode_rs{k}_{m}",
                         _native_kernel_gbps, k, m)
                _try(extra, "ec_rebuild_rs10_4_m1",
                     _native_rebuild_gbps, 10, 4, 1)
                _try(extra, "ec_rebuild_rs10_4_m4",
                     _native_rebuild_gbps, 10, 4, 4)
                try:
                    # fleet conversion on the host codec: the interleaved
                    # multi-volume pipeline is still the production CPU
                    # path (no jax import on this branch)
                    _bench_fleet_convert(extra, "cpp")
                except Exception as e:
                    print(f"bench: _bench_fleet_convert failed: {e}",
                          file=sys.stderr)
                    extra.setdefault("gated_bench_failed", []).append(
                        "_bench_fleet_convert")
                _emit(gbps, "cpu-native", baseline, extra)
                return _exit_code(extra)

    import jax
    on_tpu = jax.default_backend() == "tpu"
    backend = "tpu" if on_tpu else "cpu-xla"

    # Every kernel metric runs at the SAME per-iteration depth (~640 MiB of
    # volume data) — round 4 benched the sweep/rebuild configs at 1/4 the
    # primary's depth and the ~1 ms/iteration fixed cost made them look
    # 2-4x slower than the rs10_4 encode for no kernel reason.
    def _n_for(k: int) -> int:
        if not on_tpu:
            return 1024 * 1024
        total, tile = 640 * 1024 * 1024, 32768
        return max(tile, total // (k * tile) * tile)

    # re-tune the Pallas tile on this chip first: the winner is pinned
    # via WEEDTPU_EC_TILE, so the primary metric (and every codec built
    # after it — mesh, batch, fleet) runs the measured-best config
    try:
        _bench_tile_sweep(extra, _n_for(10), on_tpu)
    except Exception as e:
        print(f"bench: tile sweep failed: {e}", file=sys.stderr)

    gbps = _bench_encode_kernel(10, 4, _n_for(10), on_tpu, iters=60)

    for k, m in RS_SWEEP:
        _try(extra, f"ec_encode_rs{k}_{m}",
             _bench_encode_kernel, k, m, _n_for(k), on_tpu, 60)
    _try(extra, "ec_rebuild_rs10_4_m1",
         _bench_rebuild_kernel, 10, 4, 1, _n_for(10), on_tpu, 60)
    _try(extra, "ec_rebuild_rs10_4_m4",
         _bench_rebuild_kernel, 10, 4, 4, _n_for(10), on_tpu, 60)
    _try(extra, "ec_encode_rs10_4_mesh",
         _bench_encode_kernel, 10, 4, _n_for(10), on_tpu, 60,
         _mesh_codec_factory)
    _try(extra, "ec_encode_batch4_place",
         _bench_batch_place, 10, 4, 4, _n_for(10) // 4, on_tpu, 60)
    # batch placement runs the same bytes through the same kernel plus a
    # shard-spread all_to_all — it must never UNDERPERFORM the unsharded
    # call (such a regression once went through ungated)
    b4 = extra.get("ec_encode_batch4_place")
    if b4 is not None and gbps > 0:
        ratio = b4 / gbps
        extra["batch_place_ratio"] = round(ratio, 3)
        if ratio < BATCH_PLACE_TOL:
            extra["batch_place_regression"] = True
            print(f"bench: REGRESSION — ec_encode_batch4_place runs at "
                  f"{ratio:.2f}x the single-call kernel (must be >= "
                  f"{BATCH_PLACE_TOL}). Failing the bench run.",
                  file=sys.stderr)

    # fleet conversion e2e: device codec on this backend (single-chip
    # unit batches through the fused batch kernel; a >1-device attach
    # rides the unit-sharded mesh).  The TPU size is a leftover of the
    # old harness; the served-size cell is ROADMAP S1/R6.
    try:
        if on_tpu:
            _bench_fleet_convert(extra, None, vol_mb=2, n_vols=4, reps=1)
        else:
            _bench_fleet_convert(extra, None)
    except Exception as e:
        print(f"bench: _bench_fleet_convert failed: {e}", file=sys.stderr)
        extra.setdefault("gated_bench_failed", []).append(
            "_bench_fleet_convert")

    # xprof trace of one warm encode batch (WEEDTPU_JAX_PROFILE=dir):
    # proves the kernel timeline the way the reference's pprof profiles do
    trace_dir = os.environ.get("WEEDTPU_JAX_PROFILE")
    if trace_dir:
        try:
            import jax.numpy as jnp
            from seaweedfs_tpu.utils import grace as _grace
            codec = _device_codec(10, 4, on_tpu)
            data = jnp.asarray(np.random.default_rng(0).integers(
                0, 256, (10, 4 * 1024 * 1024), dtype=np.uint8))
            np.asarray(codec.encode_parity(data))  # warm/compile first
            with _grace.jax_profile(trace_dir):
                np.asarray(codec.encode_parity(data))
            extra["jax_profile_trace"] = trace_dir
        except Exception as e:
            print(f"bench: jax profile failed: {e}", file=sys.stderr)

    # e2e through write_ec_files on the device codec: a 20MiB probe (the
    # size is a leftover of the old harness; the served-size cell is
    # ROADMAP S1/R3); the host number above is the 1GiB one.
    if on_tpu:
        d: dict = {}
        _try(extra, "ec_encode_e2e_device", _bench_e2e,
             20 * 1024 * 1024, 2 * 1024 * 1024, "tpu", 2, d,
             profile_stacks=True)
        if d:
            extra["ec_encode_e2e_device_detail"] = d
    else:
        # the host e2e (measured pre-XLA above) stays the canonical
        # ec_encode_e2e; the XLA-codec probe is recorded under its own
        # key instead of being discarded
        key_e2e = ("ec_encode_e2e_xla" if "ec_encode_e2e" in extra
                   else "ec_encode_e2e")
        xd: dict = {}
        _try(extra, key_e2e, _bench_e2e,
             80 * 1024 * 1024, 8 * 1024 * 1024, None, 4, xd,
             profile_stacks=True)
        if xd:
            extra[key_e2e + "_detail"] = xd

    _emit(gbps, backend, baseline, extra)
    return _exit_code(extra)


def _exit_code(extra: dict) -> int:
    """Nonzero when a hard regression gate tripped — the JSON line still
    prints so the round records WHAT regressed, but the driver sees a
    failed bench instead of a silently slower one."""
    gates = ("ec_encode_e2e_pipeline_regression",
             "blob_read_degraded_regression",
             "filer_stream_pipeline_regression",
             "trace_overhead_regression",
             "profile_overhead_regression",
             "heal_time_regression",
             "scrub_overhead_regression",
             "flow_canary_overhead_regression",
             "heat_overhead_regression",
             "history_overhead_regression",
             "perf_obs_overhead_regression",
             "interference_overhead_regression",
             "geo_obs_overhead_regression",
             "repair_interference_regression",
             "repair_ratio_regression",
             "lrc_degraded_regression",
             "msr_repair_ratio_regression",
             "chaos_scenario_failed",
             "batch_place_regression",
             "fleet_convert_failed",
             "bench_regression",
             "gated_bench_failed")
    return 1 if any(extra.get(g) for g in gates) else 0


PIPELINE_REGRESSION_TOL = 0.90  # pipelined must stay within 10% of serial
READ_REGRESSION_TOL = 0.90  # batched degraded read vs per-interval serial
# the filer streaming effect size on a 2-core in-process harness is small
# (~1.05-1.1x) while host weather swings ±10%; the gate exists to catch a
# COLLAPSE (depth-4 cache thrash measured 0.68x), not weather
FILER_STREAM_REGRESSION_TOL = 0.80
# tracing at the default sample rate must cost <= 3% of blob read
# throughput vs WEEDTPU_TRACE_SAMPLE=0 (ISSUE 3 acceptance bar)
TRACE_OVERHEAD_TOL = 0.97
# automatic healing (planner-driven, concurrent) must not exceed the
# serial shell-rebuild baseline; the slack covers detection latency
# (heartbeat + ledger) and host weather on single-shot measurements
HEAL_REGRESSION_TOL = 1.25
# foreground blob reads must keep >= 0.95x throughput with the scrubber
# running at its rate limit (ISSUE 4 acceptance bar)
SCRUB_OVERHEAD_TOL = 0.95
# byte-flow accounting + a fast-cycling canary prober together must keep
# >= 0.97x foreground blob-read throughput (ISSUE 6 acceptance bar)
FLOW_CANARY_OVERHEAD_TOL = 0.97
# blob reads with the HZ=97 sampling profiler walking every thread must
# keep >= 0.95x the unprofiled rate (ISSUE 5 acceptance bar)
PROFILE_OVERHEAD_TOL = 0.95
# blob reads with the workload heat sketches updating per request must
# keep >= 0.97x the untracked rate (ISSUE 8 acceptance bar)
HEAT_OVERHEAD_TOL = 0.97
# blob reads while the master's aggregator records every scrape into the
# history store + evaluates alerts + re-forecasts capacity must keep
# >= 0.97x the recording-off rate (ISSUE 10 acceptance bar)
HISTORY_OVERHEAD_TOL = 0.97
# encodes with the performance observatory (pipeline stage accounting +
# roofline export) on must keep >= 0.97x the observatory-off rate
# (ISSUE 13 acceptance bar)
PERF_OBS_OVERHEAD_TOL = 0.97
# blob reads with the interference observatory measuring every scrape
# tick AND the governor retuning the background buckets must keep
# >= 0.97x the plane-off rate (ISSUE 14 acceptance bar)
INTERFERENCE_OVERHEAD_TOL = 0.97
# replicated writes with the geo observatory on (lag/backlog gauges,
# per-event sampled trace roots, WAN double-booking) must keep >= 0.97x
# the obs-off replication rate (ISSUE 20 acceptance bar)
GEO_OBS_OVERHEAD_TOL = 0.97
# bench trajectory: a gated headline metric dropping more than 10% below
# the best prior recorded round (same backend) fails the run
TRAJECTORY_TOL = 0.90
# mesh + fleet joined the gate in round 12: a measured mesh regression
# had shipped because nothing failed on it
# autopilot_p99_gate joined in round 15: shifting-Zipf foreground read
# p99 autopilot-OFF over autopilot-ON, SATURATED at 1.1 before gating —
# on an idle host the promote pays ~1.2-1.3x but concurrent host load
# compresses both arms toward parity, so the raw ratio (recorded
# ungated as autopilot_p99_ratio) would flap the gate; the clamp turns
# it into "the autopilot must never make foreground p99 WORSE" (a
# round where ON loses to OFF reads < 1 and fails against the 1.1 bar)
TRAJECTORY_GATED = ("ec_encode_rs10_4", "ec_rebuild_rs10_4_m1",
                    "ec_encode_rs10_4_mesh", "fleet_convert_gbps",
                    "autopilot_p99_gate", "serving_knee_rps",
                    "fleet_sim_pool_gate", "fleet_sim_actions_gate",
                    "geo_catchup_mbps")
# batch placement must stay within this fraction of the unsharded
# single-call kernel at equal bytes (satellite gate, ISSUE 12)
BATCH_PLACE_TOL = 0.90
# lower-is-better trajectory gates: the metric failing when it RISES
# more than 10% above the best (minimum) prior recorded round
TRAJECTORY_GATED_MIN = ("repair_network_ratio", "fleet_sim_tick_gate",
                        "repair_network_ratio_msr_9_16",
                        "geo_replication_lag_s")
# metric prefixes whose numbers are bound by the host I/O engine: these
# additionally require the prior round's config.aio to match (see
# _record_trajectory.metric_comparable)
AIO_SCOPED_METRICS = ("ec_encode_e2e", "fleet_convert", "ec_rebuild_e2e")
# repair-traffic metrics are shaped by the erasure code the volumes ran
# under: compare only like-codec rounds (None-tolerant — rounds
# predating the codec stamp were all RS)
CODEC_SCOPED_METRICS = ("repair_network_ratio", "heal_")
# serving-plane metrics compare ONLY against rounds measured under an
# IDENTICAL config.serving stamp (strict equality, not None-tolerant:
# rounds predating the stamp were measured before the location-cache /
# hot-tier serving stack existed and must not set — or be judged by —
# its bar; the first stamped round establishes it)
SERVING_SCOPED_METRICS = ("serving_knee_rps",)
# ...comparing against the best of only the last N recorded same-backend
# rounds, so one cache-hot outlier round ages out of the bar instead of
# ratcheting it forever
TRAJECTORY_LOOKBACK = 5
# reduced-read recovery (ISSUE 11 acceptance bar): the planner-driven
# heal must move <= 0.6x the repair bytes of the naive shell-rebuild
# walk over the same loss pattern
REPAIR_RATIO_TOL = 0.6
# PM-MSR regenerating repair (ISSUE 19 acceptance bar): remote repair
# traffic for one lost shard must stay under 1/3 of the naive k-shard
# copy (the (9,16) code's cut-set floor is d/(k*alpha) = 0.222)
MSR_REPAIR_RATIO_TOL = 0.334
# foreground read p99 while the repair planner rebuilds lost shards must
# stay within 1.5x the idle p99 (ISSUE 9 acceptance bar; the 1709.05365
# measurement: online repair/encode interference with foreground traffic)
REPAIR_INTERFERENCE_TOL = 1.5


def _bench_e2e_host(extra: dict) -> None:
    """The pipeline-machinery metrics comparable to the reference's e2e
    encode path — primary size 1 GiB (>= 1 GB), plus the
    two legacy probe sizes, per-stage attribution, the cold-inode first-rep
    number, and the measured I/O ceiling of this host (the same shard-file
    writes with the codec deleted).  `ec_encode_e2e_ceiling_frac` is the
    fraction of that ceiling the real encode achieves: when it approaches
    1.0 the e2e number is the host's disk bandwidth, not the codec.

    The host codec is also raced through the PIPELINED machinery
    (`ec_encode_e2e_pipeline_ratio`, pipelined vs WEEDTPU_EC_PIPELINE=
    serial, median of interleaved pairs): the
    pipelined strategy is what every device codec rides, so if it ever
    falls behind host-serial by more than PIPELINE_REGRESSION_TOL the run
    FAILS (ec_encode_e2e_pipeline_regression + nonzero exit) — serial
    parity writes burying the pipeline can't recur silently.
    `ec_encode_e2e_overlap_frac` is
    the achieved stage overlap of the primary e2e run (0 == fully serial;
    see ec_files.overlap_fraction)."""
    for key, size in (("ec_encode_e2e_host_1g", 1024 * 1024 * 1024),
                      ("ec_encode_e2e_host", 320 * 1024 * 1024),
                      ("ec_encode_e2e_host_40m", 40 * 1024 * 1024)):
        detail: dict = {}
        _try(extra, key, _bench_e2e, size, 8 * 1024 * 1024, "cpp", 4,
             detail)
        if detail:
            extra[key + "_detail"] = detail
    pdetail: dict = {}
    _try(extra, "ec_encode_e2e_serial_1g", _bench_e2e,
         1024 * 1024 * 1024, 8 * 1024 * 1024, "cpp", 4, pdetail,
         "serial")
    if pdetail:
        extra["ec_encode_e2e_serial_1g_detail"] = pdetail
    try:
        ceil = _bench_e2e_ceiling(1024 * 1024 * 1024, 8 * 1024 * 1024)
        extra["ec_encode_e2e_ceiling_1g"] = round(ceil["ceiling_gbps"], 3)
        # frac from INTERLEAVED encode/null pairs (median ratio), not
        # from dividing two best-ofs measured minutes apart — see
        # _bench_e2e_ceiling
        extra["ec_encode_e2e_ceiling_frac"] = round(ceil["frac"], 3)
        extra["ec_encode_e2e_paired_1g"] = round(ceil["encode_gbps"], 3)
        # the measured host I/O ceiling feeds the disk roofline rows
        # (stats/profile.py): shard_write fractions become queryable
        from seaweedfs_tpu.stats import profile as _profile
        _profile.set_ceiling("disk", ceil["ceiling_gbps"])
        from seaweedfs_tpu.storage import aio as _aio
        _PROBED_DISK_CEILING.update(gbps=round(ceil["ceiling_gbps"], 3),
                                    aio=_aio.engine_label())
    except Exception as e:
        print(f"bench: ec_encode_e2e_ceiling_1g failed: {e}",
              file=sys.stderr)
    for key in ("ec_encode_e2e_host_1g", "ec_encode_e2e_host",
                "ec_encode_e2e_host_40m"):  # largest size that measured
        if key in extra:
            extra["ec_encode_e2e"] = extra[key]
            break
    for key in ("ec_encode_e2e_host_1g", "ec_encode_e2e_host",
                "ec_encode_e2e_host_40m"):
        frac = extra.get(key + "_detail", {}).get("overlap_frac")
        if frac is not None:
            extra["ec_encode_e2e_overlap_frac"] = frac
            break
    try:
        ratio = _bench_pipeline_ratio(1024 * 1024 * 1024, 8 * 1024 * 1024)
        extra["ec_encode_e2e_pipeline_ratio"] = round(ratio, 3)
        if ratio < PIPELINE_REGRESSION_TOL:
            extra["ec_encode_e2e_pipeline_regression"] = True
            print(f"bench: REGRESSION — pipelined e2e encode runs at "
                  f"{ratio:.2f}x host-serial (median of interleaved "
                  f"pairs); the overlapped shard-I/O pipeline has "
                  f"stopped overlapping. Failing the bench run.",
                  file=sys.stderr)
    except Exception as e:
        print(f"bench: pipeline ratio failed: {e}", file=sys.stderr)
    detail = {}
    _try(extra, "ec_rebuild_e2e_host", _bench_rebuild_e2e,
         320 * 1024 * 1024, detail)
    if detail:
        extra["ec_rebuild_e2e_host_detail"] = detail
    try:
        _bench_blob_rps(extra)
    except Exception as e:  # cluster spin-up is best-effort in a bench
        print(f"bench: blob rps failed: {e}", file=sys.stderr)


def _bench_blob_rps(extra: dict, n: int = 2000, size: int = 1024,
                    concurrency: int = 16) -> None:
    """The reference's own headline benchmark shape (weed benchmark /
    README.md:539-583: concurrent 1KB writes then random reads) against an
    in-process master+volume cluster — blob_write_rps / blob_read_rps land
    in `extra` for comparison with BASELINE.md's published req/s."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer


    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    with tempfile.TemporaryDirectory(prefix="weedtpu-rps-") as d:
        master = MasterServer("127.0.0.1", free_port())
        vs = VolumeServer([d], master.url, port=free_port(),
                          heartbeat_interval=0.2)
        started = []
        try:
            run(master.start())
            started.append(master)
            run(vs.start())  # sends its first heartbeat synchronously
            started.append(vs)
            deadline = time.time() + 10
            while time.time() < deadline and not master.topo.nodes:
                time.sleep(0.05)
            client = WeedClient(master.url)
            payload = bytes(range(256)) * (size // 256 + 1)
            payload = payload[:size]
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(concurrency) as ex:
                fids = list(ex.map(
                    lambda i: client.upload(payload, name=f"b{i}"),
                    range(n)))
            extra["blob_write_rps"] = round(
                n / (time.perf_counter() - t0), 1)
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(concurrency) as ex:
                for data in ex.map(client.download, fids):
                    assert len(data) == size
            extra["blob_read_rps"] = round(
                n / (time.perf_counter() - t0), 1)
            client.close()
        finally:
            # each cleanup step isolated: a stop failure must not leak
            # the other server or the loop thread
            if vs in started:
                run_quiet(vs.stop())
            if master in started:
                run_quiet(master.stop())
            loop.call_soon_threadsafe(loop.stop)


def _bench_codec_family(extra: dict, n_needles: int = 24,
                        nsize: int = 64 * 1024, reads: int = 120) -> None:
    """Codec-family benches (ISSUE 19), all on the host codec:

    (a) codec-labeled encode throughput — ``ec_encode_lrc_10_2_2`` /
        ``ec_encode_msr_9_16`` GB/s next to the RS rows;
    (b) LRC vs RS(10,4) single-loss degraded-read p99: the LRC decode
        touches ONE local parity group (r+1 surviving shards) where RS
        gathers all k, so its tail must come in below RS — a round
        where it does not fails the run;
    (c) PM-MSR reduced-repair network ratio: every survivor served
        remotely, measured helper bytes over the naive k-shard copy,
        gated at MSR_REPAIR_RATIO_TOL (the (9,16) cut-set floor is
        d/(k*alpha) = 0.222) and recorded codec-labeled for the
        lower-is-better trajectory gate."""
    from seaweedfs_tpu import native
    from seaweedfs_tpu.ops import codecs as _codecs
    from seaweedfs_tpu.ops import gf
    from seaweedfs_tpu.storage import needle as ndl
    from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
    from seaweedfs_tpu.storage.volume import Volume

    kind = "cpp" if native.available() else "numpy"
    old = os.environ.get("WEEDTPU_EC_CODEC")
    os.environ["WEEDTPU_EC_CODEC"] = kind
    try:
        # (a) encode throughput per family, one device-free dispatch shape
        n_bytes = 4 * 1024 * 1024
        rng = np.random.default_rng(19)
        for tag in ("lrc_10_2_2", "msr_9_16"):
            spec = _codecs.parse_tag(tag)
            codec = _codecs.make_codec(tag, kind)
            data = rng.integers(0, 256, (spec.k, n_bytes), dtype=np.uint8)
            codec.encode_parity(data)  # warm
            iters = 8
            t0 = time.perf_counter()
            for _ in range(iters):
                codec.encode_parity(data)
            el = time.perf_counter() - t0
            extra[f"ec_encode_{tag}"] = round(
                spec.k * n_bytes * iters / el / 1e9, 3)

        small = 4096
        with tempfile.TemporaryDirectory(prefix="weedtpu-codec-") as d:
            vol = Volume(d, "", 19)
            ids = []
            for i in range(1, n_needles + 1):
                data = rng.integers(0, 256, nsize, dtype=np.uint8).tobytes()
                vol.append_needle(ndl.Needle(cookie=0x77, id=i, data=data))
                ids.append(i)
            vol.close()
            src_base = os.path.join(d, "19")

            def make(tag: str, lose: tuple) -> str:
                bdir = os.path.join(d, tag)
                os.makedirs(bdir)
                base = os.path.join(bdir, "19")
                for ext in (".dat", ".idx"):
                    os.link(src_base + ext, base + ext)
                ec_files.write_ec_files(base, large_block=1 << 40,
                                        small_block=small,
                                        batch_size=small * 40,
                                        codec_tag=tag)
                ec_files.write_sorted_ecx(base + ".idx")
                for sid in lose:
                    os.remove(base + layout.to_ext(sid))
                return base

            # (b) single-loss degraded p99, LRC vs RS — per-read
            # latencies on one thread, interleaved arms, shard 1 lost
            bases = {tag: make(tag, (1,))
                     for tag in ("rs_10_4", "lrc_10_2_2")}
            lats: dict[str, list] = {t: [] for t in bases}
            evs = {t: ec_volume.EcVolume(b, 1 << 40, small)
                   for t, b in bases.items()}
            try:
                for t, ev in evs.items():  # warm both arms
                    ev.read_needle(ids[0])
                for i in range(reads):
                    for t, ev in evs.items():
                        nid = ids[i % len(ids)]
                        t0 = time.perf_counter()
                        n = ev.read_needle(nid)
                        lats[t].append(time.perf_counter() - t0)
                        assert len(n.data) == nsize
            finally:
                for ev in evs.values():
                    ev.close()
            p99 = {t: sorted(v)[int(0.99 * (len(v) - 1))] * 1e3
                   for t, v in lats.items()}
            extra["rs_degraded_p99_ms"] = round(p99["rs_10_4"], 3)
            extra["lrc_degraded_p99_ms"] = round(p99["lrc_10_2_2"], 3)
            if p99["lrc_10_2_2"] >= p99["rs_10_4"]:
                extra["lrc_degraded_regression"] = True
                print(f"bench: REGRESSION — LRC single-loss degraded "
                      f"p99 {p99['lrc_10_2_2']:.2f}ms is not below "
                      f"RS(10,4)'s {p99['rs_10_4']:.2f}ms; the local-"
                      f"group decode has stopped paying off. Failing "
                      f"the bench run.", file=sys.stderr)

            # (c) MSR repair network ratio: one shard lost, EVERY
            # survivor remote — measured helper payloads / naive copy
            mbase = make("msr_9_16", ())
            spec = _codecs.parse_tag("msr_9_16")
            shard_size = os.path.getsize(mbase + layout.to_ext(0))
            shards = {i: np.fromfile(mbase + layout.to_ext(i),
                                     dtype=np.uint8)
                      for i in range(spec.n)}
            lost = 2
            for i in range(spec.n):  # nothing local: all repair is net
                os.remove(mbase + layout.to_ext(i))
            a = spec.alpha
            fetched = {"bytes": 0}

            def fetch(group, sids, coeff, off, size):
                blocks: dict[int, np.ndarray] = {}
                rows = []
                for s in sids:
                    f = s // a
                    if f not in blocks:
                        blocks[f] = shards[f][off * a:(off + size) * a
                                              ].reshape(size, a)
                    rows.append(np.ascontiguousarray(blocks[f][:, s % a]))
                out = gf.gf_matmul(np.asarray(coeff, np.uint8),
                                   np.stack(rows))
                fetched["bytes"] += out.nbytes
                return out.tobytes()

            groups = [{"node": f"h{i}:1", "shards": [i], "locality": 3,
                       "shard_size": shard_size}
                      for i in range(spec.n) if i != lost]
            res = ec_files.rebuild_ec_reduced(mbase, [lost], groups,
                                              fetch, codec_tag="msr_9_16")
            rebuilt = np.fromfile(mbase + layout.to_ext(lost),
                                  dtype=np.uint8)
            assert np.array_equal(rebuilt, shards[lost]), \
                "msr repair output differs"
            ratio = fetched["bytes"] / (spec.k * shard_size)
            extra["repair_network_ratio_msr_9_16"] = round(ratio, 3)
            extra["msr_repair_bytes"] = int(fetched["bytes"])
            if ratio > MSR_REPAIR_RATIO_TOL:
                extra["msr_repair_ratio_regression"] = True
                print(f"bench: REGRESSION — MSR repair moved "
                      f"{ratio:.3f}x of the naive copy bytes (bar: "
                      f"<= {MSR_REPAIR_RATIO_TOL}; cut-set floor "
                      f"{spec.params[1] / (spec.k * a):.3f}). Failing "
                      f"the bench run.", file=sys.stderr)
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_EC_CODEC", None)
        else:
            os.environ["WEEDTPU_EC_CODEC"] = old


def _bench_degraded_read(extra: dict, n_needles: int = 40,
                         nsize: int = 64 * 1024, concurrency: int = 8,
                         pairs: int = 4) -> None:
    """Degraded EC needle reads/s: the batched read engine (all intervals
    planned up front, adjacent per-shard ranges coalesced, survivor reads
    fanned out on the shared pool, ONE reconstruction dispatch per needle)
    vs the per-interval serial baseline (WEEDTPU_EC_READ=serial) on the
    same shard files with two data shards deleted.  64KB needles over 4KB
    blocks give ~17 intervals per needle — the shape where the
    per-interval matmul tax shows.  A fresh EcVolume per rep keeps the
    reconstruction LRU cold so the number measures the engine, not the
    cache; interleaved pairs + median ratio cancel machine weather (same
    rationale as _bench_e2e_ceiling).  Below READ_REGRESSION_TOL the run
    FAILS (blob_read_degraded_regression + nonzero exit)."""
    import concurrent.futures

    from seaweedfs_tpu import native
    from seaweedfs_tpu.storage import needle as ndl
    from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
    from seaweedfs_tpu.storage.volume import Volume

    small = 4096
    old = os.environ.get("WEEDTPU_EC_CODEC")
    # host codec: this metric times read-path orchestration, not a device
    # kernel
    os.environ["WEEDTPU_EC_CODEC"] = "cpp" if native.available() else "numpy"
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-deg-") as d:
            vol = Volume(d, "", 9)
            rng = np.random.default_rng(4)
            ids = []
            for i in range(1, n_needles + 1):
                data = rng.integers(0, 256, nsize, dtype=np.uint8).tobytes()
                vol.append_needle(ndl.Needle(cookie=0x77, id=i, data=data))
                ids.append(i)
            vol.close()
            base = os.path.join(d, "9")
            ec_files.write_ec_files(base, large_block=1 << 40,
                                    small_block=small,
                                    batch_size=small * 10)
            ec_files.write_sorted_ecx(base + ".idx")
            for sid in (1, 4):  # two data shards lost
                os.remove(base + layout.to_ext(sid))

            def rep(mode: str) -> float:
                ev = ec_volume.EcVolume(base, 1 << 40, small)
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(concurrency) as ex:
                    for n in ex.map(
                            lambda nid: ev.read_needle(nid, mode=mode), ids):
                        assert len(n.data) == nsize
                el = time.perf_counter() - t0
                ev.close()
                return el

            best_b = best_s = float("inf")
            ratios = []
            for i in range(pairs):
                if i % 2 == 0:
                    t_s = rep("serial")
                    t_b = rep("batched")
                else:
                    t_b = rep("batched")
                    t_s = rep("serial")
                if i == 0:
                    continue  # cold page cache / codec warmup on both sides
                best_b = min(best_b, t_b)
                best_s = min(best_s, t_s)
                ratios.append(t_s / t_b)
            # per-stage engine counters from one fresh batched pass
            ev = ec_volume.EcVolume(base, 1 << 40, small)
            for nid in ids[:8]:
                ev.read_needle(nid)
            extra["blob_read_degraded_detail"] = ev.read_stats_snapshot()
            ev.close()
        ratios.sort()
        ratio = ratios[len(ratios) // 2]
        extra["blob_read_rps_degraded"] = round(n_needles / best_b, 1)
        extra["blob_read_rps_degraded_serial"] = round(n_needles / best_s, 1)
        extra["blob_read_degraded_ratio"] = round(ratio, 3)
        if ratio < READ_REGRESSION_TOL:
            extra["blob_read_degraded_regression"] = True
            print(f"bench: REGRESSION — batched degraded reads run at "
                  f"{ratio:.2f}x the per-interval serial baseline (median "
                  f"of interleaved pairs); the one-shot reconstruction "
                  f"engine has stopped paying off. Failing the bench run.",
                  file=sys.stderr)
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_EC_CODEC", None)
        else:
            os.environ["WEEDTPU_EC_CODEC"] = old


def _bench_filer_stream(extra: dict, size: int = 24 * 1024 * 1024,
                        pairs: int = 6) -> None:
    """Whole-file filer streaming MB/s: the bounded readahead pipeline
    (WEEDTPU_READAHEAD=2, fetch+decode of chunk N+1.. overlapping the
    client write of N) vs the serial fetch->write loop (=0), interleaved
    pairs over the same entry on an in-process master+volume+filer
    cluster.  The filer's chunk cache is DISABLED so every GET pays real
    volume-server fetches — the latency the pipeline exists to hide.
    Below FILER_STREAM_REGRESSION_TOL the run FAILS
    (filer_stream_pipeline_regression + nonzero exit)."""
    import asyncio
    import threading
    import urllib.request

    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    old = os.environ.get("WEEDTPU_READAHEAD")
    best_p = best_s = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-fstream-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            filer = FilerServer(master.url, port=free_port(),
                                chunk_cache_mem=0)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                run(filer.start())
                started.append(filer)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                payload = np.random.default_rng(5).integers(
                    0, 256, size, dtype=np.uint8).tobytes()
                url = f"http://127.0.0.1:{filer.port}/bench/stream.bin"
                req = urllib.request.Request(url, data=payload,
                                             method="PUT")
                with urllib.request.urlopen(req, timeout=120) as r:
                    r.read()

                def rep(depth: str) -> float:
                    os.environ["WEEDTPU_READAHEAD"] = depth
                    t0 = time.perf_counter()
                    got = 0
                    with urllib.request.urlopen(url, timeout=120) as r:
                        while True:
                            b = r.read(1 << 20)
                            if not b:
                                break
                            got += len(b)
                    assert got == size, got
                    return time.perf_counter() - t0

                for i in range(pairs):
                    if i % 2 == 0:
                        t_s = rep("0")
                        t_p = rep("2")
                    else:
                        t_p = rep("2")
                        t_s = rep("0")
                    if i == 0:
                        continue  # warm connections / page cache
                    best_p = min(best_p, t_p)
                    best_s = min(best_s, t_s)
                    ratios.append(t_s / t_p)
            finally:
                if filer in started:
                    run_quiet(filer.stop())
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_READAHEAD", None)
        else:
            os.environ["WEEDTPU_READAHEAD"] = old
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["filer_stream_mbps"] = round(size / 1e6 / best_p, 1)
    extra["filer_stream_mbps_serial"] = round(size / 1e6 / best_s, 1)
    extra["filer_stream_pipeline_ratio"] = round(ratio, 3)
    if ratio < FILER_STREAM_REGRESSION_TOL:
        extra["filer_stream_pipeline_regression"] = True
        print(f"bench: REGRESSION — readahead filer streaming runs at "
              f"{ratio:.2f}x the serial loop (median of interleaved "
              f"pairs); the chunk prefetch pipeline has stopped "
              f"overlapping. Failing the bench run.", file=sys.stderr)


def _bench_trace_overhead(extra: dict, n: int = 1200, size: int = 1024,
                          concurrency: int = 16, pairs: int = 9) -> None:
    """Tracing tax on the hottest path: blob reads against an in-process
    master+volume cluster with tracing at its DEFAULT sample rate vs
    fully off (WEEDTPU_TRACE_SAMPLE=0), interleaved pairs over the same
    blobs.  The middleware reads the env per request, so flipping it
    between reps retargets live servers.  Below TRACE_OVERHEAD_TOL
    (<= 3% regression allowed) the run FAILS (trace_overhead_regression
    + nonzero exit).  The true per-request tax is ~1µs against a ~300µs
    request, so the signal is far below host weather on a narrow box —
    hence MORE pairs than the other gates (median of 8 ratios), or the
    3%-tight gate flaps on scheduler noise alone."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    old = os.environ.get("WEEDTPU_TRACE_SAMPLE")
    best_on = best_off = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-trov-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"t{i}"),
                        range(n)))

                def rep(sample: str) -> float:
                    os.environ["WEEDTPU_TRACE_SAMPLE"] = sample
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    return time.perf_counter() - t0

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = rep("0")
                        t_on = rep("16")  # the default rate, explicit
                    else:
                        t_on = rep("16")
                        t_off = rep("0")
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_TRACE_SAMPLE", None)
        else:
            os.environ["WEEDTPU_TRACE_SAMPLE"] = old
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_traced"] = round(n / best_on, 1)
    extra["blob_read_rps_untraced"] = round(n / best_off, 1)
    extra["trace_overhead_ratio"] = round(ratio, 3)
    if ratio < TRACE_OVERHEAD_TOL:
        extra["trace_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with tracing at the "
              f"default sample rate run at {ratio:.3f}x the untraced "
              f"rate (median of interleaved pairs); tracing exceeds its "
              f"3% budget. Failing the bench run.", file=sys.stderr)


def _bench_profile_overhead(extra: dict, n: int = 1200, size: int = 1024,
                            concurrency: int = 16, pairs: int = 7) -> None:
    """Sampling-profiler tax on the hottest path: blob reads against an
    in-process master+volume cluster with the continuous profiler walking
    every thread at HZ=97 vs no profiler at all, interleaved pairs over
    the same blobs.  The sampler holds the GIL for one frame walk per
    tick; below PROFILE_OVERHEAD_TOL (>= 5% regression) the run FAILS
    (profile_overhead_regression + nonzero exit).  The winning top
    collapsed stack is recorded so the JSON shows WHAT the profiler saw
    while it was being measured."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.stats import profile as _profile

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    # an inherited WEEDTPU_PROFILE_HZ would start the CONTINUOUS profiler
    # inside the servers below, taxing both arms equally and pinning the
    # ratio at ~1.0 — the gate could then never fire
    old_hz = os.environ.pop("WEEDTPU_PROFILE_HZ", None)
    _profile.shutdown()

    best_on = best_off = float("inf")
    ratios: list[float] = []
    top_stack = ""
    with tempfile.TemporaryDirectory(prefix="weedtpu-prov-") as d:
        master = MasterServer("127.0.0.1", free_port())
        vs = VolumeServer([d], master.url, port=free_port(),
                          heartbeat_interval=0.2)
        started = []
        try:
            run(master.start())
            started.append(master)
            run(vs.start())
            started.append(vs)
            deadline = time.time() + 10
            while time.time() < deadline and not master.topo.nodes:
                time.sleep(0.05)
            client = WeedClient(master.url)
            payload = (bytes(range(256)) * (size // 256 + 1))[:size]
            with concurrent.futures.ThreadPoolExecutor(concurrency) as ex:
                fids = list(ex.map(
                    lambda i: client.upload(payload, name=f"p{i}"),
                    range(n)))

            def rep(profiled: bool) -> float:
                prof = _profile.SamplingProfiler(97).start() \
                    if profiled else None
                try:
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    return time.perf_counter() - t0
                finally:
                    if prof is not None:
                        prof.stop()
                        nonlocal top_stack
                        top_stack = prof.collapsed(limit=1) or top_stack

            for i in range(pairs):
                if i % 2 == 0:
                    t_off = rep(False)
                    t_on = rep(True)
                else:
                    t_on = rep(True)
                    t_off = rep(False)
                if i == 0:
                    continue  # warm connections / page cache
                best_on = min(best_on, t_on)
                best_off = min(best_off, t_off)
                ratios.append(t_off / t_on)
            client.close()
        finally:
            if vs in started:
                run_quiet(vs.stop())
            if master in started:
                run_quiet(master.stop())
            loop.call_soon_threadsafe(loop.stop)
            if old_hz is not None:
                os.environ["WEEDTPU_PROFILE_HZ"] = old_hz
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_profiled"] = round(n / best_on, 1)
    extra["blob_read_rps_unprofiled"] = round(n / best_off, 1)
    extra["profile_overhead_ratio"] = round(ratio, 3)
    if top_stack:
        extra["profile_top_stack"] = top_stack
    if ratio < PROFILE_OVERHEAD_TOL:
        extra["profile_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with the sampling "
              f"profiler at HZ=97 run at {ratio:.3f}x the unprofiled "
              f"rate (median of interleaved pairs); profiling exceeds "
              f"its 5% budget. Failing the bench run.", file=sys.stderr)


def _bench_heal_time(extra: dict, n_volumes: int = 4,
                     blobs_per_vol: int = 24, size: int = 48 * 1024) -> None:
    """seconds-to-reprotected: inject loss of 2 shards in each of
    `n_volumes` EC volumes on a 2-node cluster and measure how long the
    automatic repair planner takes to return every volume to 14/14 —
    against the serial shell-rebuild baseline (ec.rebuild walks volumes
    one by one) over the same loss pattern.  The planner runs repairs
    concurrently under its token bucket, so healing slower than the
    serial loop (beyond HEAL_REGRESSION_TOL slack for detection latency)
    means the executor stopped overlapping: heal_time_regression +
    nonzero exit."""
    import asyncio
    import io
    import threading
    import urllib.request

    from seaweedfs_tpu import native
    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(180)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    def post(url, path, body):
        req = urllib.request.Request(
            f"http://{url}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=180) as r:
            return json.loads(r.read())

    def get(url, path):
        with urllib.request.urlopen(f"http://{url}{path}",
                                    timeout=30) as r:
            return json.loads(r.read())

    overrides = {
        # host codec, parked background loops (the bench drives ticks
        # explicitly), wide repair concurrency
        "WEEDTPU_EC_CODEC": "cpp" if native.available() else "numpy",
        "WEEDTPU_SCRUB_INTERVAL": "3600",
        "WEEDTPU_REPAIR_INTERVAL": "3600",
        "WEEDTPU_REPAIR_CONCURRENCY": "8",
        "WEEDTPU_REPAIR_BURST": "8",
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-heal-") as d:
            master = MasterServer("127.0.0.1", free_port())
            servers = []
            started = []
            try:
                run(master.start())
                started.append(master)
                for i in range(2):
                    vd = os.path.join(d, f"vs{i}")
                    os.makedirs(vd, exist_ok=True)
                    vs = VolumeServer([vd], master.url, port=free_port(),
                                      max_volumes=20,
                                      heartbeat_interval=0.2)
                    run(vs.start())
                    servers.append(vs)
                    started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and \
                        len(master.topo.nodes) < 2:
                    time.sleep(0.05)
                env = CommandEnv(master.url)
                out = io.StringIO()
                run_command(env, "lock", out)
                run_command(env, f"volume.grow -count {n_volumes}", out)
                time.sleep(0.5)
                client = WeedClient(master.url)
                rng = np.random.default_rng(11)
                vids: set[int] = set()
                for i in range(n_volumes * blobs_per_vol):
                    data = rng.integers(0, 256, size,
                                        dtype=np.uint8).tobytes()
                    fid = client.upload(data, name=f"h{i}.bin")
                    vids.add(int(fid.split(",")[0]))
                time.sleep(0.5)
                vids = sorted(vids)
                for vid in vids:
                    run_command(env, f"ec.encode -volumeId {vid}", out)
                time.sleep(0.7)

                def kill_two(vid: int) -> None:
                    locs = env.ec_shard_locations(vid)
                    killed = 0
                    for sid in sorted(locs):
                        post(locs[sid][0], "/admin/ec/delete_shards",
                             {"volume": vid, "shards": [sid]})
                        killed += 1
                        if killed == 2:
                            return

                def wait_missing() -> None:
                    deadline = time.time() + 15
                    while time.time() < deadline:
                        if all(len(env.ec_shard_locations(v)) <= 12
                               for v in vids):
                            return
                        time.sleep(0.1)

                def wait_protected(timeout: float = 120) -> bool:
                    deadline = time.time() + timeout
                    while time.time() < deadline:
                        if all(len(env.ec_shard_locations(v)) == 14
                               for v in vids):
                            return True
                        time.sleep(0.1)
                    return False

                from seaweedfs_tpu.stats import netflow as _nf

                repair_bytes = {"heal": 0.0, "naive": 0.0}

                def serial_rep() -> float:
                    """Serial baseline: the shell's one-by-one rebuild
                    walk (holds the admin lock, so the planner yields).
                    Its class=repair byte delta IS the naive
                    10-survivor-read cost ROADMAP item 1 must beat."""
                    for vid in vids:
                        kill_two(vid)
                    wait_missing()
                    run_command(env, "lock", out)
                    b0 = _nf.class_total("recv", "repair")
                    t0 = time.perf_counter()
                    run_command(env, "ec.rebuild", out)
                    el = time.perf_counter() - t0
                    repair_bytes["naive"] = \
                        _nf.class_total("recv", "repair") - b0
                    run_command(env, "unlock", out)
                    assert wait_protected(), "serial rebuild stuck"
                    return el

                def heal_rep() -> tuple[float, bool]:
                    for vid in vids:
                        kill_two(vid)
                    wait_missing()
                    b0 = _nf.class_total("recv", "repair")
                    t0 = time.perf_counter()
                    deadline = time.time() + 120
                    while time.time() < deadline:
                        post(master.url, "/maintenance/tick",
                             {"wait": True})
                        st = get(master.url, "/maintenance/status")
                        if all(st["volumes"].get(str(v), {}).get("state")
                               == "healthy" for v in vids):
                            repair_bytes["heal"] = _nf.class_total(
                                "recv", "repair") - b0
                            return time.perf_counter() - t0, True
                        time.sleep(0.1)
                    return time.perf_counter() - t0, False

                run_command(env, "unlock", out)
                # interleaved pairs + best-of per side: single-shot
                # sub-second measurements on a shared host compare
                # weather, not strategies (same rationale as
                # _bench_e2e_ceiling)
                serial_s = heal_s = float("inf")
                healed = True
                for _ in range(2):
                    serial_s = min(serial_s, serial_rep())
                    h, ok = heal_rep()
                    healed = healed and ok
                    heal_s = min(heal_s, h)
                client.close()
            finally:
                for vs in reversed([s for s in started
                                    if s is not master]):
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
        extra["heal_time_s"] = round(heal_s, 3)
        extra["heal_serial_s"] = round(serial_s, 3)
        extra["heal_volumes"] = n_volumes
        # fleet-scale repair traffic (arXiv:1309.0186): bytes the heal
        # moved under class=repair, and the shell walk's naive cost —
        # the baseline ROADMAP item 1's reduced-read decode must beat
        extra["repair_network_bytes"] = int(repair_bytes["heal"])
        extra["repair_network_bytes_naive"] = int(repair_bytes["naive"])
        if repair_bytes["naive"] > 0:
            net_ratio = repair_bytes["heal"] / repair_bytes["naive"]
            extra["repair_network_ratio"] = round(net_ratio, 3)
            if net_ratio > REPAIR_RATIO_TOL:
                extra["repair_ratio_regression"] = True
                print(f"bench: REGRESSION — reduced-read heal moved "
                      f"{net_ratio:.2f}x the naive rebuild's repair "
                      f"bytes (must be <= {REPAIR_RATIO_TOL}x: "
                      f"{repair_bytes['heal']:.0f}B vs "
                      f"{repair_bytes['naive']:.0f}B). Failing the "
                      f"bench run.", file=sys.stderr)
        if not healed:
            extra["heal_time_regression"] = True
            print("bench: REGRESSION — automatic healing never converged "
                  "within its deadline. Failing the bench run.",
                  file=sys.stderr)
            return
        ratio = heal_s / max(serial_s, 1e-9)
        extra["heal_ratio"] = round(ratio, 3)
        if ratio > HEAL_REGRESSION_TOL:
            extra["heal_time_regression"] = True
            print(f"bench: REGRESSION — automatic healing took "
                  f"{ratio:.2f}x the serial-rebuild baseline "
                  f"({heal_s:.2f}s vs {serial_s:.2f}s); the concurrent "
                  f"repair executor has stopped paying off. Failing the "
                  f"bench run.", file=sys.stderr)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_chaos(extra: dict, n_volumes: int = 3,
                 blobs_per_vol: int = 24, size: int = 48 * 1024) -> None:
    """The chaos driver's three numbers (ISSUE 9):

    chaos_mttr_s                   seconds from the SLO burn-rate flip
                                   (the repair_backlog rule seeing lost
                                   shards) to the SLO reading ok again
                                   after the automatic repair converged
    repair_interference_p99_ratio  foreground blob-read p99 WITH the
                                   repair planner rebuilding lost shards
                                   vs idle — gated at
                                   REPAIR_INTERFERENCE_TOL (nonzero exit
                                   above 1.5x; arXiv:1709.05365's
                                   online-repair interference metric)
    chaos_hedge_p99_ratio          degraded-read p99 with hedging off vs
                                   on under a 350ms-slow shard peer
                                   (>1 means hedging pays; the >=1.2x
                                   GATE lives in tests/test_chaos.py)
    chaos_scenarios                two matrix cells run end-to-end
                                   (integrity asserted; failure flips
                                   chaos_scenario_failed -> exit 1)
    """
    import tempfile as _tf
    import threading
    import urllib.request

    from seaweedfs_tpu import native
    from seaweedfs_tpu.maintenance import chaos, faults
    from seaweedfs_tpu.maintenance.chaos import (ChaosCluster,
                                                 encode_all_volumes,
                                                 run_scenario)
    from seaweedfs_tpu.utils import resilience

    overrides = {
        "WEEDTPU_EC_CODEC": "cpp" if native.available() else "numpy",
        "WEEDTPU_SCRUB_INTERVAL": "3600",
        "WEEDTPU_REPAIR_INTERVAL": "3600",
        "WEEDTPU_REPAIR_CONCURRENCY": "8",
        "WEEDTPU_REPAIR_BURST": "8",
        "WEEDTPU_AGG_INTERVAL": "0",       # the bench pumps scrapes
        "WEEDTPU_SLO_WINDOWS": "5,15",     # minutes-long windows would
                                           # dominate a seconds-long MTTR
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)

    def p99(samples):
        s = sorted(samples)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    def blob_get(url, fid, timeout=60.0):
        with urllib.request.urlopen(f"http://{url}/{fid}",
                                    timeout=timeout) as r:
            return r.read()

    try:
        with _tf.TemporaryDirectory(prefix="weedtpu-chaos-") as d:
            import pathlib
            tmp = pathlib.Path(d)
            c = ChaosCluster(tmp, n_volume_servers=2, with_filer=True,
                             heartbeat_interval=0.2).start()
            try:
                c.wait_heartbeats()
                master = c.leader()
                client = c.client()
                rng = np.random.default_rng(23)
                blobs: dict[str, bytes] = {}
                for i in range(n_volumes * blobs_per_vol):
                    data = rng.integers(0, 256, size,
                                        dtype=np.uint8).tobytes()
                    blobs[client.upload(data, name=f"c{i}.bin")] = data
                time.sleep(0.5)
                encode_all_volumes(c)
                fids = list(blobs)

                # --- idle arm: foreground read p99, repair quiet ------
                # warm pass first: the cold EC read path (location
                # lookups, fd opens, page cache) must not be billed to
                # the idle arm and flatter the interference ratio
                for fid in fids:
                    blob_get(client.lookup(int(fid.split(",")[0]))[0],
                             fid)
                lat_idle = []
                t_end = time.perf_counter() + 6.0
                i = 0
                while time.perf_counter() < t_end:
                    fid = fids[i % len(fids)]
                    i += 1
                    t0 = time.perf_counter()
                    url = client.lookup(int(fid.split(",")[0]))[0]
                    assert blob_get(url, fid) == blobs[fid]
                    lat_idle.append(time.perf_counter() - t0)

                # --- fault: lose 2 shards per volume ------------------
                vs0 = c.volume_servers[0]
                for vid in chaos._ec_vids_on(vs0):
                    ev = vs0.store.get_ec_volume(vid)
                    for sid in ev.shard_ids()[:2]:
                        faults.delete_shard(vs0.store, vid, sid)
                c.submit(vs0._heartbeat_once())

                # --- MTTR: SLO flip -> repair -> SLO ok ---------------
                def slo_state() -> str:
                    master.maintenance.ledger()  # refresh health gauge
                    master.aggregator.scrape_once()
                    return master.aggregator.slo_status().get("state",
                                                              "unknown")

                flipped = False
                flip_deadline = time.time() + 30.0
                while time.time() < flip_deadline:
                    if slo_state() != "ok":
                        flipped = True
                        break
                    time.sleep(0.2)
                t_flip = time.perf_counter()
                mttr = None

                # --- interference arm: reads while the repair runs ----
                lat_repair: list[float] = []
                stop_reads = threading.Event()

                def reader():
                    j = 0
                    while not stop_reads.is_set():
                        fid = fids[j % len(fids)]
                        j += 1
                        t0 = time.perf_counter()
                        try:
                            got = blob_get(client.lookup(
                                int(fid.split(",")[0]))[0], fid)
                        except OSError:
                            continue
                        if got == blobs[fid]:
                            lat_repair.append(time.perf_counter() - t0)

                rt = threading.Thread(target=reader, daemon=True)
                rt.start()
                try:
                    chaos.heal_until_clean(c, timeout=120.0)
                    rec_deadline = time.time() + 60.0
                    while time.time() < rec_deadline:
                        if slo_state() == "ok":
                            mttr = time.perf_counter() - t_flip
                            break
                        time.sleep(0.2)
                finally:
                    stop_reads.set()
                    rt.join(10)

                if mttr is not None and flipped:
                    extra["chaos_mttr_s"] = round(mttr, 3)
                elif not flipped:
                    # without the burn-rate flip the number would just
                    # be heal time wearing an MTTR costume — report the
                    # miss instead so a detection regression is visible
                    extra["chaos_mttr_flip_missed"] = True
                    print("bench: chaos MTTR — SLO never flipped on the "
                          "injected shard loss; no chaos_mttr_s",
                          file=sys.stderr)
                if lat_idle and len(lat_repair) >= 20:
                    ratio = p99(lat_repair) / max(p99(lat_idle), 1e-9)
                    extra["repair_interference_p99_ratio"] = round(ratio, 3)
                    extra["repair_interference_p99_idle_ms"] = round(
                        p99(lat_idle) * 1000.0, 2)
                    extra["repair_interference_p99_repair_ms"] = round(
                        p99(lat_repair) * 1000.0, 2)
                    if ratio > REPAIR_INTERFERENCE_TOL:
                        extra["repair_interference_regression"] = True
                        print(f"bench: REGRESSION — foreground read p99 "
                              f"under repair is {ratio:.2f}x idle "
                              f"(> {REPAIR_INTERFERENCE_TOL}x). Failing "
                              f"the bench run.", file=sys.stderr)

                client.close()
            finally:
                c.stop()
                resilience.reset_breakers()

        # --- hedge ratio under a slow shard peer (deterministic
        # placement: shards 0+1 behind a 350ms peer, 12 survivors
        # local; maintenance/chaos.hedge_ratio_arms) -------------------
        with _tf.TemporaryDirectory(prefix="weedtpu-chaos-") as d:
            import pathlib
            c = ChaosCluster(pathlib.Path(d), n_volume_servers=2,
                             with_filer=False,
                             heartbeat_interval=0.2).start()
            try:
                c.wait_heartbeats()
                client = c.client()
                rng = np.random.default_rng(29)
                hedge_blobs = {}
                for i in range(24):
                    data = rng.integers(0, 256, 50_000,
                                        dtype=np.uint8).tobytes()
                    hedge_blobs[client.upload(data)] = data
                vid = int(next(iter(hedge_blobs)).partition(",")[0])
                time.sleep(0.5)
                p_off, p_on = chaos.hedge_ratio_arms(c, hedge_blobs, vid)
                extra["chaos_hedge_p99_ratio"] = round(
                    p_off / max(p_on, 1e-9), 3)
                extra["chaos_hedge_p99_off_ms"] = round(p_off * 1000.0, 2)
                extra["chaos_hedge_p99_on_ms"] = round(p_on * 1000.0, 2)
                client.close()
            finally:
                c.stop()
                resilience.reset_breakers()

        # --- two representative matrix cells, integrity-asserted ------
        scenarios = [("degraded_read", "shard_loss"),
                     ("filer_stream", "partition")]
        reports = []
        for workload, fault in scenarios:
            with _tf.TemporaryDirectory(prefix="weedtpu-chaos-") as d:
                import pathlib
                c = ChaosCluster(pathlib.Path(d), n_volume_servers=2,
                                 with_filer=True,
                                 heartbeat_interval=0.2).start()
                try:
                    c.wait_heartbeats()
                    reports.append(run_scenario(c, workload, fault))
                except Exception as e:
                    extra["chaos_scenario_failed"] = True
                    print(f"bench: chaos scenario {workload}x{fault} "
                          f"FAILED: {e}. Failing the bench run.",
                          file=sys.stderr)
                finally:
                    c.stop()
                    resilience.reset_breakers()
        if reports:
            extra["chaos_scenarios"] = reports
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_autopilot(extra: dict, blobs_per_group: int = 18,
                     size: int = 24 * 1024) -> None:
    """Autopilot under a shifting-Zipf open-loop read workload (ISSUE 15):

    autopilot_p99_ratio     foreground read p99 with the autopilot OFF
                            over p99 with it ON (execute mode), in the
                            settled window after the hotspot shifts
                            onto an EC-tiered volume group.  OFF keeps
                            the hot group on the EC read path forever;
                            ON detects the sustained-hot volume and
                            promotes it back to the mmap fast path, so
                            >1 means the decision layer pays.  Gated
                            via the bench trajectory (TRAJECTORY_GATED).
    autopilot_heal_p99_*_ms p99 of the reads that overlapped the
                            post-shift shard-loss heal, per arm
                            (repair-interference view; informational —
                            a single in-process rebuild burst is too
                            bursty to gate a ratio on)
    autopilot_promotes      promote actions the ON arm executed (0 would
                            make the ratio vacuous: recorded + flagged)

    Both arms run the identical schedule: two volume groups sealed to
    EC up front (the demoted state), Zipf reads hot on group A shifting
    to group B at half-time, shard loss on a PARKED volume healed
    synchronously at the shift (the interference phase), then each
    arm's decision loop runs to quiescence BEFORE the measured window —
    the gated number compares steady serving paths, not whichever arm a
    rebuild burst happened to land in.
    """
    import asyncio
    import tempfile as _tf
    import threading

    from seaweedfs_tpu.maintenance import chaos as _chaos
    from seaweedfs_tpu.maintenance import faults
    from seaweedfs_tpu.maintenance.chaos import ChaosCluster
    from seaweedfs_tpu.utils import resilience

    overrides = {
        "WEEDTPU_SCRUB_INTERVAL": "3600",
        "WEEDTPU_REPAIR_INTERVAL": "3600",  # the bench drives ticks
        "WEEDTPU_AGG_INTERVAL": "0",
        "WEEDTPU_CONVERT_RATE": "100",
        "WEEDTPU_CONVERT_BURST": "100",
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    old_mode = os.environ.get("WEEDTPU_AUTOPILOT")
    os.environ.update(overrides)

    def p99(samples):
        s = sorted(samples)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    def run_arm(mode: str):
        """-> (settled-window read p99 s, promotes executed,
        heal-phase read p99 s or None)."""
        os.environ["WEEDTPU_AUTOPILOT"] = mode
        with _tf.TemporaryDirectory(prefix="weedtpu-ap-") as d:
            import pathlib
            c = ChaosCluster(pathlib.Path(d), n_volume_servers=1,
                             with_filer=False,
                             heartbeat_interval=0.2).start()
            try:
                c.wait_heartbeats()
                master = c.leader()
                ap = master.autopilot
                # bench-speed thresholds; demotes disabled mid-run (the
                # sealed setup IS the demoted state, and re-demote churn
                # would measure the scheduler, not the promote payoff)
                ap.hot_rps = 0.5
                ap.hot_s = 1.0
                ap.cooldown_s = 0.0
                ap.cold_s = 1e9
                client = c.client()
                rng = np.random.default_rng(0xA117)
                groups: list[list[str]] = []
                payload: dict[str, bytes] = {}
                for gi, collection in enumerate(("", "tier2", "parked")):
                    fids = []
                    for i in range(blobs_per_group):
                        data = rng.integers(0, 256, size,
                                            dtype=np.uint8).tobytes()
                        fid = client.upload(data, name=f"g{gi}-{i}.bin",
                                            collection=collection)
                        payload[fid] = data
                        fids.append(fid)
                    groups.append(fids)
                vs = c.volume_servers[0]
                vids = sorted({vid for loc in vs.store.locations
                               for vid in loc.volumes})
                for v in vids:
                    vs.store.get_volume(v).nm.flush()
                time.sleep(0.5)
                # the demoted state, identically in both arms: every
                # volume sealed to EC (shard set serves, .dat retired)
                master.convert.enqueue(vids, seal=True)
                c.submit(asyncio.wait_for(master.convert.tick(), 120))
                assert master.convert.status()["converted"] == \
                    len(vids), master.convert.status()
                time.sleep(0.5)
                # warm pass: cold-path costs must not skew either arm
                for fid in payload:
                    client.download(fid)

                half = 2.5              # hotspot shift time
                window_s = 5.0          # measured window length
                lats: list[tuple[float, float]] = []
                lats_lock = threading.Lock()
                stop = threading.Event()
                t0 = time.perf_counter()

                def reader(seed):
                    from seaweedfs_tpu.client import WeedClient
                    # one pooled (keep-alive) client per thread: a
                    # fresh TCP dial per request costs ~10 ms on this
                    # host and would bury the serving-path difference
                    cl = WeedClient(master.url)
                    r = np.random.default_rng(seed)
                    zipf = r.zipf(1.4, size=4096)
                    j = 0
                    mine = []
                    while not stop.is_set():
                        now = time.perf_counter() - t0
                        hot, cold = (groups[0], groups[1]) \
                            if now < half else (groups[1], groups[0])
                        grp = hot if r.random() < 0.85 else cold
                        fid = grp[int(zipf[j % len(zipf)]) % len(grp)]
                        j += 1
                        t1 = time.perf_counter()
                        try:
                            got = cl.download(fid)
                        except (OSError, RuntimeError):
                            continue
                        if got == payload[fid]:
                            mine.append((now,
                                         time.perf_counter() - t1))
                    cl.close()
                    with lats_lock:
                        lats.extend(mine)

                readers = [threading.Thread(target=reader, args=(s,),
                                            daemon=True)
                           for s in (11, 12, 13, 14, 15, 16)]
                for r in readers:
                    r.start()
                # phase 1: hotspot on group A until the shift
                while time.perf_counter() - t0 < half:
                    master.collect_heat()
                    c.submit(asyncio.wait_for(master.autopilot.tick(),
                                              30))
                    time.sleep(0.3)
                # the shift: repair interference fires in BOTH arms —
                # shards lost on the PARKED (never-read) volume, healed
                # synchronously while the readers hammer the new
                # hotspot; its p99 is recorded separately below
                heal_t0 = time.perf_counter() - t0
                ev_vid = next(
                    (v for v in vids
                     if v not in {int(f.partition(",")[0])
                                  for f in groups[0] + groups[1]}
                     and vs.store.get_ec_volume(v) is not None), None)
                if ev_vid is not None:
                    ev = vs.store.get_ec_volume(ev_vid)
                    for sid in ev.shard_ids()[:2]:
                        faults.delete_shard(vs.store, ev_vid, sid)
                    c.submit(vs._heartbeat_once())
                    c.drive_repair(wait=True)
                heal_t1 = time.perf_counter() - t0
                # run the decision loop to quiescence: the gated window
                # must compare steady serving paths, so the promote's
                # detection + decode (ON arm) happens HERE, not inside
                # the measurement.  The condition is a done promote of a
                # GROUP B volume specifically — phase 1 may already have
                # promoted the then-hot group A, which must not satisfy
                # the wait for the post-shift hotspot
                b_vids = {int(f.partition(",")[0]) for f in groups[1]}
                quiesce_deadline = time.perf_counter() + 4.0
                while time.perf_counter() < quiesce_deadline:
                    master.collect_heat()
                    c.submit(asyncio.wait_for(master.autopilot.tick(),
                                              30))
                    c.submit(asyncio.wait_for(
                        master.autopilot.wait_idle(), 60))
                    if mode != "execute" or any(
                            p["policy"] == "tiering_promote"
                            and p["state"] == "done"
                            and p["vid"] in b_vids
                            for p in master.autopilot.plans.values()):
                        break
                    time.sleep(0.3)
                settle = time.perf_counter() - t0 + 0.3
                time.sleep(window_s + 0.3)
                stop.set()
                for rt in readers:
                    rt.join(10)
                promotes = sum(
                    1 for p in master.autopilot.plans.values()
                    if p["policy"] == "tiering_promote"
                    and p["state"] == "done")
                heal = [l for ts, l in lats
                        if heal_t0 <= ts < heal_t1]
                window = [(ts, l) for ts, l in lats if ts >= settle]
                client.close()
                if len(window) < 200:
                    raise RuntimeError(
                        f"only {len(window)} settled-window samples")
                # median of per-second sub-window p99s: still a tail
                # statistic, but one host stall (GC, scheduler hiccup —
                # 50-100 ms on this virtualized host) corrupts one
                # sub-window instead of owning the whole arm's p99;
                # measured run-to-run spread drops ~3x vs a raw p99
                buckets: dict[int, list[float]] = {}
                for ts, l in window:
                    buckets.setdefault(int(ts - settle), []).append(l)
                sub = sorted(p99(b) for b in buckets.values()
                             if len(b) >= 50)
                if not sub:
                    raise RuntimeError("no populated sub-windows")
                return (sub[len(sub) // 2], promotes,
                        p99(heal) if len(heal) >= 20 else None)
            finally:
                c.stop()
                resilience.reset_breakers()
                _chaos.faults.clear_net()

    try:
        p_off, _, heal_off = run_arm("0")
        p_on, promotes, heal_on = run_arm("execute")
        extra["autopilot_p99_off_ms"] = round(p_off * 1000.0, 2)
        extra["autopilot_p99_on_ms"] = round(p_on * 1000.0, 2)
        if heal_off is not None:
            extra["autopilot_heal_p99_off_ms"] = round(
                heal_off * 1000.0, 2)
        if heal_on is not None:
            extra["autopilot_heal_p99_on_ms"] = round(
                heal_on * 1000.0, 2)
        extra["autopilot_promotes"] = promotes
        if promotes == 0:
            # vacuity guard: an ON arm that never promoted measured
            # nothing — record the miss, do NOT record a fake ratio
            extra["autopilot_bench_vacuous"] = True
            print("bench: autopilot ON arm executed zero promotes; "
                  "autopilot_p99_ratio not recorded", file=sys.stderr)
        else:
            ratio = p_off / max(p_on, 1e-9)
            extra["autopilot_p99_ratio"] = round(ratio, 3)
            # the TRAJECTORY_GATED twin, saturated at 1.1: host load
            # compresses both arms toward parity (measured: 1.25 idle
            # -> 1.03 under a concurrent test suite), so the gate
            # asserts "never worse than off" rather than chasing the
            # idle-host margin round over round
            extra["autopilot_p99_gate"] = round(min(ratio, 1.1), 3)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if old_mode is None:
            os.environ.pop("WEEDTPU_AUTOPILOT", None)
        else:
            os.environ["WEEDTPU_AUTOPILOT"] = old_mode


def _bench_fleetsim(extra: dict, small: int = 50, large: int = 500,
                    ticks: int = 5) -> None:
    """Control-plane scaling under a simulated fleet (ISSUE 18): a real
    master scraped over loopback from FleetSim vnodes whose responses
    carry a 25 ms service delay, so scrape RTT — the term the fan-out
    pool amortizes — dominates the aggregator tick the way a real
    network does.

    fleet_sim_agg_tick_ms_{50,500}         median aggregator tick wall
                                           (ms) at each fleet size with
                                           the fleet-scaled pool
                                           (utils/fanout.py default)
    fleet_sim_agg_tick_ms_fixed8_{50,500}  same, pool pinned at 8 — the
                                           pre-fix min(8, n) wall, kept
                                           as the before-curve so the
                                           pool win stays a measured
                                           number round over round
    fleet_sim_tick_ratio                   med(500)/med(50), scaled
                                           pool: the tick-time-vs-node-
                                           count scaling curve.  ~10x
                                           nodes -> <=10 means linear
                                           or better; raw value swings
                                           with host weather (the
                                           50-node arm is overhead-
                                           dominated), so the GATED
                                           twin fleet_sim_tick_gate =
                                           max(ratio, 11) saturates in
                                           the linear regime and fails
                                           only on a genuinely
                                           superlinear wall (an O(n^2)
                                           merge would read ~100)
    fleet_sim_pool_win                     fixed8_500 / scaled_500: the
                                           pool fix's measured win at
                                           500 nodes (~2.2-2.6x).  Both
                                           arms run back-to-back in one
                                           process, so host weather
                                           cancels; the gated twin
                                           fleet_sim_pool_gate =
                                           min(win, 1.5) fails when the
                                           fan-out pool stops scaling
                                           (win collapses to ~1.0) —
                                           the regression detector for
                                           this round's fix
    fleet_sim_actions_per_s                loop-observatory throughput:
                                           sum of per-loop items
                                           processed (scrapes parsed,
                                           series recorded, nodes
                                           observed) per wall second at
                                           500 nodes.  Raw value is
                                           host-speed-bound (measured
                                           1300-1900/s across runs);
                                           the gated twin
                                           fleet_sim_actions_gate =
                                           min(value, 800) asserts the
                                           observatory never collapses
                                           below ~800 actions/s
    """
    import pathlib
    import statistics
    import tempfile as _tf

    from seaweedfs_tpu.maintenance.chaos import ChaosCluster
    from seaweedfs_tpu.maintenance.fleetsim import FleetSim

    overrides = {
        "WEEDTPU_SCRUB_INTERVAL": "3600",
        "WEEDTPU_REPAIR_INTERVAL": "3600",  # the bench drives ticks
        "WEEDTPU_AGG_INTERVAL": "0",
        "WEEDTPU_FLEETSIM_DELAY_MS": "25",
    }
    old_env = {k: os.environ.get(k)
               for k in (*overrides, "WEEDTPU_FANOUT_POOL")}
    os.environ.update(overrides)

    def repool(agg):
        # the fan-out pool is grow-only; drop it so the next scrape
        # rebuilds at the current knob (lets one process measure both
        # the pinned-8 before-arm and the fleet-scaled after-arm)
        with agg._lock:
            ex, agg._pull_ex, agg._pull_ex_size = agg._pull_ex, None, 0
        if ex is not None:
            ex.shutdown(wait=False)

    def med_tick_ms(agg, pool: str | None) -> float:
        if pool is None:
            os.environ.pop("WEEDTPU_FANOUT_POOL", None)
        else:
            os.environ["WEEDTPU_FANOUT_POOL"] = pool
        repool(agg)
        agg.scrape_once()  # warm: pool build + first-sight baselines
        samples = []
        for _ in range(ticks):
            t0 = time.perf_counter()
            agg.scrape_once()
            samples.append((time.perf_counter() - t0) * 1000.0)
        return statistics.median(samples)

    try:
        with _tf.TemporaryDirectory(prefix="weedtpu-fs-") as d:
            c = ChaosCluster(pathlib.Path(d), n_volume_servers=1,
                             with_filer=False,
                             heartbeat_interval=0.2).start()
            sim = None
            try:
                c.wait_heartbeats()
                master = c.leader()
                sim = FleetSim(master.url, nodes=small, racks=10,
                               volumes_per_node=4, heartbeat_s=3600.0,
                               seed=11)
                sim.start()
                sim.beat_all()
                fixed8_50 = med_tick_ms(master.aggregator, "8")
                scaled_50 = med_tick_ms(master.aggregator, None)
                sim.add_nodes(large - small)
                sim.beat_all()
                fixed8_500 = med_tick_ms(master.aggregator, "8")
                # the scaled arm doubles as the actions/s window: every
                # monitored loop runs on these same scrape_once ticks
                before = master.loops.status()["loops"]
                items0 = sum(st["items_total"] for st in before.values())
                w0 = time.perf_counter()
                scaled_500 = med_tick_ms(master.aggregator, None)
                elapsed = time.perf_counter() - w0
                after = master.loops.status()["loops"]
                items1 = sum(st["items_total"] for st in after.values())
                extra["fleet_sim_agg_tick_ms_fixed8_50"] = round(
                    fixed8_50, 2)
                extra["fleet_sim_agg_tick_ms_50"] = round(scaled_50, 2)
                extra["fleet_sim_agg_tick_ms_fixed8_500"] = round(
                    fixed8_500, 2)
                extra["fleet_sim_agg_tick_ms_500"] = round(scaled_500, 2)
                ratio = scaled_500 / max(scaled_50, 1e-9)
                extra["fleet_sim_tick_ratio"] = round(ratio, 3)
                extra["fleet_sim_tick_gate"] = round(max(ratio, 11.0), 3)
                win = fixed8_500 / max(scaled_500, 1e-9)
                extra["fleet_sim_pool_win"] = round(win, 3)
                extra["fleet_sim_pool_gate"] = round(min(win, 1.5), 3)
                actions = (items1 - items0) / max(elapsed, 1e-9)
                extra["fleet_sim_actions_per_s"] = round(actions, 1)
                extra["fleet_sim_actions_gate"] = round(
                    min(actions, 800.0), 1)
            finally:
                if sim is not None:
                    sim.stop()
                c.stop()
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_flow_canary_overhead(extra: dict, n: int = 1200,
                                size: int = 1024, concurrency: int = 16,
                                pairs: int = 7) -> None:
    """Flight-recorder tax on the hottest path: blob reads with byte-flow
    accounting ON plus a fast-cycling canary prober (0.25s rounds writing
    /reading/deleting sentinel blobs through the live cluster) vs both
    OFF (WEEDTPU_NETFLOW=0, no canary), interleaved pairs over the same
    blobs.  Median ratio below FLOW_CANARY_OVERHEAD_TOL (foreground must
    keep >= 0.97x) fails the run (flow_canary_overhead_regression +
    nonzero exit).  The ON arm's canary p99 is recorded as
    canary_probe_p99_ms."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    overrides = {
        "WEEDTPU_CANARY_INTERVAL": "0",  # the bench drives start/stop
        "WEEDTPU_CANARY_PATHS": "blob",
        "WEEDTPU_SCRUB_MBPS": "0",
        "WEEDTPU_REPAIR_INTERVAL": "3600",
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    old_netflow = os.environ.get("WEEDTPU_NETFLOW")
    os.environ.update(overrides)
    best_on = best_off = float("inf")
    ratios: list[float] = []
    p99 = None
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-flow-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []

            async def canary_on():
                master.canary.start(0.25)

            async def canary_off():
                master.canary.stop()

            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"fc{i}"),
                        range(n)))

                def rep(recorder: bool) -> float:
                    os.environ["WEEDTPU_NETFLOW"] = \
                        "1" if recorder else "0"
                    if recorder:
                        run(canary_on())
                    try:
                        t0 = time.perf_counter()
                        with concurrent.futures.ThreadPoolExecutor(
                                concurrency) as ex:
                            for data in ex.map(client.download, fids):
                                assert len(data) == size
                        return time.perf_counter() - t0
                    finally:
                        if recorder:
                            run(canary_off())

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = rep(False)
                        t_on = rep(True)
                    else:
                        t_on = rep(True)
                        t_off = rep(False)
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                # guarantee latency samples even when every rep beat
                # the 0.25s canary tick to the finish line
                run(master.canary.run_once(paths=("blob",)))
                st = master.canary.status()
                p99 = st.get("paths", {}).get("blob", {}).get("p99_ms")
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if old_netflow is None:
            os.environ.pop("WEEDTPU_NETFLOW", None)
        else:
            os.environ["WEEDTPU_NETFLOW"] = old_netflow
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_recorded"] = round(n / best_on, 1)
    extra["blob_read_rps_unrecorded"] = round(n / best_off, 1)
    extra["flow_canary_overhead_ratio"] = round(ratio, 3)
    if p99 is not None:
        extra["canary_probe_p99_ms"] = round(p99, 2)
    if ratio < FLOW_CANARY_OVERHEAD_TOL:
        extra["flow_canary_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with byte-flow accounting "
              f"+ the canary prober run at {ratio:.3f}x the unrecorded "
              f"rate (median of interleaved pairs); the flight recorder "
              f"exceeds its 3% budget. Failing the bench run.",
              file=sys.stderr)


def _bench_scrub_overhead(extra: dict, n: int = 1000, size: int = 1024,
                          concurrency: int = 16, pairs: int = 7) -> None:
    """Scrub tax on foreground reads: blob reads against an in-process
    master+volume cluster with a continuously-cycling rate-limited
    scrubber vs without, interleaved pairs over the same blobs.  Median
    ratio below SCRUB_OVERHEAD_TOL (foreground must keep >= 0.95x) fails
    the run (scrub_overhead_regression + nonzero exit)."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu import native
    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.maintenance.scrub import Scrubber
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    overrides = {
        "WEEDTPU_EC_CODEC": "cpp" if native.available() else "numpy",
        "WEEDTPU_SCRUB_INTERVAL": "3600",  # the server's own loop parks
        "WEEDTPU_REPAIR_INTERVAL": "3600",
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    best_on = best_off = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-scrub-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"s{i}"),
                        range(n)))

                def read_all() -> float:
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    return time.perf_counter() - t0

                def rep_on() -> float:
                    # continuously cycling, rate-limited like production
                    s = Scrubber(vs.store, mbps=16, interval=0.01).start()
                    try:
                        time.sleep(0.05)  # let the first pass begin
                        return read_all()
                    finally:
                        s.stop()

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = read_all()
                        t_on = rep_on()
                    else:
                        t_on = rep_on()
                        t_off = read_all()
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_scrubbed"] = round(n / best_on, 1)
    extra["blob_read_rps_unscrubbed"] = round(n / best_off, 1)
    extra["scrub_overhead_ratio"] = round(ratio, 3)
    if ratio < SCRUB_OVERHEAD_TOL:
        extra["scrub_overhead_regression"] = True
        print(f"bench: REGRESSION — foreground blob reads run at "
              f"{ratio:.3f}x with the scrubber active (median of "
              f"interleaved pairs); the scrub rate limiter has stopped "
              f"protecting foreground I/O. Failing the bench run.",
              file=sys.stderr)


def _bench_heat_overhead(extra: dict, n: int = 1200, size: int = 1024,
                         concurrency: int = 16, pairs: int = 7) -> None:
    """Workload-heat tax on the hottest path: blob reads with the heat
    sketches updating per request (WEEDTPU_HEAT=1, the default) vs fully
    off (=0), interleaved pairs over the same blobs.  The tracker reads
    the env per record call, so flipping it between reps retargets live
    servers.  Median ratio below HEAT_OVERHEAD_TOL (foreground must keep
    >= 0.97x) fails the run (heat_overhead_regression + nonzero
    exit)."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    old = os.environ.get("WEEDTPU_HEAT")
    best_on = best_off = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-heat-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"ht{i}"),
                        range(n)))

                def rep(tracking: str) -> float:
                    os.environ["WEEDTPU_HEAT"] = tracking
                    # the tracker caches the env switch for up to 0.5s;
                    # let the flip take effect before timing the arm
                    time.sleep(0.6)
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    return time.perf_counter() - t0

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = rep("0")
                        t_on = rep("1")
                    else:
                        t_on = rep("1")
                        t_off = rep("0")
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_HEAT", None)
        else:
            os.environ["WEEDTPU_HEAT"] = old
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_heat"] = round(n / best_on, 1)
    extra["blob_read_rps_unheat"] = round(n / best_off, 1)
    extra["heat_overhead_ratio"] = round(ratio, 3)
    if ratio < HEAT_OVERHEAD_TOL:
        extra["heat_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with workload-heat "
              f"tracking run at {ratio:.3f}x the untracked rate (median "
              f"of interleaved pairs); the heat sketches exceed their "
              f"3% budget. Failing the bench run.", file=sys.stderr)


def _bench_history_overhead(extra: dict, n: int = 1200, size: int = 1024,
                            concurrency: int = 16, pairs: int = 7) -> None:
    """History-plane tax on the hottest path: blob reads while the
    master's aggregator scrapes the fleet every 0.2s, with the history
    store recording each tick + alert evaluation + capacity forecasting
    ON (WEEDTPU_HISTORY=1, the default) vs fully OFF (=0), interleaved
    pairs over the same blobs.  The store reads the env per record call
    (0.5s TTL), so flipping it between reps retargets the live master.
    Median ratio below HISTORY_OVERHEAD_TOL (foreground must keep >=
    0.97x) fails the run (history_overhead_regression + nonzero exit)."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    old = {k: os.environ.get(k)
           for k in ("WEEDTPU_HISTORY", "WEEDTPU_AGG_INTERVAL")}
    os.environ["WEEDTPU_AGG_INTERVAL"] = "0.2"
    best_on = best_off = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-hist-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"hs{i}"),
                        range(n)))

                def rep(recording: str) -> float:
                    os.environ["WEEDTPU_HISTORY"] = recording
                    # the store caches the env switch for up to 0.5s;
                    # let the flip take effect before timing the arm
                    time.sleep(0.6)
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    return time.perf_counter() - t0

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = rep("0")
                        t_on = rep("1")
                    else:
                        t_on = rep("1")
                        t_off = rep("0")
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                # the ON arms must have really recorded — otherwise both
                # arms measured the recording-off path and the gate
                # would pass vacuously over a broken history plane
                if master.history.series_count() == 0 or \
                        master.history.ticks == 0:
                    raise RuntimeError(
                        "history recording never engaged during the ON "
                        "arms (0 series/ticks) — overhead gate is "
                        "meaningless")
                extra["history_series"] = master.history.series_count()
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_history"] = round(n / best_on, 1)
    extra["blob_read_rps_unhistory"] = round(n / best_off, 1)
    extra["history_overhead_ratio"] = round(ratio, 3)
    if ratio < HISTORY_OVERHEAD_TOL:
        extra["history_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with history recording "
              f"run at {ratio:.3f}x the recording-off rate (median of "
              f"interleaved pairs); the history plane exceeds its 3% "
              f"budget. Failing the bench run.", file=sys.stderr)


def _bench_geo_replication(extra: dict, n: int = 48, size: int = 64 * 1024,
                           pairs: int = 5, batch_files: int = 12) -> None:
    """Geo-replication observatory bench on a real two-region topology
    (GeoCluster: two master+VS+filer clusters linked by FilerSync).
    Three headline numbers:

    - ``geo_replication_lag_s``: steady-state replication lag right
      after a converged write batch (trajectory-gated, lower is better);
    - ``geo_catchup_mbps``: post-partition catch-up throughput — bytes
      written during a WAN partition divided by the time from heal() to
      byte-converged on the far region, reconnect backoff included
      (trajectory-gated, higher is better);
    - ``geo_obs_overhead_ratio``: the observatory's own price — batch
      write+converge throughput with WEEDTPU_GEO_OBS on vs off,
      interleaved pairs (the pump reads the switch per event), median
      ratio below GEO_OBS_OVERHEAD_TOL fails the run."""
    import pathlib

    from seaweedfs_tpu.maintenance.chaos import GeoCluster
    from seaweedfs_tpu.stats import metrics as _metrics

    old = {k: os.environ.get(k) for k in (
        "WEEDTPU_GEO_OBS", "WEEDTPU_GEO_AUDIT_INTERVAL",
        "WEEDTPU_SYNC_BACKLOG_INTERVAL", "WEEDTPU_SYNC_BACKOFF_BASE",
        "WEEDTPU_SYNC_BACKOFF_CAP")}
    # deterministic arms: no background audits, fast reconnects
    os.environ["WEEDTPU_GEO_AUDIT_INTERVAL"] = "0"
    os.environ["WEEDTPU_SYNC_BACKLOG_INTERVAL"] = "1"
    os.environ["WEEDTPU_SYNC_BACKOFF_BASE"] = "0.1"
    os.environ["WEEDTPU_SYNC_BACKOFF_CAP"] = "0.5"
    os.environ.pop("WEEDTPU_GEO_OBS", None)
    payload = (bytes(range(256)) * (size // 256 + 1))[:size]
    seq = iter(range(10_000))
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-geo-") as d:
            geo = GeoCluster(pathlib.Path(d))
            geo.start()
            try:
                def converge(paths, timeout=120.0):
                    deadline = time.time() + timeout
                    for p in paths:
                        while geo.read("b", p)[0] != 200:
                            if time.time() > deadline:
                                raise RuntimeError(
                                    f"replication never converged: {p}")
                            time.sleep(0.02)

                def batch(count) -> float:
                    tag = next(seq)
                    paths = [f"/bench/{tag}/f{i}.bin" for i in range(count)]
                    t0 = time.perf_counter()
                    for p in paths:
                        geo.write("a", p, payload)
                    converge(paths)
                    return time.perf_counter() - t0

                batch(8)  # warm pools, volume grow, subscribe stream
                # steady state: lag right after a converged batch
                batch(n // 2)
                extra["geo_replication_lag_s"] = round(
                    geo.sync.a2b.lag_s(), 3)

                # catch-up: write through a WAN partition, heal, time to
                # byte-convergence on the far region
                geo.partition()
                paths = [f"/bench/catchup/f{i}.bin" for i in range(n)]
                for p in paths:
                    geo.write("a", p, payload)
                time.sleep(0.5)  # the pump must hit the partition first
                geo.heal()
                t0 = time.perf_counter()
                converge(paths)
                dt = time.perf_counter() - t0
                extra["geo_catchup_mbps"] = round(n * size / dt / 1e6, 2)

                # observatory price: interleaved GEO_OBS on/off pairs
                applied = _metrics.REPLICATION_APPLIED.labels("a->b")

                def rep(obs: str) -> float:
                    os.environ["WEEDTPU_GEO_OBS"] = obs
                    return batch(batch_files)

                ratios: list[float] = []
                for i in range(pairs):
                    before = applied.value
                    if i % 2 == 0:
                        t_on = rep("1")
                        t_off = rep("0")
                    else:
                        t_off = rep("0")
                        t_on = rep("1")
                    # the ON arm must have really exported: otherwise
                    # both arms measured the obs-off path and the gate
                    # would pass vacuously over a broken lag plane
                    if applied.value <= before:
                        raise RuntimeError(
                            "geo observatory never engaged during the "
                            "ON arm — overhead gate is meaningless")
                    if i == 0:
                        continue  # warm page cache / pool connections
                    ratios.append(t_off / t_on)
            finally:
                geo.stop()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["geo_obs_overhead_ratio"] = round(ratio, 3)
    if ratio < GEO_OBS_OVERHEAD_TOL:
        extra["geo_obs_overhead_regression"] = True
        print(f"bench: REGRESSION — replicated writes with the geo "
              f"observatory on run at {ratio:.3f}x the obs-off rate "
              f"(median of interleaved pairs); the lag plane exceeds "
              f"its 3% budget. Failing the bench run.", file=sys.stderr)


def _bench_interference_overhead(extra: dict, n: int = 1200,
                                 size: int = 1024, concurrency: int = 16,
                                 pairs: int = 7) -> None:
    """Interference-plane tax on the hottest path: blob reads while the
    master's aggregator scrapes every 0.2s with the observatory delta'ing
    each tick AND the governor retuning the background buckets
    (WEEDTPU_INTERFERENCE=1 + WEEDTPU_GOVERNOR=1, the defaults) vs both
    fully OFF (=0), interleaved pairs over the same blobs.  The
    observatory reads its env per tick (0.5s TTL) so flipping it
    retargets the live master.  Median ratio below
    INTERFERENCE_OVERHEAD_TOL (foreground must keep >= 0.97x) fails the
    run (interference_overhead_regression + nonzero exit)."""
    import asyncio
    import concurrent.futures
    import threading

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    old = {k: os.environ.get(k)
           for k in ("WEEDTPU_INTERFERENCE", "WEEDTPU_GOVERNOR",
                     "WEEDTPU_AGG_INTERVAL")}
    os.environ["WEEDTPU_AGG_INTERVAL"] = "0.2"
    best_on = best_off = float("inf")
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-interf-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"if{i}"),
                        range(n)))

                engaged = {"ticks": 0, "nodes": 0}

                def rep(setting: str) -> float:
                    os.environ["WEEDTPU_INTERFERENCE"] = setting
                    os.environ["WEEDTPU_GOVERNOR"] = setting
                    # the observatory caches the env switch ~0.5s; let
                    # the flip take effect before timing the arm
                    time.sleep(0.6)
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(
                            concurrency) as ex:
                        for data in ex.map(client.download, fids):
                            assert len(data) == size
                    dt = time.perf_counter() - t0
                    if setting == "1":
                        # capture engagement evidence DURING the ON arm:
                        # an OFF arm retires the observatory's node
                        # state, so a post-loop snapshot would read
                        # empty whenever the last arm was OFF
                        engaged["ticks"] = max(engaged["ticks"],
                                               master.interference.ticks)
                        engaged["nodes"] = max(
                            engaged["nodes"],
                            len(master.interference.snapshot()["nodes"]))
                    return dt

                for i in range(pairs):
                    if i % 2 == 0:
                        t_off = rep("0")
                        t_on = rep("1")
                    else:
                        t_on = rep("1")
                        t_off = rep("0")
                    if i == 0:
                        continue  # warm connections / page cache
                    best_on = min(best_on, t_on)
                    best_off = min(best_off, t_off)
                    ratios.append(t_off / t_on)
                # vacuity guard: the ON arms must have really observed —
                # otherwise both arms measured the plane-off path and
                # the gate would pass over a broken observatory
                if engaged["ticks"] == 0 or engaged["nodes"] == 0:
                    raise RuntimeError(
                        "interference observatory never engaged during "
                        "the ON arms (0 ticks/nodes) — overhead gate is "
                        "meaningless")
                extra["interference_obs_ticks"] = engaged["ticks"]
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["blob_read_rps_interference"] = round(n / best_on, 1)
    extra["blob_read_rps_uninterference"] = round(n / best_off, 1)
    extra["interference_overhead_ratio"] = round(ratio, 3)
    if ratio < INTERFERENCE_OVERHEAD_TOL:
        extra["interference_overhead_regression"] = True
        print(f"bench: REGRESSION — blob reads with the interference "
              f"observatory + governor run at {ratio:.3f}x the "
              f"plane-off rate (median of interleaved pairs); the "
              f"interference plane exceeds its 3% budget. Failing the "
              f"bench run.", file=sys.stderr)


def _bench_serving_knee(extra: dict, n_blobs: int = 400,
                        size: int = 1024, start_rps: float = 50.0,
                        step: float = 1.6, max_rps: float = 8000.0,
                        level_s: float = 2.0) -> None:
    """Open-loop serving knee: Poisson arrivals at a TARGET rate (fired
    on schedule whether or not earlier requests finished — the
    closed-loop benches above self-throttle and can never see queueing
    collapse) stepped up until `/cluster/slo` flips off `ok`.  Reports
    `serving_knee_rps` (the last SLO-compliant arrival rate),
    `serving_knee_p99_ms` (client p99 at that rate), and the first
    violating rate — the measurement harness the ROADMAP item 4 serving
    plane will be gated on.  Tight 1s/3s SLO windows + an on-demand
    aggregator make each level's verdict reflect THAT level's traffic.

    The flip signal rides the CANARY's latency histogram: the
    server-side request histograms time the handler body, so overload
    queueing (which piles up in the accept queue and event loop, before
    any handler runs) is structurally invisible to them — but the
    canary prober is a CLIENT of the gateway paths, its probes queue
    behind the open-loop backlog like real requests, and its latency
    histogram already feeds the SLO engine.  A fast-cycling blob canary
    plus a `canary_latency` rule makes /cluster/slo flip exactly when
    the fleet stops absorbing the arrival rate."""
    import asyncio
    import concurrent.futures
    import random as _random
    import threading
    import urllib.request

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    overrides = {
        "WEEDTPU_AGG_INTERVAL": "0",  # scrape on demand per level
        "WEEDTPU_SLO_WINDOWS": "1,3",
        # the knee definition: canary-observed blob latency through
        # 250ms (the queueing-sensitive signal), volume-side service
        # time through 100ms (a genuinely slow store knees here), and
        # read availability
        "WEEDTPU_SLO_RULES":
            "read_availability=availability,op=read,target=0.999;"
            "read_latency=latency,family=weedtpu_volume_request_seconds,"
            "label.type=read,ms=100,target=0.9;"
            "canary_latency=latency,"
            "family=weedtpu_canary_probe_seconds,label.path=blob,"
            "ms=250,target=0.8;"
            "canary_availability=availability,"
            "family=weedtpu_canary_probes_total,target=0.99",
        "WEEDTPU_CANARY_INTERVAL": "0",  # started explicitly below
        "WEEDTPU_CANARY_PATHS": "blob",
        "WEEDTPU_REPAIR_INTERVAL": "3600",
        "WEEDTPU_SCRUB_MBPS": "0",
    }
    old_env = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    knee = None
    knee_p99 = None
    flip_rps = None
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-knee-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs = VolumeServer([d], master.url, port=free_port(),
                              heartbeat_interval=0.2)
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                client = WeedClient(master.url)
                payload = (bytes(range(256)) * (size // 256 + 1))[:size]
                with concurrent.futures.ThreadPoolExecutor(16) as ex:
                    fids = list(ex.map(
                        lambda i: client.upload(payload, name=f"k{i}"),
                        range(n_blobs)))

                async def canary_on():
                    master.canary.start(0.25)

                run(canary_on())

                def slo_state() -> str:
                    with urllib.request.urlopen(
                            f"http://{master.url}/cluster/slo?refresh=1",
                            timeout=30) as r:
                        return json.loads(r.read()).get("state", "unknown")

                rng = _random.Random(17)
                # wide pool: past the knee, completions lag arrivals and
                # in-flight requests pile up — a narrow pool would
                # quietly re-close the loop at its own width and the
                # arrival pressure would never reach the server
                pool = concurrent.futures.ThreadPoolExecutor(512)

                def level(rate: float) -> tuple[float | None, str]:
                    """Drive one open-loop level; -> (p99_ms, slo)."""
                    lat: list[float] = []
                    lat_lock = threading.Lock()

                    def one(fid: str) -> None:
                        t0 = time.perf_counter()
                        try:
                            client.download(fid)
                        except Exception:
                            pass  # a failed read is the SLO's problem
                        ms = (time.perf_counter() - t0) * 1000.0
                        with lat_lock:
                            lat.append(ms)

                    slo_state()  # window edge: snapshot before the load
                    t_next = time.perf_counter()
                    t_end = t_next + level_s
                    i = 0
                    while True:
                        t_next += rng.expovariate(rate)
                        if t_next >= t_end:
                            break
                        delay = t_next - time.perf_counter()
                        if delay > 0:
                            time.sleep(delay)
                        # open loop: fire on schedule, never wait for
                        # completions — backlog is the signal
                        pool.submit(one, fids[i % len(fids)])
                        i += 1
                    # verdict scrape while the backlog is LIVE (the
                    # canary's in-window probes are queueing behind it);
                    # only then drain so the next level starts clean and
                    # the client p99 covers every fired request
                    state = slo_state()
                    drain = time.time() + 30
                    while time.time() < drain:
                        with lat_lock:
                            done = len(lat)
                        if done >= i:
                            break
                        time.sleep(0.05)
                    with lat_lock:
                        ls = sorted(lat)
                    p99 = ls[min(len(ls) - 1, int(0.99 * len(ls)))] \
                        if ls else None
                    return p99, state

                rate = start_rps
                levels: list[dict] = []
                while rate <= max_rps:
                    p99, state = level(rate)
                    levels.append({"rps": round(rate, 1),
                                   "p99_ms": None if p99 is None
                                   else round(p99, 2),
                                   "slo": state})
                    if state != "ok":
                        flip_rps = rate
                        break
                    knee, knee_p99 = rate, p99
                    rate *= step
                pool.shutdown(wait=False, cancel_futures=True)
                extra["serving_knee_levels"] = levels
                client.close()
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if knee is None and flip_rps is None:
        return  # no level completed: the harness itself failed
    # knee None = even the first level violated: report the floor
    extra["serving_knee_rps"] = round(knee if knee is not None
                                      else start_rps, 1)
    if knee_p99 is not None:
        extra["serving_knee_p99_ms"] = round(knee_p99, 2)
    if flip_rps is not None:
        extra["serving_knee_flip_rps"] = round(flip_rps, 1)
    else:
        # the fleet outran the bench's ceiling without flipping
        extra["serving_knee_saturated"] = True


def _bench_serving_plane(extra: dict, n_files: int = 64,
                         size: int = 64 * 1024,
                         cache_mem: int = 3 * 1024 * 1024,
                         level_s: float = 2.0,
                         n_threads: int = 8) -> None:
    """Cluster hot tier OFF/ON A/B through two filer gateways sharing
    one namespace: the working set (64 x 64 KiB) is ~1.3x ONE filer's
    chunk cache, so with the tier OFF each gateway thrashes its own LRU
    and re-fetches from the volume tier forever, while ON the
    rendezvous ring splits the set so each half fits its home's cache
    and the whole cluster fetches each chunk once.  Reports
    `serving_plane_read_rps_{off,on}` (closed-loop fixed-thread read
    throughput), `serving_plane_volume_fetches_{off,on}` (volume-tier
    GETs each phase issued for the same client load),
    `serving_plane_offload` (off/on fetch ratio — the scarce resource
    at serving scale is the volume tier, and fetch-once semantics is
    what the tier buys), and `hot_tier_hit_ratio` (the ON-phase
    fraction of chunk demands served from the tier).  NOTE the rps pair
    is recorded for honesty, not as the headline: on a one-process
    loopback harness the extra gateway hop costs about what the saved
    loopback volume fetch costs, so wall-clock parity (or a small loss)
    here coexists with a large volume-tier offload — the number that
    moves the knee when the volume tier is disk- or network-bound."""
    import asyncio
    import threading
    import urllib.request

    from seaweedfs_tpu.client import WeedClient
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(120)

    def run_quiet(coro):
        try:
            run(coro)
        except Exception:
            pass

    overrides = {"WEEDTPU_CANARY_INTERVAL": "0",
                 "WEEDTPU_REPAIR_INTERVAL": "3600",
                 "WEEDTPU_SCRUB_MBPS": "0",
                 "WEEDTPU_HOT_SEED_INTERVAL": "0"}
    old_env = {k: os.environ.get(k)
               for k in (*overrides, "WEEDTPU_HOT_TIER")}
    os.environ.update(overrides)
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-plane-") as d:
            master = MasterServer("127.0.0.1", free_port())
            vs_dir = os.path.join(d, "v")
            os.makedirs(vs_dir, exist_ok=True)
            vs = VolumeServer([vs_dir], master.url,
                              port=free_port(), heartbeat_interval=0.2)
            shared = os.path.join(d, "filer-ns")
            started = []
            try:
                run(master.start())
                started.append(master)
                run(vs.start())
                started.append(vs)
                deadline = time.time() + 10
                while time.time() < deadline and not master.topo.nodes:
                    time.sleep(0.05)
                # seed the shared namespace through a bootstrap gateway
                # (uploads do not warm read caches — both phases start
                # cold)
                boot = FilerServer(master.url, port=free_port(),
                                   data_dir=shared)
                run(boot.start())
                # incompressible payload: stored chunks must occupy
                # their nominal size or the working set silently fits
                # one cache and the OFF arm never thrashes
                import random as _random
                payload = _random.Random(0xB10B).randbytes(size)
                paths = [f"/plane/f{i:03d}.bin" for i in range(n_files)]
                for p in paths:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://{boot.url}{p}", data=payload,
                        method="PUT"), timeout=30).read()
                run_quiet(boot.stop())
                master.cluster_members.get("filer", {}).clear()

                def phase(hot: bool) -> tuple[float, float | None, int]:
                    os.environ["WEEDTPU_HOT_TIER"] = "1" if hot else "0"
                    filers = [FilerServer(master.url, port=free_port(),
                                          data_dir=shared,
                                          chunk_cache_mem=cache_mem)
                              for _ in range(2)]
                    for f in filers:
                        run(f.start())
                    dl = time.time() + 10
                    while time.time() < dl and len(
                            master.cluster_members.get("filer", {})) < 2:
                        time.sleep(0.05)
                    for f in filers:
                        run(f._refresh_hot_ring())
                    stop_at = time.time() + level_s
                    counts = [0] * n_threads
                    errors = [0]

                    def worker(k: int) -> None:
                        # uniform random over (gateway, path): every
                        # filer sees the FULL working set (a strided
                        # walk would quietly shard it so each cache
                        # fits its half and the OFF arm never misses)
                        rng = _random.Random(0xCAFE + k)
                        while time.time() < stop_at:
                            url = (f"http://"
                                   f"{filers[rng.randrange(2)].url}"
                                   f"{paths[rng.randrange(n_files)]}")
                            try:
                                with urllib.request.urlopen(
                                        url, timeout=30) as r:
                                    r.read()
                                counts[k] += 1
                            except Exception:
                                errors[0] += 1
                    threads = [threading.Thread(target=worker, args=(k,))
                               for k in range(n_threads)]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join(level_s + 60)
                    ev = {k: sum(f.hot_stats[k] for f in filers)
                          for k in ("hit_local", "route_out", "direct")}
                    for f in filers:
                        run_quiet(f.stop())
                    master.cluster_members.get("filer", {}).clear()
                    rps = sum(counts) / level_s
                    hits = ev["hit_local"] + ev["route_out"]
                    demands = hits + ev["direct"]
                    ratio = round(hits / demands, 4) if demands else None
                    if errors[0]:
                        extra[f"serving_plane_errors_"
                              f"{'on' if hot else 'off'}"] = errors[0]
                    return rps, ratio, ev["direct"]

                rps_off, _, fetches_off = phase(False)
                rps_on, hit_ratio, fetches_on = phase(True)
                extra["serving_plane_read_rps_off"] = round(rps_off, 1)
                extra["serving_plane_read_rps_on"] = round(rps_on, 1)
                extra["serving_plane_volume_fetches_off"] = fetches_off
                extra["serving_plane_volume_fetches_on"] = fetches_on
                if fetches_on > 0:
                    extra["serving_plane_offload"] = round(
                        fetches_off / fetches_on, 2)
                if hit_ratio is not None:
                    extra["hot_tier_hit_ratio"] = hit_ratio
            finally:
                if vs in started:
                    run_quiet(vs.stop())
                if master in started:
                    run_quiet(master.stop())
                loop.call_soon_threadsafe(loop.stop)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_e2e_ceiling(size: int, batch: int, reps: int = 10) -> dict:
    """write_ec_files' shard-file I/O with the GF matmul swapped for the
    cheapest conceivable codec — parity = memcpy of input rows — through
    the SAME machinery the production encode uses: data shards copy out
    of the .dat on the striped writer workers, parity rides the
    countdown-released buffer ring sized exactly like the encoder's, and
    the producer pays every cost any encoder must: one full read of the
    .dat (each unit's rows feed the null codec) and the materialisation
    of every parity byte into a real cycling buffer before the writers
    copy it out again.  An earlier ceiling wrote all parity from one
    L1-hot zeros buffer — unreachable by ANY codec, since real parity is
    0.4x the volume in fresh bytes that must transit DRAM twice (codec
    out, writer in).

    Real-encode and null-codec reps run INTERLEAVED over the same .dat
    and warm shard inodes, and `frac` is the MEDIAN of per-pair
    encode/null ratios: on a shared/ballooned VM the two absolute
    numbers drift by tens of percent minute to minute, so comparing a
    best-of encode against a best-of ceiling measured minutes apart
    reports machine weather, not the codec's distance from its I/O
    bound.  Pairing cancels the common mode.  Returns {ceiling_gbps,
    encode_gbps, frac}: e2e-minus-the-GF-math and how closely the real
    encode tracks it."""
    import mmap as mmap_mod
    from seaweedfs_tpu.storage import aio as _aio
    from seaweedfs_tpu.storage.ec import ec_files, layout
    k, m = layout.DATA_SHARDS, layout.PARITY_SHARDS
    sb = 1024 * 1024
    with tempfile.TemporaryDirectory(prefix="weedtpu-ceil-") as d:
        base = os.path.join(d, "v")
        rng = np.random.default_rng(2)
        with open(base + ".dat", "wb") as f:
            left = size
            while left:
                n2 = min(left, 64 * 1024 * 1024)
                f.write(rng.integers(0, 256, n2, dtype=np.uint8).tobytes())
                left -= n2
        min_step, max_step = ec_files._unit_steps(size, 1 << 40, sb, batch)
        acc = np.empty(max_step, dtype=np.uint8)

        def null_rep(dat_fd: int, view: np.ndarray) -> float:
            fds = [os.open(base + layout.to_ext(i) + ".ceil",
                           os.O_RDWR | os.O_CREAT, 0o644)
                   for i in range(layout.TOTAL_SHARDS)]
            try:
                t0 = time.perf_counter()
                pool = queue.Queue()
                # aligned + registered like the real encoder's ring: the
                # ceiling must ride the same aio engine (O_DIRECT,
                # registered buffers) as the data path — a buffered
                # ceiling under an io_uring data path reports a bound the
                # production writes don't live under
                pbufs = [_aio.aligned_empty((m, max_step))
                         for _ in range(ec_files._parity_ring_size(
                             min_step, max_step))]
                for pb in pbufs:
                    pool.put(pb)
                writers = ec_files._ShardWriterPool(fds, reg_bufs=pbufs)
                sink = ec_files._make_sink(writers, layout.TOTAL_SHARDS,
                                           min_step)
                for row_start, block, col, step, shard_off in \
                        ec_files._iter_units(size, 1 << 40, sb, batch):
                    nz, tail = ec_files._unit_coverage(
                        size, row_start, block, col, step)
                    for j in range(nz):
                        off = row_start + j * block + col
                        n2 = step if j < nz - 1 else tail
                        sink.copy(j, dat_fd, off, shard_off, n2,
                                  src_view=view)
                        # the codec-mandatory read of this row
                        np.bitwise_xor(acc[:n2], view[off:off + n2],
                                       out=acc[:n2])
                    try:
                        pbuf = pool.get_nowait()
                    except queue.Empty:
                        sink.flush()
                        pbuf = pool.get()
                    # null codec: parity row i := input row i % nz
                    for i in range(m):
                        off = row_start + (i % nz) * block + col
                        n2 = min(step, size - off)
                        np.copyto(pbuf[i, :n2], view[off:off + n2])
                    release = ec_files._countdown(
                        m, lambda b=pbuf: pool.put(b))
                    for i in range(m):
                        sink.put(k + i, pbuf[i, :step], shard_off,
                                 release=release)
                    sink.account(step)
                sink.flush()
                writers.close()
                if writers.errors:
                    raise writers.errors[0]
                return time.perf_counter() - t0
            finally:
                for fd in fds:
                    os.close(fd)

        def encode_rep() -> float:
            for i in range(layout.TOTAL_SHARDS):
                f = base + layout.to_ext(i)
                if os.path.exists(f):
                    os.replace(f, f + ".tmp")
            old = os.environ.get("WEEDTPU_EC_CODEC")
            os.environ["WEEDTPU_EC_CODEC"] = "cpp"  # same codec as host_1g
            try:
                t0 = time.perf_counter()
                ec_files.write_ec_files(base, large_block=1 << 40,
                                        small_block=sb, batch_size=batch)
                return time.perf_counter() - t0
            finally:
                if old is None:
                    os.environ.pop("WEEDTPU_EC_CODEC", None)
                else:
                    os.environ["WEEDTPU_EC_CODEC"] = old

        best_null = best_enc = float("inf")
        ratios = []
        with open(base + ".dat", "rb") as datf:
            dat_fd = datf.fileno()
            mm = mmap_mod.mmap(dat_fd, 0, prot=mmap_mod.PROT_READ)
            view = np.frombuffer(mm, dtype=np.uint8)
            try:
                for rep in range(reps):
                    # alternate within-pair order: each rep dirties
                    # ~1.4GiB of page cache whose writeback lands on
                    # whatever runs NEXT, so a fixed null-then-encode
                    # order systematically taxes the encode side
                    if rep % 2 == 0:
                        t_null = null_rep(dat_fd, view)
                        t_enc = encode_rep()
                    else:
                        t_enc = encode_rep()
                        t_null = null_rep(dat_fd, view)
                    if rep == 0:
                        continue  # cold inodes/page cache on both sides
                    best_null = min(best_null, t_null)
                    best_enc = min(best_enc, t_enc)
                    ratios.append(t_null / t_enc)
            finally:
                del view
                mm.close()
    ratios.sort()
    return {"ceiling_gbps": size / 1e9 / best_null,
            "encode_gbps": size / 1e9 / best_enc,
            "frac": ratios[len(ratios) // 2]}


def _bench_perf_obs_overhead(extra: dict, n_needles: int = 64,
                             reads: int = 1600, blocks: int = 6) -> None:
    """Performance-observatory tax on its hottest per-op path: EC needle
    reads through the batched read engine (every read brackets the
    ec_read flow account's local_pread stage CM; a degraded fraction
    adds the reconstruct stage) with WEEDTPU_PERF_OBS=1 vs =0 over the
    same warm volume.  An encode-based A/B was tried first and
    rejected: a 96MB shard-write run swings ±15% pair-to-pair on this
    host (disk-bound), drowning a 3% budget; page-cache reads amortize
    over thousands of ops like the other overhead gates.  Arms run in
    counterbalanced ABBA blocks (off-on-on-off, then on-off-off-on) so
    linear host drift cancels within every block, and each block's
    ratio sums two arms per side.  perf_obs_enabled() caches the env
    ~0.5s; each flip expires the cache directly rather than sleeping.  Median block
    ratio below PERF_OBS_OVERHEAD_TOL (>= 0.97x) fails the run
    (perf_obs_overhead_regression + nonzero exit)."""
    from seaweedfs_tpu.stats import pipeline as _pipeline
    from seaweedfs_tpu.storage import needle as ndl
    from seaweedfs_tpu.storage.ec import ec_files, ec_volume, layout
    from seaweedfs_tpu.storage.volume import Volume
    large, small = 10000, 100
    old = {k: os.environ.get(k)
           for k in ("WEEDTPU_PERF_OBS", "WEEDTPU_EC_CODEC")}
    os.environ["WEEDTPU_EC_CODEC"] = "numpy"
    ratios: list[float] = []
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-pobs-") as d:
            vol = Volume(d, "", 3)
            rng = np.random.default_rng(7)
            blobs: dict[int, bytes] = {}
            for i in range(1, n_needles + 1):
                data = rng.integers(0, 256, int(rng.integers(200, 4000)),
                                    dtype=np.uint8).tobytes()
                vol.append_needle(ndl.Needle(cookie=0x9, id=i, data=data))
                blobs[i] = data
            vol.close()
            base = os.path.join(d, "3")
            ec_files.write_ec_files(base, large_block=large,
                                    small_block=small,
                                    batch_size=small * 10)
            ec_files.write_sorted_ecx(base + ".idx")
            os.remove(base + layout.to_ext(2))  # a degraded slice too
            ev = ec_volume.EcVolume(base, large, small)
            nids = sorted(blobs)

            def rep(obs: str) -> float:
                if os.environ.get("WEEDTPU_PERF_OBS") != obs:
                    os.environ["WEEDTPU_PERF_OBS"] = obs
                    # expire the enabled() cache in place: sleeping out
                    # its 0.5s TTL costs ~8-10s of wall per bench run
                    _pipeline._enabled_cache = (0.0, obs != "0")
                t0 = time.perf_counter()
                for j in range(reads):
                    nid = nids[j % len(nids)]
                    assert ev.read_needle(nid).data == blobs[nid]
                return time.perf_counter() - t0

            _pipeline.reset()
            try:
                rep("1")
                rep("0")  # warm page cache / recon LRU / code paths
                for i in range(blocks):
                    seq = ("0", "1", "1", "0") if i % 2 == 0 \
                        else ("1", "0", "0", "1")
                    t = {"0": 0.0, "1": 0.0}
                    for obs in seq:
                        t[obs] += rep(obs)
                    ratios.append(t["0"] / t["1"])
            finally:
                ev.close()
            # the ON arms must have really booked flow occupancy —
            # otherwise both arms measured the observatory-off path and
            # the gate passes vacuously over a broken plane
            flows = [s for s in _pipeline.jobs_snapshot()
                     if s["kind"] == "ec_read"]
            if not flows or not flows[0]["stages"].get(
                    "local_pread", {}).get("busy_s"):
                raise RuntimeError(
                    "observatory never engaged during the ON arms — "
                    "overhead gate is meaningless")
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not ratios:
        return
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    extra["perf_obs_overhead_ratio"] = round(ratio, 3)
    if ratio < PERF_OBS_OVERHEAD_TOL:
        extra["perf_obs_overhead_regression"] = True
        print(f"bench: REGRESSION — EC reads with the performance "
              f"observatory on run at {ratio:.3f}x the observatory-off "
              f"rate (median of interleaved pairs); the instrumentation "
              f"exceeds its 3% budget. Failing the bench run.",
              file=sys.stderr)


def _bench_pipeline_ratio(size: int, batch: int, reps: int = 5) -> float:
    """pipelined/serial e2e speed as the median of INTERLEAVED pairs over
    the same .dat and warm shard inodes (same rationale as
    _bench_e2e_ceiling: two best-ofs measured minutes apart on a noisy VM
    compare machine weather, not strategies).  >= 1.0 means the pipelined
    machinery is at least as fast as host-serial; the regression gate
    trips below PIPELINE_REGRESSION_TOL."""
    from seaweedfs_tpu.storage.ec import ec_files, layout
    sb = 1024 * 1024
    with tempfile.TemporaryDirectory(prefix="weedtpu-pipe-") as d:
        base = os.path.join(d, "v")
        rng = np.random.default_rng(2)
        with open(base + ".dat", "wb") as f:
            left = size
            while left:
                n2 = min(left, 64 * 1024 * 1024)
                f.write(rng.integers(0, 256, n2, dtype=np.uint8).tobytes())
                left -= n2

        def rep(mode: str) -> float:
            for i in range(layout.TOTAL_SHARDS):
                f = base + layout.to_ext(i)
                if os.path.exists(f):
                    os.replace(f, f + ".tmp")
            old_c = os.environ.get("WEEDTPU_EC_CODEC")
            old_p = os.environ.get("WEEDTPU_EC_PIPELINE")
            os.environ["WEEDTPU_EC_CODEC"] = "cpp"
            os.environ["WEEDTPU_EC_PIPELINE"] = mode
            try:
                t0 = time.perf_counter()
                ec_files.write_ec_files(base, large_block=1 << 40,
                                        small_block=sb, batch_size=batch)
                return time.perf_counter() - t0
            finally:
                for key, old in (("WEEDTPU_EC_CODEC", old_c),
                                 ("WEEDTPU_EC_PIPELINE", old_p)):
                    if old is None:
                        os.environ.pop(key, None)
                    else:
                        os.environ[key] = old

        ratios = []
        for i in range(reps):
            t_serial = rep("serial")
            t_pipe = rep("pipelined")
            if i == 0:
                continue  # cold inodes/page cache
            ratios.append(t_serial / t_pipe)
    ratios.sort()
    return ratios[len(ratios) // 2]


def _bench_rebuild_e2e(size: int, detail: dict | None = None,
                       reps: int = 3) -> float:
    """shard files -> rebuilt missing shards through rebuild_ec_files on the
    host codec: encode once, delete 4 shards (1 data + 3 parity), rebuild,
    best of reps with the rebuilt files recycled as warm .tmp inodes between
    reps (same rationale as _bench_e2e).  GB/s is survivor bytes streamed,
    matching how the reference's RebuildEcFiles walks k survivor files."""
    from seaweedfs_tpu.storage.ec import ec_files, layout
    old = os.environ.get("WEEDTPU_EC_CODEC")
    os.environ["WEEDTPU_EC_CODEC"] = "cpp"
    try:
        with tempfile.TemporaryDirectory(prefix="weedtpu-rbe2e-") as d:
            base = os.path.join(d, "v")
            rng = np.random.default_rng(3)
            rng.integers(0, 256, size, dtype=np.uint8).tofile(base + ".dat")
            ec_files.write_ec_files(base, large_block=1 << 40,
                                    small_block=1024 * 1024,
                                    batch_size=16 * 1024 * 1024)
            kill = [3, 11, 12, 13]
            shard_size = os.path.getsize(base + layout.to_ext(0))
            streamed = shard_size * layout.DATA_SHARDS
            best = float("inf")
            best_stats: dict = {}
            for _ in range(reps):
                for i in kill:
                    f = base + layout.to_ext(i)
                    if os.path.exists(f):
                        os.replace(f, f + ".tmp")
                stats: dict = {}
                t0 = time.perf_counter()
                rebuilt = ec_files.rebuild_ec_files(
                    base, batch_size=8 * 1024 * 1024, stats=stats)
                el = time.perf_counter() - t0
                assert sorted(rebuilt) == kill, rebuilt
                if el < best:
                    best, best_stats = el, stats
        if detail is not None:
            for k_ in ("reconstruct_s", "write_s", "mode"):
                if k_ in best_stats:
                    detail[k_] = (round(best_stats[k_], 4)
                                  if isinstance(best_stats[k_], float)
                                  else best_stats[k_])
        return streamed / 1e9 / best
    finally:
        if old is None:
            os.environ.pop("WEEDTPU_EC_CODEC", None)
        else:
            os.environ["WEEDTPU_EC_CODEC"] = old


if __name__ == "__main__":
    sys.exit(main())
