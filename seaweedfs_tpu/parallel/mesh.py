"""Device-mesh parallel erasure coding.

SeaweedFS scales EC by spreading the 14 shard *files* of each volume across
volume servers (weed/shell/command_ec_encode.go:164-270 spreadEcShards +
balancedEcDistribution). The TPU-native analogue has three axes:

- **column parallelism** ("sequence parallel" of this system): the byte
  columns of one stripe matrix [k, n] shard over devices; parity is
  column-local so encode needs NO collectives — each chip crunches its slice.
- **unit parallelism** (the fleet-encode shape): a batch of independent
  [k, B] column units — interleaved from many volumes by the conversion
  pipeline (ops/fleet_convert.py) — shards over devices on the unit axis.
  Parity is unit-local, so this too needs NO collectives, and unlike column
  sharding there is no per-chip tile-width loss: every chip runs the fused
  kernel at its preferred tile on whole units.  `FleetUnitEncoder` keeps
  in/out shardings matched call-to-call so device-resident outputs never
  reshard between unit batches.
- **volume/shard placement** ("data parallel" + all-to-all): a batch of
  volumes [V, k, n] shards over devices on V; after local encode, one
  `all_to_all` over ICI re-distributes so device d holds shard-group d of
  *every* volume — the shard-spread step of ec.encode, but riding ICI
  instead of 14 gRPC copies.

Per-device compute dispatches through ONE body seam (`_ApplyKernel`):
the fused Pallas kernel on real TPU chips (ops/pallas_gf), the XLA
bit-sliced matmul everywhere else (CPU test meshes).  Mesh throughput
against the single-chip kernel: not measured on current code.

Everything is `shard_map` over a `jax.sharding.Mesh`, so it runs identically
on a real multi-chip slice and on the virtual CPU mesh used in tests.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.ops import codec_base, gf, gfmat_jax


def _book_h2d(nbytes: float, secs: float) -> None:
    """Book a place_columns() H2D into the kernel profile: the
    column-placed path bypasses ops/dispatch, whose `h2d` stage books
    every other put (FleetUnitEncoder.place among them)."""
    from seaweedfs_tpu.stats.profile import KERNELS
    KERNELS.record("encode_parity", "device", calls=0,
                   h2d_s=secs, h2d_bytes=nbytes)


def make_mesh(n_devices: int | None = None,
              axis_names: tuple[str, ...] = ("data",),
              shape: tuple[int, ...] | None = None) -> Mesh:
    """Build a Mesh over the first n_devices (default: all devices, or
    prod(shape) when an explicit shape is given)."""
    devs = jax.devices()
    if n_devices is None and shape is not None:
        n_devices = int(np.prod(shape))
    if n_devices is not None:
        devs = devs[:n_devices]
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names)


def resolve_kernel(kernel: str = "auto") -> str:
    """Per-device compute body: the fused Pallas kernel only on real TPU
    chips (under the CPU interpreter it would benchmark the emulator);
    the XLA bit-sliced path — byte-identical by construction — elsewhere."""
    if kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return kernel


class _ApplyKernel:
    """The per-device GF(2^8) matrix-apply seam of the mesh encoders.

    `lift(C)` pre-lifts a GF matrix to the bit-matrix layout its body
    expects (bit-major for XLA, plane-major + sublane-padded for Pallas);
    `body(bm, x2)` / `batch_body(bm, x3)` apply it to a local [k, n] /
    [U, k, n] block inside shard_map.  Both bodies are un-jitted — they
    inline into the enclosing jit(shard_map) — and both tolerate
    non-tile-aligned column counts (the Pallas body pads internally).
    `tile` is the widest the Pallas body will use; the tile of a matrix
    follows from the matrix (`matrix_tile`, as `PallasGFMatrix` has it:
    131072 on a TPU for every RS and LRC matrix, 8192 for PM-MSR's
    [72, 72])."""

    def __init__(self, kernel: str = "auto", tile: int | None = None):
        self.kind = resolve_kernel(kernel)
        if self.kind == "pallas":
            from seaweedfs_tpu.ops import pallas_gf
            self._pg = pallas_gf
            self.tile = pallas_gf.resolved_tile(tile)
        else:
            self._pg = None
            self.tile = 0

    def lift(self, C: np.ndarray) -> jax.Array:
        if self._pg is not None:
            kpad = self._kpad(C.shape[1])
            return jnp.asarray(
                self._pg.gf_matrix_to_bitmatrix_planemajor(C, kpad),
                dtype=jnp.int8)
        return jnp.asarray(gf.gf_matrix_to_bitmatrix(C), dtype=jnp.int8)

    def _kpad(self, k: int) -> int:
        pp = self._pg.PLANE_PAD
        return max(pp, -(-k // pp) * pp)

    def matrix_tile(self, m: int, k: int) -> int:
        """The tile of an [m, k] matrix's Pallas body (0: the XLA body
        has none)."""
        if self._pg is None:
            return 0
        return self._pg.matrix_tile(m, self._kpad(k), self.tile)

    def body(self, bm: jax.Array, x: jax.Array) -> jax.Array:
        if self._pg is None:
            return gfmat_jax.bitsliced_apply_body(bm, x)
        k, n = x.shape
        m = bm.shape[0] // 8
        tile = self.matrix_tile(m, k)
        pad = (-n) % tile
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        out = self._pg._gf_apply(bm, x, k, m, self._kpad(k), tile, False)
        return out[:, :n] if pad else out

    def batch_body(self, bm: jax.Array, x: jax.Array) -> jax.Array:
        if self._pg is None:
            return gfmat_jax.bitsliced_apply_batch_body(bm, x)
        U, k, n = x.shape
        m = bm.shape[0] // 8
        tile = self.matrix_tile(m, k)
        pad = (-n) % tile
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
        out = self._pg._gf_apply_batch(bm, x, k, m, self._kpad(k), tile,
                                       False)
        return out[:, :, :n] if pad else out


class ShardedRSEncoder:
    """RS(k, m) encode/rebuild over a device mesh.

    `col_axis` shards byte columns; optional `vol_axis` shards a leading
    volume-batch dimension for `encode_batch_place`. The jitted shard_map
    callables are built once here — per-call construction would make jax
    retrace and XLA recompile on every stripe.
    """

    def __init__(self, code, mesh: Mesh, col_axis: str = "data",
                 vol_axis: str | None = None, kernel: str = "auto",
                 tile: int | None = None):
        self.code = code
        self.k, self.m, self.n_shards = code.k, code.m, code.n
        self.mesh = mesh
        self.col_axis = col_axis
        self.vol_axis = vol_axis
        self.kernel = _ApplyKernel(kernel, tile)
        self.tile = self.kernel.matrix_tile(self.m, self.k)  # for /perf
        self.parity_bits = self.kernel.lift(code.parity_matrix)

        apply_body = self.kernel.body

        self._encode = jax.jit(shard_map(
            lambda bm, x: jnp.concatenate([x, apply_body(bm, x)], axis=0),
            mesh=mesh, in_specs=(P(), P(None, col_axis)),
            out_specs=P(None, col_axis)))

        # decode shares one compiled fn across survivor patterns: the
        # pattern only changes `bm`, which is a plain array argument.
        self._apply_cols = jax.jit(shard_map(
            apply_body,
            mesh=mesh, in_specs=(P(), P(None, col_axis)),
            out_specs=P(None, col_axis)))

        self._placement_groups: int | None = None
        if vol_axis is not None:
            D = mesh.shape[vol_axis]
            S = -(-self.n_shards // D) * D
            self._placement_groups = S
            pad_rows = S - self.n_shards
            batch_body = self.kernel.batch_body

            def _enc_place(bm, vols):  # vols: [Vl, k, nl]
                # ONE batched kernel launch for all local volumes (the
                # fused Pallas grid on TPU), not a vmap of the XLA body
                par = batch_body(bm, vols)
                shards = jnp.concatenate([vols, par], axis=1)  # [Vl, k+m, nl]
                if D == 1:
                    # degenerate placement (1-way vol axis): every shard
                    # group already lives here, and the row pad +
                    # all_to_all below would be pure whole-batch HBM
                    # copies
                    return shards
                if pad_rows:
                    shards = jnp.pad(shards, ((0, 0), (0, pad_rows), (0, 0)))
                # all_to_all over the volume axis: split shard rows into D
                # groups, gather all volumes -> each device holds one
                # shard-group of every volume
                return jax.lax.all_to_all(
                    shards, vol_axis, split_axis=1, concat_axis=0, tiled=True)

            # no donation: input [V, k, n] and output [V, S_pad, n] differ
            # in shape, so XLA could never alias the donated buffer
            self._encode_place = jax.jit(shard_map(
                _enc_place,
                mesh=mesh, in_specs=(P(), P(vol_axis, None, col_axis)),
                out_specs=P(None, vol_axis, col_axis)))

    # -- column-parallel single volume ---------------------------------

    def encode(self, data: jax.Array) -> jax.Array:
        """[k, n] -> [k+m, n]; columns sharded over `col_axis`, no collectives."""
        return self._encode(self.parity_bits, data)

    def encode_parity(self, data: jax.Array) -> jax.Array:
        """[k, n] -> [m, n] parity, column-sharded; pads n up to a
        device-count multiple internally (shard_map needs even splits)."""
        k, n = data.shape
        D = self.mesh.shape[self.col_axis]
        pad = (-n) % D
        if pad:
            data = jnp.pad(data, ((0, 0), (0, pad)))
        out = self._apply_cols(self.parity_bits, data)
        return out[:, :n] if pad else out

    def place_columns(self, arr) -> jax.Array:
        """H2D an array with columns already sharded over `col_axis`, so
        the first encode doesn't pay a gather+reshard: each device pulls
        only its slice from the host buffer.  This is the in_sharding
        `encode`/`encode_parity` expect — committed here, never reshard."""
        t0 = time.perf_counter()
        out = jax.device_put(
            arr, NamedSharding(self.mesh, P(None, self.col_axis)))
        _book_h2d(getattr(arr, "nbytes", 0), time.perf_counter() - t0)
        return out

    def reconstruct(self, shards: dict[int, jax.Array],
                    wanted: list[int] | None = None) -> dict[int, jax.Array]:
        """Column-parallel rebuild of missing shards from >= k survivors.
        Pads columns to a device-count multiple like encode_parity
        (shard_map needs even splits)."""
        present = sorted(shards)
        if wanted is None:
            wanted = [i for i in range(self.n_shards) if i not in shards]
        if not wanted:
            return {}
        D = self.code.decode_matrix(present, wanted)
        dbits = self.kernel.lift(D)
        stack = jnp.stack([shards[i] for i in present[: self.k]], axis=0)
        n = stack.shape[1]
        ndev = self.mesh.shape[self.col_axis]
        pad = (-n) % ndev
        if pad:
            stack = jnp.pad(stack, ((0, 0), (0, pad)))
        out = self._apply_cols(dbits, stack)
        if pad:
            out = out[:, :n]
        return {w: out[i] for i, w in enumerate(wanted)}

    # -- batched volumes + shard placement over ICI --------------------

    def placement_groups(self) -> int:
        """Shard rows are padded so every device gets an equal group."""
        assert self._placement_groups is not None, "construct with vol_axis="
        return self._placement_groups

    def encode_batch_place(self, volumes: jax.Array) -> jax.Array:
        """[V, k, n] -> [V, S_pad, n] where the shard dimension is sharded
        over `vol_axis`: device d ends up holding shard rows
        [d*S_pad/D, (d+1)*S_pad/D) of EVERY volume (ec.encode's spreadEcShards
        as one ICI all_to_all instead of 14 gRPC file copies)."""
        assert self.vol_axis is not None, "construct with vol_axis= for batching"
        return self._encode_place(self.parity_bits, volumes)


class FleetUnitEncoder:
    """Unit-parallel fleet encode: the mesh shape of the multi-volume
    conversion pipeline (ops/fleet_convert.py).

    A batch of U independent units, interleaved from N volumes, shards
    over the mesh on the unit axis.  Each chip encodes its U/D units
    wholly (parity is unit-local): NO collectives, no cross-chip bytes.
    Both forms run `_ApplyKernel.batch_body` inside one jitted shard_map
    (`jit_batch_body` on a device trace) whose in and out shardings are
    P(unit_axis):

      a unit as the conversion stream selects it in a `.dat`'s map
      (`place_units`, `encode_units_linear`): 1-D pieces that hold, one
      after the other, R stripe rows of k blocks (`codec_base.stacked`'s
      third form).  Each piece is put to the unit's own device from where
      it lies, the per-device arrays become unit-sharded global arrays
      without a copy, the program lays the unit out as [1, k, R * block]
      on its chip and gives the parity back as m 1-D runs of [R * block],
      one contiguous run of each parity shard's file: nothing 2-D
      crosses (TPU v5e: a 2-D uint8 array goes through a host relayout in
      both directions that costs more than the transfer; PERF.md, PR 26
      and PR 31).  Under a sub-packetised code (`alpha` > 1: PM-MSR,
      whose matrix works on alpha sub-rows a file, `MSRFileCodec` over
      this encoder) the same pieces hold rows of k / alpha blocks, the
      program splits each file's R * block bytes into its alpha sub-rows
      ([1, k, R * block / alpha]: sixteen 9 MiB rows become
      [1, 72, 2 MiB] under msr_9_16) and merges the product back into
      m / alpha file runs.  One program per distinct unit shape;
      a [U, k, B] batch (`place`, `encode_parity_batch`): a host array
      from a test or a caller that holds one; parity [U, m, B], its
      device-local blocks streamed by `unit_shards`.

    Nothing is donated: input and output differ in shape, and the
    executable compiled for a v5e carries no input/output alias.
    """

    def __init__(self, code, mesh: Mesh | None = None,
                 unit_axis: str = "unit", kernel: str = "auto",
                 tile: int | None = None):
        if mesh is None:
            mesh = make_mesh(axis_names=(unit_axis,))
        self.code = code
        self.k, self.m = code.k, code.m
        self.mesh = mesh
        self.unit_axis = unit_axis
        self.n_devices = mesh.shape[unit_axis]
        self.kernel = _ApplyKernel(kernel, tile)
        self.tile = self.kernel.matrix_tile(self.m, self.k)  # for /perf
        self.parity_bits = self.kernel.lift(code.parity_matrix)
        self.in_sharding = NamedSharding(mesh, P(unit_axis))
        # the fleet program's name on a device trace: `jit_batch_body`
        self._encode = codec_base.named_jit("batch_body")(shard_map(
            self.kernel.batch_body,
            mesh=mesh, in_specs=(P(), P(unit_axis)),
            out_specs=P(unit_axis)))
        self._encode_linear = codec_base.named_jit(
            "batch_body", static_argnames=("stripes", "alpha"))(self._linear)
        # (device, bytes) -> a zero piece resident on that device: what a
        # slot with no unit is made of
        self._zeros: dict = {}

    def unit_slots(self, min_units: int) -> int:
        """Round a desired in-flight unit count up to an even per-device
        split (shard_map needs one)."""
        D = self.n_devices
        return max(D, -(-min_units // D) * D)

    def _linear(self, bm, units, stripes: int, alpha: int = 1):
        """[slot-in-device][piece] unit-sharded 1-D arrays -> [slot][run]
        unit-sharded parity runs: inside the shard_map each chip sees its
        own units' pieces, stacks them (`codec_base.stacked`; `alpha`
        sub-rows a file), runs the batch kernel on [U/D, k, W / alpha] and
        splits the parity into m / alpha file runs a unit
        (`codec_base.unstacked`)."""
        def body(bm, units):
            # the barrier keeps the compiler from fusing a unit's layout
            # into the step to three dimensions: fused, a two-row unit
            # compiled for a v5e in 44 s into 17 MB of code, apart in 6 s
            # (compiled with no chip, PR 33)
            out = self.kernel.batch_body(bm, jnp.stack(
                [jax.lax.optimization_barrier(
                    codec_base.stacked(pieces, self.k, stripes, alpha))
                 for pieces in units]))
            return tuple(codec_base.unstacked(o, stripes, alpha)
                         for o in out)
        return shard_map(body, mesh=self.mesh,
                         in_specs=(P(), P(self.unit_axis)),
                         out_specs=P(self.unit_axis))(bm, units)

    def _zero_piece(self, device, n: int) -> jax.Array:
        z = self._zeros.get((device, n))
        if z is None:  # a put, not a program: nothing compiles for it
            z = self._zeros[device, n] = jax.device_put(
                np.zeros(n, dtype=np.uint8), device)
        return z

    def place_units(self, units: list) -> tuple:
        """H2D a batch of `unit_slots` slots, each a unit as a sequence of
        1-D host pieces (all units of one batch alike in their pieces'
        lengths) or None: every piece goes to its slot's device as it
        lies, 1-D, and piece i of the d-th devices' j-th slots becomes one
        global array sharded over the mesh, assembled from the per-device
        arrays without a copy.  An empty slot takes zeros that already
        live on its device and costs no PCIe byte.  The runtime may read
        a piece after this returns: the caller keeps the pieces alive and
        unchanged until the parity is materialised."""
        devices = list(self.mesh.devices.flat)
        per = len(units) // len(devices)
        assert per * len(devices) == len(units), (len(units), len(devices))
        lengths = [len(p) for p in next(u for u in units if u is not None)]
        placed = [jax.device_put(list(u), dev) if u is not None
                  else [self._zero_piece(dev, n) for n in lengths]
                  for u, dev in zip(units, (d for d in devices
                                           for _ in range(per)))]
        return tuple(
            tuple(jax.make_array_from_single_device_arrays(
                (len(devices) * n,), self.in_sharding,
                [placed[d * per + j][i] for d in range(len(devices))])
                for i, n in enumerate(lengths))
            for j in range(per))

    def encode_units_linear(self, placed: tuple, stripes: int,
                            alpha: int = 1) -> list:
        """`place_units`' arrays -> per slot, in `place_units`' order, the
        m / alpha parity files' runs of its unit as 1-D arrays on the
        slot's device (un-materialised: the caller's sync point waits and
        copies)."""
        out = self._encode_linear(self.parity_bits, placed, stripes=stripes,
                                  alpha=alpha)
        D = self.n_devices
        # global run -> its D device-local runs, in mesh order
        local = [[sorted(run.addressable_shards,
                         key=lambda s: s.index[0].start or 0)
                  for run in runs] for runs in out]
        return [tuple(sh[d].data for sh in local[j])
                for d in range(D) for j in range(len(out))]

    def place(self, host_units: np.ndarray) -> jax.Array:
        """H2D a [U, k, B] host batch with units sharded over the mesh:
        each device pulls exactly its U/D units from the host buffer, so
        no later reshard (this IS the encode's in_sharding)."""
        assert host_units.shape[0] % self.n_devices == 0, \
            (host_units.shape, self.n_devices)
        # timed and booked by the caller: ops/dispatch's `h2d` stage
        return jax.device_put(host_units, self.in_sharding)

    def encode_parity_batch(self, units: jax.Array) -> jax.Array:
        """[U, k, B] (device-resident, unit-sharded) -> [U, m, B] parity,
        unit-sharded with the SAME spec — device-resident outputs chain
        into whatever consumes them without moving."""
        return self._encode(self.parity_bits, units)

    def unit_shards(self, parity: jax.Array):
        """Yield (u_start, u_stop, np.ndarray) per addressable device
        shard, in unit order: the streaming D2H of the conversion drain.
        Plain single-device arrays yield one chunk."""
        shards = getattr(parity, "addressable_shards", None)
        if not shards:
            yield 0, int(parity.shape[0]), np.asarray(parity)
            return
        for sh in sorted(shards, key=lambda s: s.index[0].start or 0):
            idx = sh.index[0]
            start = idx.start or 0
            data = np.asarray(sh.data)
            yield int(start), int(start) + data.shape[0], data


def shard_columns(mesh: Mesh, arr: jax.Array, axis: str = "data") -> jax.Array:
    """Place [k, n] with columns sharded over `axis`."""
    return jax.device_put(arr, NamedSharding(mesh, P(None, axis)))
