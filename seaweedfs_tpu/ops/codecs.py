"""Codec registry: tag grammar, per-volume codec identity, backend builds.

A volume's erasure code is no longer a constant — `.vif` metadata, the
heartbeat shard report, repair planning and the autopilot all carry a
codec *tag*, and this module is the one place the tag grammar lives:

    rs_<k>_<m>        Reed-Solomon (MDS), e.g. rs_10_4
    lrc_<k>_<l>_<g>   locally repairable, e.g. lrc_10_2_2
    msr_<k>_<d>       product-matrix regenerating, e.g. msr_9_16

`parse_tag(None)` and any unknown tag resolve to the RS default — old
nodes that never heard of codec tags keep working with no flag-day.

It is also the one place a tag becomes a codec object: `resolve(tag,
kind)` is the only resolution the program has (`ec_files._get_codec`,
`make_codec` and `fleet_convert.fleet_codec` call it and choose
nothing themselves).  Which shell runs a code is a pure function of
the tag, the WEEDTPU_EC_CODEC kind (auto|tpu|jax|cpp|numpy|mesh) and
the platform (`backend_for`), and every shell is built over the tag's
own code object, so the spec's k and m are what the files get: `rs_6_3`
is 6 + 3 on every backend.  `rs` and `lrc` are fixed matrices with a
`decode_matrix`, which is all the fused Pallas kernel
(`pallas_gf.PallasGFMatrix`, generic in (m, k)) and the XLA and native
shells ask for: on a TPU under `auto` / `tpu` both get `PallasRSCodec`.
So does MSR, whose inner code is a fixed matrix over alpha sub-rows a
file ([72, 72] under msr_9_16; the kernel's tile follows from the matrix,
`pallas_gf.matrix_tile`), wrapped in its file codec.  The multi-volume
conversion (`fleet=True`) carries every tag a single volume's encode
carries, on every backend: its stream takes k, m and the sub-rows a file
from the codec (ops/fleet_convert).  A tag whose family or geometry the
chosen backend does not carry raises `CodecUnsupported` before any file
is touched (the volume server answers 400 with the reason): the
column-sharded mesh encoder is Reed-Solomon only, no set has more than
`layout.MAX_TOTAL_SHARDS` files.

Knobs: WEEDTPU_CODEC_DEFAULT (tag or family for untagged volumes),
WEEDTPU_CODEC_LRC ("k,l,g" params behind the bare "lrc" family name),
WEEDTPU_CODEC_MSR ("k,d" likewise).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

DEFAULT_TAG = "rs_10_4"


@dataclasses.dataclass(frozen=True)
class CodecSpec:
    """Identity + geometry of one registered code: everything the
    control plane needs without building a backend."""
    tag: str
    family: str       # rs | lrc | msr
    k: int            # data shard files
    m: int            # parity shard files
    n: int            # total shard files
    alpha: int        # sub-packetization (1 for rs/lrc)
    params: tuple     # family params, e.g. (10, 4) / (10, 2, 2) / (9, 16)

    @property
    def tolerance(self) -> int:
        """Worst-case guaranteed losses: m for MDS codes, the minimum
        distance - 1 for LRC (g + 1 with one local parity per group)."""
        if self.family == "lrc":
            return self.params[2] + 1
        return self.m

    def describe(self) -> dict:
        return {"tag": self.tag, "family": self.family, "k": self.k,
                "m": self.m, "n": self.n, "alpha": self.alpha,
                "tolerance": self.tolerance,
                "params": list(self.params)}


def _lrc_params() -> tuple[int, int, int]:
    raw = os.environ.get("WEEDTPU_CODEC_LRC", "10,2,2")
    try:
        k, l, g = (int(v) for v in raw.split(","))  # noqa: E741
        return k, l, g
    except ValueError:
        return 10, 2, 2


def _msr_params() -> tuple[int, int]:
    raw = os.environ.get("WEEDTPU_CODEC_MSR", "9,16")
    try:
        k, d = (int(v) for v in raw.split(","))
        return k, d
    except ValueError:
        return 9, 16


def _spec_rs(k: int, m: int) -> CodecSpec:
    return CodecSpec(tag=f"rs_{k}_{m}", family="rs", k=k, m=m, n=k + m,
                     alpha=1, params=(k, m))


def _spec_lrc(k: int, l: int, g: int) -> CodecSpec:  # noqa: E741
    return CodecSpec(tag=f"lrc_{k}_{l}_{g}", family="lrc", k=k, m=l + g,
                     n=k + l + g, alpha=1, params=(k, l, g))


def _spec_msr(k: int, d: int) -> CodecSpec:
    n = d + 2
    return CodecSpec(tag=f"msr_{k}_{d}", family="msr", k=k, m=n - k, n=n,
                     alpha=k - 1, params=(k, d))


def parse_tag(tag: str | None) -> CodecSpec:
    """Tag string -> CodecSpec.  None, "", bare family names and any
    unparseable/unknown tag degrade to a usable spec — an old node
    reporting no codec means RS, not an error."""
    if not tag:
        return parse_tag(DEFAULT_TAG)
    tag = str(tag).strip().lower()
    if tag == "rs":
        return _spec_rs(10, 4)
    if tag == "lrc":
        return _spec_lrc(*_lrc_params())
    if tag == "msr":
        return _spec_msr(*_msr_params())
    parts = tag.split("_")
    try:
        if parts[0] == "rs" and len(parts) == 3:
            return _spec_rs(int(parts[1]), int(parts[2]))
        if parts[0] == "lrc" and len(parts) == 4:
            return _spec_lrc(int(parts[1]), int(parts[2]), int(parts[3]))
        if parts[0] == "msr" and len(parts) == 3:
            return _spec_msr(int(parts[1]), int(parts[2]))
    except ValueError:
        pass
    return parse_tag(DEFAULT_TAG)


def default_tag() -> str:
    """The codec newly-encoded volumes get when nothing chose one:
    WEEDTPU_CODEC_DEFAULT accepts a full tag or a bare family name."""
    return parse_tag(os.environ.get("WEEDTPU_CODEC_DEFAULT", DEFAULT_TAG)).tag


def registered() -> list[CodecSpec]:
    """The codec family as configured right now — what `ec.codecs`
    lists."""
    return [_spec_rs(10, 4), _spec_lrc(*_lrc_params()),
            _spec_msr(*_msr_params())]


# ---------------------------------------------------------------------------
# the one resolution: (tag, kind, platform) -> backend -> codec object


class CodecUnsupported(ValueError):
    """The chosen backend does not carry the tag's family or geometry.
    Raised by `resolve` before any shard file or `.tmp` exists; the
    volume server's EC handlers answer 400 with the message."""


class _NumpyShell:
    """Pure-numpy eager shell for non-RS inner codes when no native lib
    and no device backend is wanted (WEEDTPU_EC_CODEC=numpy).  Slowest
    path, test/reference only."""

    host_backend = True

    def __init__(self, code):
        self.code = code
        self.k, self.m, self.n = code.k, code.m, code.n
        self._decode_cache: dict = {}

    def encode_parity(self, data):
        from seaweedfs_tpu.ops import gf
        return gf.gf_matmul(self.code.parity_matrix, np.asarray(data))

    def encode(self, data):
        data = np.asarray(data)
        return np.concatenate([data, self.encode_parity(data)], axis=0)

    def reconstruct(self, shards, wanted=None):
        from seaweedfs_tpu.ops import codec_base, gf
        present = tuple(sorted(shards))
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        basis = codec_base.select_survivors(self.code, present, list(wanted))
        mat = self.code.decode_matrix(list(present), list(wanted))
        stack = np.stack([np.asarray(shards[i]) for i in basis])
        out = gf.gf_matmul(mat, stack)
        return {w: out[i] for i, w in enumerate(wanted)}


def _code_for(spec: CodecSpec):
    """The bare code object (matrix + decode protocol) behind a spec.
    For MSR this is the inner virtual-row code; the file surface is
    MSRFileCodec's."""
    try:
        if spec.family == "lrc":
            from seaweedfs_tpu.ops import lrc
            return lrc.get_code(*spec.params)
        if spec.family == "msr":
            from seaweedfs_tpu.ops import msr
            return msr.get_code(*spec.params)
        from seaweedfs_tpu.models import rs
        return rs.get_code(spec.k, spec.m)
    except (ValueError, AssertionError) as e:
        raise CodecUnsupported(f"{spec.tag}: no such code: {e}") from e


def backend_for(spec: CodecSpec, kind: str, platform: str | None = None,
                devices: int = 1, fleet: bool = False) -> str:
    """Which shell runs `spec` under the WEEDTPU_EC_CODEC `kind`:
    `pallas`, `xla`, `native`, `numpy`, `mesh` or `fleet`.  A pure
    function of its arguments.  `platform` (`tpu`, `native`: a CPU host
    with the C++ library, `cpu`) and `devices` matter under `auto`
    alone, which is the only kind that asks JAX anything (`_platform`);
    `fleet` is the multi-volume conversion's resolution, which under
    `auto` takes the unit-sharded mesh encoder when there is more than
    one device (and under `mesh` too: units shard where columns would)
    and carries every tag, family and geometry the single-volume
    resolution does.  Raises CodecUnsupported where the backend does not
    carry the tag."""
    from seaweedfs_tpu.storage.ec import layout
    if spec.k < 1 or spec.m < 1 or spec.n > layout.MAX_TOTAL_SHARDS:
        raise CodecUnsupported(
            f"{spec.tag}: {spec.k} + {spec.m} shard files; a shard set "
            f"has at least 1 + 1 and at most {layout.MAX_TOTAL_SHARDS}")
    if fleet and (kind in ("mesh", "fleet") or
                  (kind == "auto" and devices > 1)):
        return "fleet"
    if kind in ("cpp", "native"):
        return "native"
    if kind == "numpy":
        return "numpy"
    if kind == "mesh":
        if spec.family != "rs":
            raise CodecUnsupported(
                f"{spec.tag}: the column-sharded mesh encoder rebuilds "
                f"from the first k survivors, which only a Reed-Solomon "
                f"code allows")
        return "mesh"
    if kind == "tpu" or (kind == "auto" and platform == "tpu"):
        # the fused kernel takes any fixed matrix, the MSR inner code's
        # among them (its byte interleave is inside the same program)
        return "pallas"
    if kind == "auto" and platform == "native":
        return "native"
    return "xla"


def _platform(kind: str, fleet: bool) -> tuple[str | None, int]:
    """(platform, device count) as `backend_for` wants them.  Only `auto`
    asks: an explicit kind is honoured before JAX is asked anything, so
    a host-codec process never initialises a backend on a machine whose
    chip belongs to the volume server."""
    if kind != "auto":
        return None, 1
    import jax
    devices = len(jax.devices()) if fleet else 1
    if jax.default_backend() == "tpu":
        return "tpu", devices
    from seaweedfs_tpu import native
    return ("native" if native.available() else "cpu"), devices


def _shell(code, backend: str):
    if backend == "pallas":
        from seaweedfs_tpu.ops import pallas_gf
        return pallas_gf.PallasRSCodec(code)  # the platform's one tile
    if backend == "native":
        from seaweedfs_tpu.ops import native_codec
        return native_codec.NativeRSCodec(code)
    if backend == "mesh":
        # multi-chip column-parallel codec: stripes shard over every
        # attached device; built once, so its shard_maps compile once
        from seaweedfs_tpu.parallel import mesh as pmesh
        return pmesh.ShardedRSEncoder(code, pmesh.make_mesh())
    if backend == "fleet":
        from seaweedfs_tpu.parallel import mesh as pmesh
        return pmesh.FleetUnitEncoder(code)
    if backend == "numpy":
        # a bare RS code is its own numpy reference (ops/dispatch)
        return code if getattr(code, "family", "rs") == "rs" \
            else _NumpyShell(code)
    from seaweedfs_tpu.ops import gfmat_jax
    return gfmat_jax.JaxRSCodec(code)


@functools.lru_cache(maxsize=32)
def _build(tag: str, backend: str):
    """One object per (tag, backend): decode matrices and compiled
    programs are cached on it."""
    spec = parse_tag(tag)
    codec = _shell(_code_for(spec), backend)
    if backend in ("pallas", "xla", "mesh", "fleet"):
        import jax
        if jax.default_backend() != "cpu":  # results are copied back
            from seaweedfs_tpu.ops import dispatch
            dispatch.keep_freed_pages()
    if spec.family == "msr":
        from seaweedfs_tpu.ops import msr
        codec = msr.MSRFileCodec(codec)
    if (codec.k, codec.m) != (spec.k, spec.m):
        raise CodecUnsupported(
            f"{tag}: the {backend} backend built {codec.k} + {codec.m} "
            f"where the tag says {spec.k} + {spec.m}")
    return codec


def resolve(tag: str | None = None, kind: str | None = None,
            fleet: bool = False):
    """The backend codec for a codec tag: the program's one resolution.
    `kind` defaults to the WEEDTPU_EC_CODEC knob (auto: Pallas on a TPU,
    native C++ AVX2 on a CPU host that has it, XLA bit-sliced otherwise;
    `tpu` means the compiled kernel, and on a host with no chip it
    raises).  What each selection resolved to is logged once and rides
    /perf `codecs` (stats/profile.note_codec)."""
    spec = parse_tag(tag)
    kind = kind or os.environ.get("WEEDTPU_EC_CODEC", "auto")
    backend = backend_for(spec, kind, *_platform(kind, fleet), fleet=fleet)
    codec = _build(spec.tag, backend)
    _note(kind, spec.tag, codec)
    return codec


make_codec = resolve


def _note(kind: str, tag: str, codec) -> None:
    """Report one selection.  Keyed so the describe() behind it runs once
    per distinct resolution, not per degraded-read batch; `auto` has
    asked JAX for its backend already, so its block may name the platform
    even when a host codec won."""
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.stats import profile
    profile.note_codec(
        (kind, tag, dispatch.backend_name(codec)),
        lambda: {"asked": kind, "tag": tag,
                 **dispatch.describe(codec, jax_live=kind == "auto")})


def spec_of(codec) -> CodecSpec:
    """Best-effort spec for a live codec object (for metrics labels)."""
    code = getattr(codec, "code", codec)
    tag = getattr(code, "tag", None)
    if tag:
        return parse_tag(tag)
    fam = getattr(code, "family", "rs")
    if fam == "msr":
        return _spec_msr(code.k_nodes, code.d)
    return _spec_rs(getattr(codec, "k", 10), getattr(codec, "m", 4))
