"""seaweedfs_tpu — a TPU-native distributed object/file store.

Capability surface of SeaweedFS (master + volume servers with O(1)-seek needle
storage, replication, erasure coding, filer metadata layer, S3 gateway, admin
shell), re-designed TPU-first: the erasure-coding data plane runs as batched
GF(2^8) bit-sliced matmuls on the TPU MXU (JAX/XLA/Pallas), scaled over device
meshes with `shard_map` + XLA collectives.

Package layout:
  ops/       GF(2^8) field math and the TPU codec kernels (XLA + Pallas)
  models/    erasure-code "model families": RS (Vandermonde/Cauchy), XOR, LRC
  parallel/  device-mesh sharded encode/rebuild, shard-placement all_to_all
  storage/   needle/volume on-disk engine, EC file layout (reference-compatible)
  topology/  cluster metadata: DC/rack/node tree, volume layout, growth
  server/    master + volume + filer servers (HTTP data path, gRPC-style control)
  filer/     metadata layer: entries, chunking, stores
  shell/     admin shell commands (ec.encode / ec.rebuild / ec.balance ...)
  utils/     config, logging, metrics
"""

import os as _os

__version__ = "0.1.0"

# JAX's persistent compilation cache.  JAX reads the variable when it is
# first imported, and the directory is part of every cache key, so the
# place is decided here — the one module every entry point (server,
# shell, chip_smoke.py) imports before any `import jax`.  An
# operator's JAX_COMPILATION_CACHE_DIR wins; otherwise one fixed,
# git-ignored directory at the root of the checkout.
COMPILE_CACHE_DIR = _os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), ".jax_cache"))
