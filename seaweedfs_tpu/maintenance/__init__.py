"""Self-healing maintenance plane.

Three cooperating parts (see README "Self-healing"):

  scrub.py   rate-limited background walker on each volume server: verifies
             needle CRC32C on store volumes and runs batched GF(2^8)
             parity-syndrome checks on EC shards through the same
             ops/dispatch backend seam the encoder uses; corrupt ranges are
             quarantined locally and reported to the master.
  repair.py  the master folds heartbeat shard maps and scrub verdicts into
             a per-volume health ledger and drives the existing rebuild
             machinery automatically (token-bucket limited, per-node
             concurrency caps, exponential backoff, trace spans).
  faults.py  test-only fault injection (WEEDTPU_FAULTS / /admin/faults):
             flip bits, delete shards, delay peers — the heal loop is
             provable end-to-end in tests.
"""
