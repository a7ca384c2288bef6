#!/usr/bin/env python3
"""serve.py — the benchmark's launcher of the server child.

The only process of a run that touches JAX.  It calls the program's normal
entry point, `seaweedfs_tpu.__main__.main([...])`, with the configuration's
argv, in the main thread.  Beside it one control thread sleeps in a read on
standard input; the parent (`run.py`) writes one command a line and reads
one JSON reply a line from the file descriptor given as `--reply-fd`:

  trace_start <dir>   open jax.profiler on <dir> (Python tracer off, so the
                      trace holds the device planes and the runtime's own
                      host events and stays small)
  trace_stop          close it; the .xplane.pb is on disk when this returns
  device              what JAX reports: platform, kind, count, and the peak
                      bytes in use on the fullest device

With `--trace 0` no trace command is ever sent: the thread wakes once, for
`device`, after the measured window has closed.  It exists in both kinds of
run because `memory_peak_bytes` can only be read inside the process that
holds the chip.
"""

from __future__ import annotations

import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _control(reply_fd: int) -> None:
    out = os.fdopen(reply_fd, "w", buffering=1)
    for line in sys.stdin:
        cmd, _, arg = line.strip().partition(" ")
        try:
            if cmd == "trace_start":
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(arg, profiler_options=opts)
                reply = {"ok": True}
            elif cmd == "trace_stop":
                import jax
                jax.profiler.stop_trace()
                reply = {"ok": True}
            elif cmd == "device":
                reply = {"ok": True, "device": _device()}
            else:
                reply = {"ok": False, "error": f"unknown command {cmd!r}"}
        except Exception as e:  # the parent decides what a failure means
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        out.write(json.dumps(reply) + "\n")


def main() -> int:
    argv = sys.argv[1:]
    if len(argv) < 4 or argv[0] != "--reply-fd" or argv[2] != "--":
        sys.exit("usage: serve.py --reply-fd N -- <seaweedfs_tpu argv>")
    sys.path.insert(0, ROOT)
    threading.Thread(target=_control, args=(int(argv[1]),),
                     name="bench-control", daemon=True).start()
    from seaweedfs_tpu.__main__ import main as weed_main
    return weed_main(argv[3:])


if __name__ == "__main__":
    sys.exit(main())
