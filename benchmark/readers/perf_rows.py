"""Reader `perf_rows`: exact counts from /perf -> roofline.rows
(stats/profile.KERNELS, fed by ops/dispatch.py), as the difference between
the snapshots taken at the window's two edges, per byte the window's
operations processed.

  what = bytes_per_byte   (h2d gbytes + d2h gbytes) * 1e9 / bytes
  what = calls_per_gb     device calls / (bytes / 1e9)

Only counts are read: the rows' seconds and ceiling fractions are not
(ISSUE 23's inventory)."""

from harness import perf_moved_bytes


def _calls(perf: dict) -> int:
    calls: dict[str, int] = {}
    for row in perf["roofline"]["rows"]:
        if row["backend"] == "device":
            # every resource row of a kernel repeats the kernel's calls
            calls[row["kernel"]] = max(calls.get(row["kernel"], 0),
                                       row["calls"])
    return sum(calls.values())


def read(ev: dict, params: dict):
    w = ev["window"]
    nbytes = sum(o["bytes"] for o in ev["ops"])
    if not nbytes or not w.get("perf0") or not w.get("perf1"):
        return None
    if params["what"] == "bytes_per_byte":
        return (perf_moved_bytes(w["perf1"]) -
                perf_moved_bytes(w["perf0"])) / nbytes
    if params["what"] == "calls_per_gb":
        return (_calls(w["perf1"]) - _calls(w["perf0"])) / (nbytes / 1e9)
    raise ValueError(f"perf_rows: unknown `what` {params['what']!r}")
