"""Reader `stage_gaps`: which of the program's stages were open while the
device sat idle, from the `ec.*` / `codec.*` annotations the program leaves
on the host plane of the profiler's trace (seaweedfs_tpu/stats/pipeline.py
`Stage`: one annotation an enter/exit of a stage, on the device planes'
clock, only while a profiler session is open).

The device's idle gaps are taken exactly as trace_reduce.reduce takes them:
the parts of the slice that no event on the device plane's `XLA Ops` and
`Async XLA Ops` lines covers.  For each stage name the table gives the idle
seconds during which an annotation of that name was open on any thread.
Shares may add to more than 100 %: the reader, the dispatcher and the drain
run side by side, so several stages are open at once, and saying so is the
point.  The metric is the part no stage covers:

  100 * idle seconds under no `ec.*` / `codec.*` annotation / idle seconds

as the mean over the devices of the trace.  The whole table goes to
`benchmark/out/<cell>/stage_gaps.json`.

The reader loads the slice's `.xplane.pb` itself: the newest under
`benchmark/work/<cell>/trace` (a cell wipes its own work directory when it
starts, and nothing is removed before the readers ran).  A trace with no
device plane (a rehearsal) or with no annotation in it (a program from
before the stage primitive) gives None."""

import glob
import json
import os
import re

import harness
import stats
import trace_reduce

PREFIXES = ("ec.", "codec.")


def table(planes: list[dict], kernels_table: dict) -> dict | None:
    """-> the idle-by-stage table of one trace, or None (module docstring)."""
    dev_re = re.compile(kernels_table["device_plane"])
    op_res = [re.compile(x) for x in kernels_table["op_lines"]]
    everything = [(s, e) for p in planes for ln in p["lines"]
                  for _n, s, e in ln["events"]]
    stages: dict[str, list] = {}
    for p in planes:
        if dev_re.match(p["name"]):
            continue
        for ln in p["lines"]:
            for n, s, e in ln["events"]:
                if n.startswith(PREFIXES):
                    stages.setdefault(n, []).append((s, e))
    if not everything or not stages:
        return None
    t0 = min(s for s, _ in everything)
    t1 = max(e for _, e in everything)
    any_stage = [iv for spans in stages.values() for iv in spans]

    def idle_outside(ops: list, spans: list) -> float:
        return sum(b - a for a, b in stats.gaps(ops + spans, t0, t1))

    devices = []
    for p in planes:
        if not dev_re.match(p["name"]):
            continue
        ops = [(s, e) for ln in p["lines"]
               if any(r.match(ln["name"]) for r in op_res)
               for _n, s, e in ln["events"]]
        idle = idle_outside(ops, [])
        devices.append({
            "plane": p["name"], "idle_s": idle,
            "unattributed_s": idle_outside(ops, any_stage),
            "by_stage_s": {name: idle - idle_outside(ops, spans)
                           for name, spans in sorted(stages.items())}})
    devices = [d for d in devices if d["idle_s"] > 0]
    if not devices:
        return None
    n = len(devices)
    return {
        "window_s": t1 - t0, "devices": devices,
        "annotations": {name: len(spans)
                        for name, spans in sorted(stages.items())},
        "idle_s": sum(d["idle_s"] for d in devices) / n,
        "unattributed_share": sum(100.0 * d["unattributed_s"] / d["idle_s"]
                                  for d in devices) / n,
        "by_stage_share": {
            name: sum(100.0 * d["by_stage_s"][name] / d["idle_s"]
                      for d in devices) / n
            for name in sorted(stages)}}


def newest_trace() -> tuple[str, str] | None:
    """-> (cell, path) of the newest .xplane.pb under any cell's trace
    directory: the running cell's, whose slice has just closed."""
    found = glob.glob(os.path.join(harness.WORK_DIR, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    return os.path.relpath(path, harness.WORK_DIR).split(os.sep)[0], path


def read(ev: dict, params: dict):
    sl = ev.get("slice")
    found = newest_trace()
    # the trace run.py reduced for this slice, and no other
    if not sl or not found or \
            os.path.getsize(found[1]) != sl.get("xplane_bytes"):
        return None
    cell, path = found
    result = table(trace_reduce.load_planes(path), harness.kernel_table())
    if result is None:
        return None
    out_dir = os.path.join(harness.OUT_DIR, cell)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "stage_gaps.json"), "w") as f:
        json.dump(dict(result, trace=os.path.relpath(path, harness.ROOT)),
                  f, indent=1)
    return result["unattributed_share"]
