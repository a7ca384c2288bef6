"""Reader `call_gaps`: the device's idle seconds inside the program's bulk
calls, and how much of them no stage covers.

Since PR 36 a bulk job (an encode, a rebuild, a fleet conversion) leaves an
annotation `job.<span>` on the host plane of the profiler's trace, from
the first file it opens to its last rename
(seaweedfs_tpu/stats/pipeline.py `PipelineJob`), round the `ec.*` /
`codec.*` annotations of its stages.  The traced slice holds a few calls
and what lies between them (the driver's untimed steps: a delete of the
last call's files, a check of a rebuilt file), so a share of the whole
slice says little about a call.  This reader cuts the slice to the calls:

  idle_share      100 * device idle seconds inside the union of the
                  slice's `job.*` intervals / that union's length
  unstaged_share  100 * those idle seconds during which no `ec.*` /
                  `codec.*` annotation was open on any thread / those idle
                  seconds

each the mean over the devices of the trace; `params["value"]` picks one.
Idle is taken as `stage_gaps` and `trace_reduce.reduce` take it: the parts
no event on the device plane's `XLA Ops` and `Async XLA Ops` lines covers.
The table by stage inside the calls, and the slice's idle seconds outside
any call, go to `benchmark/out/<cell>/call_gaps.json`.

The slice's `.xplane.pb` is found and loaded as `stage_gaps` does.  A
trace with no device plane (a rehearsal) or no `job.*` annotation (a
program from before PR 36) gives None, and the metric is left out."""

import json
import os
import re

import harness
import run
import stats
import trace_reduce

JOB_PREFIX = "job."
STAGE_PREFIXES = ("ec.", "codec.")
stage_gaps = run.load_module("readers", "stage_gaps")  # its trace-finding rule


def merged(intervals: list) -> list:
    """The union of (start, end) intervals as disjoint ones, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def host_events(planes: list[dict], dev_re, prefixes) -> dict[str, list]:
    """{name: [(start, end)]} of the host planes' events under `prefixes`."""
    found: dict[str, list] = {}
    for p in planes:
        if dev_re.match(p["name"]):
            continue
        for ln in p["lines"]:
            for n, s, e in ln["events"]:
                if n.startswith(prefixes):
                    found.setdefault(n, []).append((s, e))
    return found


def table(planes: list[dict], kernels_table: dict) -> dict | None:
    """-> the idle-in-call table of one trace, or None (module docstring)."""
    dev_re = re.compile(kernels_table["device_plane"])
    op_res = [re.compile(x) for x in kernels_table["op_lines"]]
    everything = [(s, e) for p in planes for ln in p["lines"]
                  for _n, s, e in ln["events"]]
    jobs = host_events(planes, dev_re, JOB_PREFIX)
    if not everything or not jobs:
        return None
    t0 = min(s for s, _ in everything)
    t1 = max(e for _, e in everything)
    calls = merged([iv for spans in jobs.values() for iv in spans])
    in_call_s = sum(e - s for s, e in calls)
    stages = host_events(planes, dev_re, STAGE_PREFIXES)
    any_stage = [iv for spans in stages.values() for iv in spans]

    def idle_in_calls(ops: list, spans: list) -> float:
        return sum(b - a for s, e in calls
                   for a, b in stats.gaps(ops + spans, s, e))

    devices = []
    for p in planes:
        if not dev_re.match(p["name"]):
            continue
        ops = [(s, e) for ln in p["lines"]
               if any(r.match(ln["name"]) for r in op_res)
               for _n, s, e in ln["events"]]
        idle = idle_in_calls(ops, [])
        slice_idle = sum(b - a for a, b in stats.gaps(ops, t0, t1))
        devices.append({
            "plane": p["name"], "idle_in_call_s": idle,
            "idle_outside_calls_s": slice_idle - idle,
            "unstaged_s": idle_in_calls(ops, any_stage),
            "by_stage_s": {name: idle - idle_in_calls(ops, spans)
                           for name, spans in sorted(stages.items())}})
    devices = [d for d in devices if d["idle_in_call_s"] > 0]
    if not devices or in_call_s <= 0:
        return None
    n = len(devices)

    def mean(f) -> float:
        return sum(f(d) for d in devices) / n

    return {
        "window_s": t1 - t0, "calls": calls, "in_call_s": in_call_s,
        "jobs": {name: len(spans) for name, spans in sorted(jobs.items())},
        "devices": devices,
        "idle_in_call_s": mean(lambda d: d["idle_in_call_s"]),
        "idle_outside_calls_s": mean(lambda d: d["idle_outside_calls_s"]),
        "idle_share": mean(lambda d: 100.0 * d["idle_in_call_s"] / in_call_s),
        "unstaged_share": mean(lambda d: 100.0 * d["unstaged_s"] /
                               d["idle_in_call_s"]),
        "by_stage_share": {
            name: mean(lambda d: 100.0 * d["by_stage_s"][name] /
                       d["idle_in_call_s"])
            for name in sorted(stages)}}


def slice_planes(ev: dict) -> tuple | None:
    """-> (cell, path, planes) of the trace run.py reduced for this slice,
    loaded once a run (`ev` is one dict for all of a run's readers)."""
    sl = ev.get("slice")
    found = stage_gaps.newest_trace()
    if not sl or not found or \
            os.path.getsize(found[1]) != sl.get("xplane_bytes"):
        return None
    cell, path = found
    cache = ev.setdefault("_planes", {})
    if path not in cache:
        cache[path] = trace_reduce.load_planes(path)
    return cell, path, cache[path]


def leave(cell: str, name: str, result: dict, path: str) -> None:
    out_dir = os.path.join(harness.OUT_DIR, cell)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(dict(result, trace=os.path.relpath(path, harness.ROOT)),
                  f, indent=1)


def read(ev: dict, params: dict):
    found = slice_planes(ev)
    if found is None:
        return None
    cell, path, planes = found
    result = table(planes, harness.kernel_table())
    if result is None:
        return None
    leave(cell, "call_gaps.json", result, path)
    return result[params["value"]]
