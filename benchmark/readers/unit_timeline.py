"""Reader `unit_timeline`: each unit of a bulk call laid beside the device
program that ran it, from the profiler's trace alone.

The program's `codec.*` annotations (seaweedfs_tpu/ops/dispatch.py, one
`h2d`, `dispatch`, `device_wait` and `d2h_copy` a unit) are on the device
planes' clock, and the device's `XLA Modules` line has one event a unit
program (one a chip a batch on four chips).  Inside one `job.*` interval
(seaweedfs_tpu/stats/pipeline.py `PipelineJob`, PR 36) the n-th of each is
unit n, so with no sync in the program a unit has these instants:

  h2d_start, h2d_end      the calling thread's put of the unit's rows
  dispatch_start, _end    the enqueue
  program_start, _end     the unit's program on the device
  wait_start              the drain began to wait for it
  copy_start, copy_end    the drain's copy back (wait_end = copy_start)

and the reader gives, as the median over the slice's units
(`params["value"]`):

  upload_ms      program_start - h2d_start: put, transfer and queueing as
                 the chip saw them (a latency: H2D is no device op in the
                 trace, so this is no PCIe rate)
  return_ms      copy_end - program_end: the result's way back
  device_gap_ms  program_start of unit n+1 - program_end of unit n, inside
                 one job, on one device

On four chips a batch's one `h2d` / `dispatch` / `device_wait` belong to
each chip's program of that batch, and its `d2h_copy` annotations, one an
occupied slot, to the chips in the order of their planes (slot i is on
device i of the mesh).  Where the counts do not agree (as many programs a
device as dispatches a job, as many copies as programs) the reader gives
nothing rather than a pairing it cannot stand behind.

The two clocks are checked against each other before they are subtracted:
a program cannot start before the `codec.dispatch` that enqueued it began,
nor end after the `codec.device_wait` that waited for it ended.  On one
chip the trace's planes agree to a millisecond.  On the four-chip host a
trace's device planes were seen 85 ms behind its host plane (PERF.md,
PR 36): there the device planes are shifted forward by the least that puts
every program after its enqueue (`clock_shift_s` in the file, with
`clock_shift_room_s`, how much further they could go before a program
would end after its wait: the shift is known that closely), and a trace
in which no shift does both gives nothing.  Every unit's instants go to
`benchmark/out/<cell>/unit_timeline.json`.

The slice's `.xplane.pb` is found and loaded as `stage_gaps` does.  A
trace with no `job.*` annotation (a program from before PR 36) gives
None, and the metric is left out."""

import re

import harness
import run
import stats

NAMES = ("codec.h2d", "codec.dispatch", "codec.device_wait",
         "codec.d2h_copy")
gaps = run.load_module("readers", "call_gaps")  # jobs, planes, the out file


def timeline(planes: list[dict], kernels_table: dict,
             modules_line: str) -> dict | None:
    """-> {units, medians, jobs} of one trace, or None (module docstring).
    A job whose counts disagree is listed under `unpaired` with what was
    counted, and one such job makes the whole reading None."""
    dev_re = re.compile(kernels_table["device_plane"])
    line_re = re.compile(modules_line)
    jobs = sorted((s, e, name) for name, spans in
                  gaps.host_events(planes, dev_re, gaps.JOB_PREFIX).items()
                  for s, e in spans)
    if not jobs:
        return None
    seam = gaps.host_events(planes, dev_re, NAMES)
    programs = {p["name"]: sorted(
        (s, e, n) for ln in p["lines"] if line_re.match(ln["name"])
        for n, s, e in ln["events"])
        for p in planes if dev_re.match(p["name"])}
    # /device:TPU:10 after /device:TPU:9
    order = sorted(programs, key=lambda n: [
        int(x) if x.isdigit() else x for x in re.split(r"(\d+)", n)])
    units, unpaired = [], []
    for j, (j0, j1, job_name) in enumerate(jobs):
        inside = {name: sorted(iv for iv in seam.get(name, [])
                               if j0 <= iv[0] <= j1) for name in NAMES}
        ran = {d: [m for m in programs[d] if j0 <= m[0] <= j1]
               for d in order}
        ran = {d: ms for d, ms in ran.items() if ms}
        n = len(inside["codec.dispatch"])
        counts = {"job": job_name, "t0": j0, "t1": j1,
                  **{name: len(iv) for name, iv in inside.items()},
                  "programs": {d: len(ms) for d, ms in ran.items()}}
        if not n or not ran or \
                any(len(ms) != n for ms in ran.values()) or \
                len(inside["codec.h2d"]) != n or \
                len(inside["codec.device_wait"]) != n or \
                len(inside["codec.d2h_copy"]) != n * len(ran):
            unpaired.append(counts)
            continue
        for c, (d, ms) in enumerate(ran.items()):
            for u in range(n):
                copy = inside["codec.d2h_copy"][u * len(ran) + c]
                units.append({
                    "job": j, "unit": u, "device": d, "program": ms[u][2],
                    "h2d_start": inside["codec.h2d"][u][0],
                    "h2d_end": inside["codec.h2d"][u][1],
                    "dispatch_start": inside["codec.dispatch"][u][0],
                    "dispatch_end": inside["codec.dispatch"][u][1],
                    "program_start": ms[u][0], "program_end": ms[u][1],
                    "wait_start": inside["codec.device_wait"][u][0],
                    "copy_start": copy[0], "copy_end": copy[1]})
    if unpaired or not units:
        return {"units": [], "unpaired": unpaired, "medians": None}
    # the device planes' clock against the host plane's (module docstring)
    least = max(u["dispatch_start"] - u["program_start"] for u in units)
    most = min(u["copy_start"] - u["program_end"] for u in units)
    shift = max(0.0, least)
    if shift > most:
        return {"units": [], "unpaired": [], "medians": None,
                "clock_shift_s": [least, most]}
    for u in units:
        u["program_start"] += shift
        u["program_end"] += shift
    upload = [1e3 * (u["program_start"] - u["h2d_start"]) for u in units]
    back = [1e3 * (u["copy_end"] - u["program_end"]) for u in units]
    gap = [1e3 * (b["program_start"] - a["program_end"])
           for a, b in zip(units, units[1:])
           if (a["job"], a["device"], a["unit"] + 1) ==
           (b["job"], b["device"], b["unit"])]
    return {"units": units, "unpaired": [], "clock_shift_s": shift,
            "clock_shift_room_s": most - shift,
            "jobs": [{"job": name, "t0": s, "t1": e} for s, e, name in jobs],
            "medians": {"upload_ms": stats.median(upload),
                        "return_ms": stats.median(back),
                        "device_gap_ms": stats.median(gap) if gap else None}}


def read(ev: dict, params: dict):
    found = gaps.slice_planes(ev)
    if found is None:
        return None
    cell, path, planes = found
    result = timeline(planes, harness.kernel_table(), params["modules_line"])
    if result is None:
        return None
    gaps.leave(cell, "unit_timeline.json", result, path)
    return result["medians"] and result["medians"][params["value"]]
