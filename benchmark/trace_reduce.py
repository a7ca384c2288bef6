"""From the profiler's `.xplane.pb` to numbers: per device the union of
the intervals in which an operation ran (busy), the summed durations of
the events kernels.json maps to each kernel, the operations that took
most device time, and the longest idle gaps by what the host was doing.

Reading the file needs jax.profiler.ProfileData and nothing else of JAX;
`run.py` calls this only after the server child has stopped, with
JAX_PLATFORMS=cpu, so the process that measures never holds a chip."""

from __future__ import annotations

import glob
import os
import re

import numpy as np

import stats

TOP = 10
MAX_GAPS = 2000  # idle gaps attributed to a host event, longest first
# an XLA op's event name is its whole HLO line; the breakdown keeps the
# result's name, type and the opcode
HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?.*? ([\w\-]+)\(")


def short_name(name: str) -> str:
    m = HLO.match(name)
    if m:
        name = " ".join(x for x in m.groups() if x)
    return name[:96]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_planes(path: str) -> list[dict]:
    """-> [{name, lines: [{name, events: [(name, start_s, end_s)]}]}]"""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, e.start_ns / 1e9,
                       (e.start_ns + e.duration_ns) / 1e9)
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def summarize(planes: list[dict]) -> list[dict]:
    """What a person looks at first: planes, lines, event counts and the
    most frequent names."""
    out = []
    for p in planes:
        for ln in p["lines"]:
            by: dict[str, list[float]] = {}
            for name, s, e in ln["events"]:
                c = by.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += e - s
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:12]
            out.append({"plane": p["name"], "line": ln["name"],
                        "events": len(ln["events"]),
                        "top": [[n, c, round(s, 6)] for n, (c, s) in top]})
    return out


def reduce(planes: list[dict], table: dict) -> dict:
    dev_re = re.compile(table["device_plane"])
    op_res = [re.compile(x) for x in table["op_lines"]]
    everything = [(s, e) for p in planes for ln in p["lines"]
                  for _n, s, e in ln["events"]]
    if not everything:
        return {"devices": [], "window_s": 0.0}
    t0 = min(s for s, _ in everything)
    t1 = max(e for _, e in everything)
    host = [(n, s, e) for p in planes if not dev_re.match(p["name"])
            for ln in p["lines"] for n, s, e in ln["events"]]
    host_names = [n for n, _s, _e in host]
    host_s = np.array([s for _n, s, _e in host], dtype=np.float64)
    host_e = np.array([e for _n, _s, e in host], dtype=np.float64)
    devices, op_time, gap_time = [], {}, {}
    for p in planes:
        if not dev_re.match(p["name"]):
            continue
        ops = [ev for ln in p["lines"]
               if any(r.match(ln["name"]) for r in op_res)
               for ev in ln["events"]]
        kernel_s: dict[str, float] = {}
        for kernel, spec in table["kernels"].items():
            line_re = re.compile(spec["line"])
            pats = [re.compile(x) for x in spec["patterns"]]
            s = sum(e - b for ln in p["lines"] if line_re.match(ln["name"])
                    for n, b, e in ln["events"]
                    if any(r.search(n) for r in pats))
            if s > 0:
                kernel_s[kernel] = s
        for n, b, e in ops:
            n = short_name(n)
            op_time[n] = op_time.get(n, 0.0) + (e - b)
        spans = [(b, e) for _n, b, e in ops]
        idle = sorted(stats.gaps(spans, t0, t1),
                      key=lambda g: g[0] - g[1])[:MAX_GAPS]
        for a, b in idle:
            name = _host_activity(host_names, host_s, host_e, a, b)
            gap_time[name] = gap_time.get(name, 0.0) + (b - a)
        devices.append({"plane": p["name"], "events": len(ops),
                        "busy_s": stats.union_seconds(spans),
                        "kernel_s": kernel_s})
    n = max(1, len(devices))

    def top(seconds: dict) -> list:
        """The TOP entries, in seconds a device."""
        return [[k, v / n] for k, v in
                sorted(seconds.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"devices": devices, "window_s": t1 - t0,
            "busy_s": sum(d["busy_s"] for d in devices) / n,
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}


def _host_activity(names: list, starts, ends, a: float, b: float) -> str:
    """What the host was doing in the idle gap [a, b]: the shortest host
    event that covers at least half of it (the innermost), else the one
    that covers most."""
    if not names:
        return "(no host event)"
    cover = np.minimum(ends, b) - np.maximum(starts, a)
    half = np.flatnonzero(2 * cover >= b - a)
    if half.size:
        return names[int(half[np.argmin((ends - starts)[half])])]
    i = int(np.argmax(cover))
    return names[i] if cover[i] > 0 else "(no host event)"
