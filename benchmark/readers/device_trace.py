"""Reader `device_trace`: metrics of the kernels and the device, from the
reduction of the profiler's trace of the traced slice (trace_reduce.py).

  what = kernel_s_per_gb  summed device seconds of the events kernels.json
                          maps to the listed `kernels`, per GB the slice's
                          operations processed
  what = roofline         100 * least time / kernel time, where the least
                          time is the kernel's HBM bytes over the device's
                          published HBM bytes a second (peaks.json): HBM
                          bounds this family.  The bytes are the kernel's
                          inputs plus outputs, (k + rows out) x B a
                          dispatch: exactly what the program sent up and
                          brought back for it over the slice (the h2d and
                          d2h byte counts of its /perf rows)
  what = idle_share       100 * (1 - busy / window), busy being the union
                          of the intervals in which any operation ran on
                          a device; mean over the devices of the trace
  what = devices_busy     devices with at least one mapped kernel event"""

from harness import perf_moved_bytes


def read(ev: dict, params: dict):
    sl = ev.get("slice")
    tr = sl and sl.get("trace")
    if not tr or not tr["devices"]:
        return None
    what = params["what"]
    wanted = params.get("kernels") or [params.get("kernel")]
    devs = tr["devices"]
    if what == "idle_share":
        return 100.0 * (1.0 - sum(d["busy_s"] for d in devs) /
                        (len(devs) * tr["window_s"]))
    if what == "devices_busy":
        return float(sum(1 for d in devs if d["kernel_s"]))
    kernel_s = sum(s for d in devs for k, s in d["kernel_s"].items()
                   if k in wanted)
    if kernel_s <= 0 or not sl["bytes"]:
        return None
    if what == "kernel_s_per_gb":
        return kernel_s / (sl["bytes"] / 1e9)
    if what == "roofline":
        spec = ev["kernels"][params["kernel"]]
        peak = ev["peak"][spec["bound"]]
        moved = perf_moved_bytes(sl["perf1"], spec["perf_kernels"]) - \
            perf_moved_bytes(sl["perf0"], spec["perf_kernels"])
        least = moved / peak
        return 100.0 * least / kernel_s
    raise ValueError(f"device_trace: unknown `what` {what!r}")
