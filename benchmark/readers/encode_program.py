"""Reader `encode_program`: what an encode unit's device program spends
where, from the reduction of the profiler's trace of the traced slice
(trace_reduce.py), for a code whose kernel the MXU bounds.

  what = int8_roofline    100 * least time / kernel time, where the least
                          time is the operations the code's parity apply
                          needs for the slice's `.dat` bytes
                          (`operations`: `ops_per_dat_byte` a byte, a
                          constant of the metric's file with its
                          derivation there; a reader sees no
                          configuration) over the device's published int8
                          operations a second (peaks.json, under the
                          kernel's `bound`), and the kernel time the
                          summed device seconds of the events kernels.json
                          maps to `kernel`
  what = layout_s_per_gb  device seconds outside the kernel per GB the
                          slice's operations processed: the union of the
                          intervals in which any operation ran on a device
                          less the kernel's summed events

Either is None where the trace holds no event of the kernel."""


def operations(dat_bytes: float, ops_per_dat_byte: float) -> float:
    """Operations (a multiply-add is two) the apply needs for `dat_bytes`
    of a volume: what the algorithm asks, not what an implementation pads
    it to."""
    return dat_bytes * ops_per_dat_byte


def read(ev: dict, params: dict):
    sl = ev.get("slice")
    tr = sl and sl.get("trace")
    if not tr or not tr["devices"] or not sl.get("bytes"):
        return None
    kernel_s = sum(d["kernel_s"].get(params["kernel"], 0.0)
                   for d in tr["devices"])
    if kernel_s <= 0:
        return None
    what = params["what"]
    if what == "int8_roofline":
        peak = ev["peak"][ev["kernels"][params["kernel"]]["bound"]]
        least = operations(sl["bytes"], params["ops_per_dat_byte"]) / peak
        return 100.0 * least / kernel_s
    if what == "layout_s_per_gb":
        busy_s = sum(d["busy_s"] for d in tr["devices"])
        return (busy_s - kernel_s) / (sl["bytes"] / 1e9)
    raise ValueError(f"encode_program: unknown `what` {what!r}")
