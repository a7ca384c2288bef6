"""Reader `perf_delta`: what the program counted about its own stages and
compilations, as the difference between the /perf snapshots taken at the
window's two edges.

  what = stage_ms          busy seconds of stage `stage` of the flow account
                           `flow` (/perf -> jobs, state "flow") over its
                           items, in ms: the server-side time of one pass
                           through that stage
  what = compiles_per_call backend compilations booked to the codec entry
                           point `entry` (/perf -> compiles, counted inside
                           the program by a jax.monitoring listener) per
                           device call of `kernel` (/perf -> roofline.rows)
  what = compile_share     100 * seconds of the compilations booked to
                           `entry` over the busy seconds of `flow`.`stage`

A /perf that lacks the block a metric reads (a program from before the
compile counter has no `compiles`) gives None, and so does a window in
which the denominator did not move."""


def _stage(perf: dict, flow: str, stage: str):
    for job in perf.get("jobs", []):
        if job.get("kind") == flow and job.get("state") == "flow":
            return job.get("stages", {}).get(stage)
    return None


def _stage_delta(w: dict, params: dict, field: str):
    rows = [_stage(w[edge], params["flow"], params["stage"])
            for edge in ("perf0", "perf1")]
    if rows[1] is None:
        return None
    return rows[1][field] - (rows[0][field] if rows[0] else 0.0)


def _compiles_delta(w: dict, entry: str, field: str):
    if "compiles" not in w["perf1"]:
        return None
    return w["perf1"]["compiles"].get(entry, {}).get(field, 0) - \
        w["perf0"].get("compiles", {}).get(entry, {}).get(field, 0)


def _device_calls(perf: dict, kernel: str) -> int:
    # every resource row of a kernel repeats the kernel's calls
    return max((row["calls"] for row in perf["roofline"]["rows"]
                if row["kernel"] == kernel and row["backend"] == "device"),
               default=0)


def read(ev: dict, params: dict):
    w = ev["window"]
    if not w.get("perf0") or not w.get("perf1"):
        return None
    what = params["what"]
    if what == "stage_ms":
        busy = _stage_delta(w, params, "busy_s")
        items = _stage_delta(w, params, "items")
        return 1e3 * busy / items if busy is not None and items else None
    if what == "compiles_per_call":
        count = _compiles_delta(w, params["entry"], "count")
        calls = _device_calls(w["perf1"], params["kernel"]) - \
            _device_calls(w["perf0"], params["kernel"])
        return count / calls if count is not None and calls > 0 else None
    if what == "compile_share":
        secs = _compiles_delta(w, params["entry"], "seconds")
        busy = _stage_delta(w, params, "busy_s")
        return 100.0 * secs / busy if secs is not None and busy else None
    raise ValueError(f"perf_delta: unknown `what` {what!r}")
