"""Driver `bulk_calls`: one client, one bulk operation after another.

Plays a traffic file whose `op` is one timed POST to the volume server
with untimed steps before it (`before`, once or for `each_volume`) and
untimed checks after it
(`check_shards`: the listed shard files of every volume against the
reference).  Each operation's client wall gives one sample of
bytes / wall in GB/s, where bytes are the `.dat` bytes of the volumes the
call works on.  After each operation the job's stages are read from
/admin/ec/progress (untimed) for the per-layer readers.  `compared` counts
what the run held against the reference, [wrong, of]: calls by their
answer, shard files by sha256 and size.
"""

from __future__ import annotations

import json
import time

from harness import check, compare_shards, fill, http_call, http_json
import stats


def _matches(got, want) -> bool:
    """Every key of `want` is in `got` with an equal value."""
    return all(got.get(k) == v for k, v in want.items())


def run(cell) -> dict:
    p, srv, vols = cell.traffic, cell.srv, cell.volumes
    vids = [v["vid"] for v in vols]
    nbytes = sum(v["dat_bytes"] for v in vols)
    op = p["op"]
    failures: list[str] = []
    files = [0, 0]  # shard files that differ from the reference, compared

    def compare(v: dict, only=None) -> list[str]:
        wrong = compare_shards(srv.base(v["collection"], v["vid"]), v, only)
        files[0] += len(wrong)
        files[1] += len(v["shards_sha256"] if only is None else only)
        return wrong

    def one() -> dict:
        names = {"vid": vids[0], "vids": vids, "codec": cell.codec["tag"]}
        cell.post_steps(op["before"], vids)
        timed = op["timed"]
        body = fill(timed["body"], **names)
        t_wall = time.time()
        t0 = time.perf_counter()
        status, raw = http_call(srv.volume, "POST", timed["path"], body,
                                timeout=600)
        wall = time.perf_counter() - t0
        why, answered = None, False
        if cell.device is None:  # known once the first operation has run
            cell.check_device(srv.perf())
        names["device_count"] = cell.device["count"]
        if status != 200:
            why = f"{timed['path']} -> {status}: {raw[:300]!r}"
        elif not _matches(json.loads(raw), fill(op["expect"], **names)):
            why = f"{timed['path']} answered {raw[:300]!r}"
        else:
            answered = True
            for v in vols if op["check_shards"] else ():
                wrong = compare(v, op["check_shards"])
                why = why or (wrong[0] if wrong else None)
        job = http_json(srv.volume, "GET",
                        f"/admin/ec/progress?volumeId={vids[0]}")
        return {"t0": t_wall, "t1": t_wall + wall, "wall_s": wall,
                "bytes": nbytes, "ok": why is None, "why": why,
                "answered": answered,
                "kind": job.get("kind"), "stages": job.get("stages", {})}

    for _ in range(p["warmup_ops"]):
        r = one()
        check(r["ok"], f"warm-up operation failed: {r['why']}")
    cell.window_begins()
    ops: list[dict] = []
    sl = p["trace_slice"] if cell.tracer.enabled else None
    deadline = time.time() + cell.seconds
    while time.time() < deadline:
        if sl and len(ops) == sl["skip_ops"]:
            cell.tracer.start()
        ops.append(one())
        if not ops[-1]["ok"]:
            failures.append(ops[-1]["why"])
        if sl and len(ops) == sl["skip_ops"] + sl["ops"]:
            cell.tracer.stop()
    cell.tracer.stop()  # a window too short for the whole slice
    cell.window_ended()
    if p["final_check_shards"] == "all":
        for v in vols:
            failures += compare(v)
    good = [o for o in ops if o["ok"]]
    check(good, f"no operation of the window succeeded: {failures[:3]}")
    m = p["metric"]
    return {"attempted": len(ops), "failed": len(ops) - len(good),
            "failures": failures, "ops": ops,
            "compared": {
                "calls_answered_wrong": [sum(not o["answered"] for o in ops),
                                         len(ops)],
                "shard_files_wrong": files},
            "metrics": {m["name"]: stats.stat(
                [o["bytes"] / 1e9 / o["wall_s"] for o in good], m["stat"])}}
