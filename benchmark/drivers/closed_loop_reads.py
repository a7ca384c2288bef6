"""Driver `closed_loop_reads`: N clients, each sending its next blob GET
when the last has answered.

Needles are drawn uniformly without replacement from the first volume's
needles, in an order fixed by the seed; the first `warmup_reads` of the
draw warm up and are not read again.  Latency is taken client side, from
the request sent to the last byte of the body read; the sha256 check comes
after the clock stops.  Each read is classed `degraded` when its record
touches a shard file listed in `lost_shards` (by the layout rule of the
configuration's reference module, under its `codec` block) and `healthy`
otherwise.  Should the window outlast the
draw, the order is played again and the run says so.
"""

from __future__ import annotations

import http.client
import threading
import time

from harness import check, make_rng, needle_ok, read_needle, say
import stats


def classifier(ref, codec: dict, volume: dict, lost_shards: list[int]):
    """-> needle -> `degraded` or `healthy`, for the needles of `volume`
    (a manifest entry) with the shard files `lost_shards` gone."""
    lost = set(lost_shards)

    def klass(n: list) -> str:
        held_by = ref.shards_touched(codec, volume["dat_bytes"], n[3], n[4])
        return "degraded" if held_by & lost else "healthy"
    return klass


def run(cell) -> dict:
    p, srv = cell.traffic, cell.srv
    needles = cell.volumes[0]["needles"]
    klass = classifier(cell.ref, cell.codec, cell.volumes[0],
                       p["lost_shards"])
    klass_of = {n[0]: klass(n) for n in needles}  # none of it in the window
    order = [int(i) for i in make_rng(cell.seed, 2).permutation(len(needles))]
    warm, draw = order[:p["warmup_reads"]], order[p["warmup_reads"]:]
    check(draw, "the volume holds no more needles than the warm-up reads")

    conn = http.client.HTTPConnection(srv.volume, timeout=600)
    try:
        for i in warm:
            status, body = read_needle(conn, needles[i][0])
            check(needle_ok(status, body, needles[i]),
                  f"warm-up GET {needles[i][0]} -> {status}, "
                  f"{len(body)} bytes")
    finally:
        conn.close()

    lock = threading.Lock()
    state = {"next": 0, "stop": False}
    ops: list[dict] = []
    errors: list[BaseException] = []

    def client() -> None:
        conn = http.client.HTTPConnection(srv.volume, timeout=600)
        try:
            while True:
                with lock:
                    if state["stop"]:
                        return
                    n = needles[draw[state["next"] % len(draw)]]
                    state["next"] += 1
                t_wall = time.time()
                t0 = time.perf_counter()
                status, body = read_needle(conn, n[0])
                ms = (time.perf_counter() - t0) * 1e3
                ok = needle_ok(status, body, n)
                with lock:
                    ops.append({"t0": t_wall, "ms": ms, "ok": ok,
                                "bytes": n[2], "klass": klass_of[n[0]],
                                "why": None if ok else
                                f"GET {n[0]} -> {status}, {len(body)} bytes"})
        except BaseException as e:  # read by the main thread
            errors.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, name=f"bench-client-{i}",
                                daemon=True) for i in range(p["clients"])]
    cell.window_begins()
    t_begin = time.time()
    for t in threads:
        t.start()
    sl = p["trace_slice"] if cell.tracer.enabled else None
    if sl and sl["after_s"] + sl["seconds"] < cell.seconds:
        time.sleep(sl["after_s"])
        cell.tracer.start()
        time.sleep(sl["seconds"])
        cell.tracer.stop()
    time.sleep(max(0.0, t_begin + cell.seconds - time.time()))
    with lock:
        state["stop"] = True
    for t in threads:
        t.join(600)
        check(not t.is_alive(), f"{t.name} did not finish")
    cell.window_ended()
    check(not errors, f"a client died: {errors[:1]}")
    if state["next"] > len(draw):
        say(f"the window outlasted the draw: {state['next']} reads of "
            f"{len(draw)} needles, the order was played again")
    good = [o for o in ops if o["ok"]]
    check(good, "no read of the window succeeded")
    by = {k: sum(1 for o in good if o["klass"] == k)
          for k in ("healthy", "degraded")}
    say(f"reads in the window: {len(ops)} attempted, {len(good)} good "
        f"({by['healthy']} healthy, {by['degraded']} degraded), "
        f"{len(good) / cell.seconds:.1f} a second")
    ms = [o["ms"] for o in good]
    return {"attempted": len(ops), "failed": len(ops) - len(good),
            "failures": [o["why"] for o in ops if not o["ok"]], "ops": ops,
            "compared": {"reads_wrong": [len(ops) - len(good), len(ops)]},
            "metrics": {m["name"]: stats.stat(ms, m["stat"])
                        for m in p["metrics"]}}
