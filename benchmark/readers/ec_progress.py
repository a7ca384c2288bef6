"""Reader `ec_progress`: stage occupancy of the EC file engines, from the
`stages` the job left on /admin/ec/progress after each operation.

The value is the median over the window's operations of
  100 * sum(stages[s] for s in `stages`) / (workers * wall_s)
where workers is the sum of the `workers` keys (the writer pool apportions
its threads among its stage keys), or 1 for a stage one thread runs.
Note that the `d2h_s` stage is device wait plus copy."""

import stats


def read(ev: dict, params: dict):
    shares = []
    for o in ev["ops"]:
        st = o.get("stages") or {}
        if not o.get("ok") or not st.get("wall_s") or \
                not all(k in st for k in params["stages"]):
            continue
        workers = sum(st.get(k, 0.0) for k in params["workers"]) \
            if params["workers"] else 1.0
        if workers <= 0:
            continue
        shares.append(100.0 * sum(st[k] for k in params["stages"]) /
                      (workers * st["wall_s"]))
    return stats.median(shares) if shares else None
