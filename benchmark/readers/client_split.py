"""Reader `client_split`: a statistic over the client-side latencies of
one class of read (`healthy`: the record touches no lost shard;
`degraded`: it does), as the driver classed them, or with `klass` null of
all reads."""

import stats


def read(ev: dict, params: dict):
    ms = [o["ms"] for o in ev["ops"]
          if o.get("ok") and params["klass"] in (None, o.get("klass"))]
    return stats.stat(ms, params["stat"]) if ms else None
