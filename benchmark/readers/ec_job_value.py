"""Reader `ec_job_value`: one plain value the EC file engines leave on
/admin/ec/progress `stages` after each operation (a count the job states,
not seconds): the median of `stages[key]` over the window's operations
that succeeded.  A program whose jobs do not carry the key gives nothing,
and the metric is left out of the line."""

import stats


def read(ev: dict, params: dict):
    values = [o["stages"][params["key"]] for o in ev["ops"]
              if o.get("ok") and
              isinstance((o.get("stages") or {}).get(params["key"]),
                         (int, float))]
    return stats.median(values) if values else None
