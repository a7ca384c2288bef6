"""The arithmetic every metric of the benchmark goes through."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between the
    two nearest ranks (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def stat(values, name: str) -> float:
    """`median`, `mean`, `sum`, `max` or `p<q>` of the samples."""
    if name == "median":
        return median(values)
    if name == "mean":
        return sum(values) / len(values)
    if name == "sum":
        return float(sum(values))
    if name == "max":
        return float(max(values))
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """The parts of [t0, t1] that no (start, end) interval covers."""
    out, edge = [], t0
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, min(s, t1)))
        edge = max(edge, e)
        if edge >= t1:
            break
    if edge < t1:
        out.append((edge, t1))
    return [(a, b) for a, b in out if b > a]
