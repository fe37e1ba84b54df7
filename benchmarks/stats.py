"""The arithmetic of the end-to-end metrics, apart so it can be tested."""

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks, as
    numpy's default and as `statistics.quantiles(method="inclusive")`."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def geomean(values) -> float:
    v = list(values)
    if not v or min(v) <= 0:
        raise ValueError("geometric mean needs positive values")
    return math.exp(sum(math.log(x) for x in v) / len(v))


def class_means(records) -> dict:
    """{statement id: mean client wall, ms} over ALL of a class's
    statements."""
    walls = {}
    for r in records:
        walls.setdefault(r["id"], []).append(r["wall_ms"])
    return {k: sum(w) / len(w) for k, w in walls.items()}


def mean_span_ms(records, name: str):
    """Mean wall of the program's span `name` over the statements that
    carry it; None where none does."""
    walls = [
        (r["spans"][name][1] - r["spans"][name][0]) * 1e3
        for r in records if name in r.get("spans", {})
    ]
    return sum(walls) / len(walls) if walls else None
