"""95th percentile of the client wall over all statements of the window."""

from stats import percentile


def compute(run):
    return percentile([r["wall_ms"] for r in run.records], 95)
