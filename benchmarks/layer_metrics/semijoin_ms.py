"""Host orchestration: the `SemiJoin` spans' self walls per statement,
mean over the window's kept statements, ms (as aggregate_ms)."""

from layer_metrics.aggregate_ms import per_statement, self_ms
from layer_metrics.statement_traces import mean


def compute(run):
    return mean(per_statement(run, "SemiJoin", self_ms))
