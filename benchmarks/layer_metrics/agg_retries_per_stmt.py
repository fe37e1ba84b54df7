"""Host orchestration: adaptive re-runs of a statement's group-bys: the
`retries` of its `Aggregate` spans (a program run again at a larger
capacity: the earlier run's work is thrown away), summed per statement,
mean over the window's kept statements. 0.0 where the statements have
`Aggregate` spans and none was re-run; None where they have none."""

from layer_metrics.aggregate_ms import per_statement
from layer_metrics.statement_traces import mean


def compute(run):
    return mean(per_statement(
        run, "Aggregate", lambda span, _own: span.attrs.get("retries", 0)
    ))
