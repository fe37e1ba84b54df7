"""Host orchestration: the `Aggregate` spans' self walls (each span
minus its children's), summed per statement, mean over the window's
kept statements, ms: what a statement spends in its group-bys, the
host's part and its waits for the device alike."""

from layer_metrics.statement_traces import mean, window_traces


def per_statement(run, name: str, value):
    """For each kept statement of the window that has closed spans
    called `name`, the sum over them of `value(span, self wall s)`;
    statements without one are left out."""
    sums = []
    for trace in window_traces(run):
        values = [
            value(span, own) for span, own in trace.exclusive_walls()
            if span.name == name and span.end is not None
        ]
        if values:
            sums.append(sum(values))
    return sums


def self_ms(_span, own_s):
    return own_s * 1e3


def compute(run):
    return mean(per_statement(run, "Aggregate", self_ms))
