"""Result return: mean `rows` span (server/state.py): the result's copy
to the host and its Python rows."""

from layer_metrics.statement_traces import mean_span_ms


def compute(run):
    return mean_span_ms(run, "rows")
