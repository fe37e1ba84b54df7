"""Client protocol: mean `submit` span (server/state.py): the handler
thread's parse, transaction check and resource-group admission."""

from layer_metrics.statement_traces import mean_span_ms


def compute(run):
    return mean_span_ms(run, "submit")
