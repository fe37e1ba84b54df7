"""Client protocol: mean `queued` span (server/state.py): from the end
of `submit` until a worker thread marks the statement RUNNING."""

from layer_metrics.statement_traces import mean_span_ms


def compute(run):
    return mean_span_ms(run, "queued")
