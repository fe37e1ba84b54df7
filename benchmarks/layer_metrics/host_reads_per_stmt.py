"""Host orchestration: mean `host_reads` of the `execute` span: blocking
device-to-host reads a statement's execution made (obs/span.host_read)."""

from layer_metrics.statement_traces import mean_counter


def compute(run):
    return mean_counter(run, "execute", "host_reads")
