"""Host orchestration: mean `host_read_wait_s` of the `execute` span, in
ms: the time the host stood in blocking reads, waiting for the chip."""

from layer_metrics.statement_traces import mean_counter


def compute(run):
    wait_s = mean_counter(run, "execute", "host_read_wait_s")
    return None if wait_s is None else wait_s * 1e3
