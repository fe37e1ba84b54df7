"""Operator kernels: the `Filter` spans' device-side spans per
statement, mean over the window's kept statements, ms (as
aggregate_device_ms): the predicate, and behind a dynamic filter its
probe over the page and the compaction that follows."""

from layer_metrics.aggregate_device_ms import device_ms
from layer_metrics.statement_traces import mean


def compute(run):
    return mean(device_ms(run, "Filter"))
