"""Operator kernels: the `Join` spans' device-side spans per statement,
mean over the window's kept statements, ms (as aggregate_device_ms): the
sorted-hash build, the probe (`join_n1`) or the expansion
(`join_expand`), the dynamic filter a join builds between its children,
and the gaps the host left between them."""

from layer_metrics.aggregate_device_ms import device_ms
from layer_metrics.statement_traces import mean


def compute(run):
    return mean(device_ms(run, "Join"))
