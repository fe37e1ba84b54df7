"""Operator kernels: the `Aggregate` spans' DEVICE-SIDE spans, summed
per statement, mean over the window's kept statements, ms. A device-side
span is the stretch of the device's queue that belongs to the node
(`presto_tpu.obs.span.Trace.device_spans`: from when the entry before
it on the queue was ready, or the node's own work began, to when the
node's output was ready): its programs and the gaps between them, so
on a busy device its device time, where `aggregate_ms` is the HOST's
time in the same spans. None where no kept statement has such a span
with a ready stamp (a program from before the stamps has none)."""

from layer_metrics.statement_traces import mean, window_traces


def device_ms(run, name: str):
    """For each kept statement of the window with stamped spans called
    `name`, the sum of their device-side spans, ms; statements without
    one are left out."""
    sums = []
    for trace in window_traces(run):
        device_spans = getattr(trace, "device_spans", None)
        if device_spans is None:
            return []
        values = [s * 1e3 for span, s in device_spans() if span.name == name]
        if values:
            sums.append(sum(values))
    return sums


def compute(run):
    return mean(device_ms(run, "Aggregate"))
