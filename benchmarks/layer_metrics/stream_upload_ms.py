"""Streaming scan and upload: the host link's time that nothing on the
host covered, ms a statement: `upload_s` of the statement's `TableScan`
spans as the ready stamps of their batches left it
(`presto_tpu.obs.span.sent`, queue `link`: for batch k, when its
columns were on the device minus the later of when batch k-1's were and
when the host handed batch k over). Summed per statement, mean over the
window's kept statements. The part of a copy that `catalog.scan`'s own
slicing of later columns covers is not in it. None where no kept
statement has a stamped streamed scan."""

from layer_metrics.statement_traces import mean, window_traces


def stamped_scans(run, stamp: str):
    """For each kept statement of the window whose `TableScan` spans
    carry batches' ready stamps (`uploads`), their `stamp`s."""
    found = []
    for trace in window_traces(run):
        values = [
            span.attrs[stamp] for span in trace.spans()
            if span.name == "TableScan" and "uploads" in span.attrs
            and stamp in span.attrs
        ]
        if values:
            found.append(values)
    return found


def compute(run):
    return mean(sum(v) * 1e3 for v in stamped_scans(run, "upload_s"))
