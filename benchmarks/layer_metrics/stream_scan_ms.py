"""Streaming scan and upload: the host's time handing batches to the
runtime, ms a statement: the `scan_s` counter of the statement's
`TableScan` spans (the wall inside `catalog.scan`: slice the host
columns, pad the last batch, hand each column to the runtime), summed
per statement, mean over the window's kept statements. Not the link's
time: a transfer the runtime only enqueued is waited for by whatever
reads next. None where no kept statement has a streamed scan."""

from layer_metrics.statement_traces import mean
from layer_metrics.stream_batches_per_stmt import scans


def compute(run):
    return mean(s * 1e3 for s in scans(run, "scan_s"))
