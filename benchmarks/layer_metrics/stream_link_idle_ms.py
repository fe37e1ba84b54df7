"""Streaming scan and upload: the time the host link had NOTHING of the
scan to copy because the host had not handed over the next batch, ms a
statement: `link_idle_s` of the statement's `TableScan` spans (for
batch k, the hand-over minus when batch k-1 was on the device, where
that is positive: the next batch's `catalog.scan` and the sink's host
work between batches). With `stream_upload_ms` it splits the stream's
wall, first hand-over to last batch ready, between the link and the
host. None where no kept statement has a stamped streamed scan."""

from layer_metrics.statement_traces import mean
from layer_metrics.stream_upload_ms import stamped_scans


def compute(run):
    return mean(sum(v) * 1e3 for v in stamped_scans(run, "link_idle_s"))
