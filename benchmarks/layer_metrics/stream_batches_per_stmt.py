"""Streaming scan and upload: batches a statement's streamed scans
handed to the chip: the `batches` counter of its `TableScan` spans
(`exec/stream.py` `_stream_scan`: one `catalog.scan` call a batch),
summed per statement, mean over the window's kept statements. 58.0 on
`sf10s.scan_agg` by arithmetic (59,994,841 rows / 1,048,576), so a
change of the batch size, or a batch skipped, shows. None where no kept
statement has a streamed scan: a resident table's `TableScan` carries no
such counter, and a program from before the spans existed has no such
span."""

from layer_metrics.statement_traces import mean, window_traces


def scans(run, counter: str):
    """For each kept statement of the window with a streamed scan, the
    sum of `counter` over its closed `TableScan` spans that booked one
    (a scan has no child that books it, so the span's total is its
    own); statements without one are left out."""
    sums = []
    for trace in window_traces(run):
        booked = [
            span.attrs[counter] for span in trace.spans()
            if span.name == "TableScan" and span.end is not None
            and counter in span.attrs
        ]
        if booked:
            sums.append(sum(booked))
    return sums


def compute(run):
    return mean(scans(run, "batches"))
