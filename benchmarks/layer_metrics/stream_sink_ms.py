"""Host orchestration: what a streamed statement spends in its
accumulating sinks, ms: the `Aggregate` spans' self walls (the sink's
span minus its children's, the scan's among them: per-batch partial
aggregation, merges, the reads between them), summed per statement, by
`aggregate_ms.per_statement`; mean over the window's kept statements
that streamed. None where none did (no `TableScan` span with `batches`):
on a resident table the same figure is `aggregate_ms`."""

from layer_metrics.aggregate_ms import per_statement, self_ms
from layer_metrics.statement_traces import mean
from layer_metrics.stream_batches_per_stmt import scans


def compute(run):
    if not scans(run, "batches"):
        return None
    return mean(per_statement(run, "Aggregate", self_ms))
