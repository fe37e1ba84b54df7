"""Host orchestration: mean of the `execute` span's wall minus its
`host_read_wait_s`, ms: the host's own time tracing, launching and
running Python, as against waiting for the chip."""

from layer_metrics.statement_traces import mean, spans_named


def compute(run):
    return mean(
        (s.wall_s - s.attrs.get("host_read_wait_s", 0.0)) * 1e3
        for s in spans_named(run, "execute")
    )
