"""What the six lifecycle and counter metrics share: the window's
statements as the program's own store has them at the end of the run.

`run.send` keeps three span names per statement, so these metrics read
`presto_tpu.obs.span.TRACES` instead: the kept traces (the newest 64 at
the shipped `PRESTO_TPU_TRACE_KEEP`) whose root began at or after the
window's first send. Only a tree rooted in a `statement` span counts:
a program from before that span existed has none, every reader here then
finds nothing and its metric is left out. Not a metric: no entry of
BENCHMARK.json names this file.
"""


def window_traces(run):
    """The kept traces of the window's served statements, oldest first."""
    from presto_tpu.obs import span as obs_span

    if not run.records:
        return []
    opened = run.records[0]["epoch_ns"] / 1e9
    traces = []
    for trace in obs_span.TRACES.recent():
        root = trace.root()
        if (
            root is not None and root.name == "statement"
            and root.end is not None and root.start >= opened
        ):
            traces.append(trace)
    return traces


def spans_named(run, name: str):
    """Each window trace's first closed span called `name`."""
    found = []
    for trace in window_traces(run):
        for span in trace.spans():
            if span.name == name and span.end is not None:
                found.append(span)
                break
    return found


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def mean_span_ms(run, name: str):
    """Mean wall of the span `name`, ms; None where no trace has one."""
    return mean(s.wall_s * 1e3 for s in spans_named(run, name))


def mean_counter(run, span_name: str, counter: str):
    """Mean of the subtree total of `counter` the span `span_name`
    carries (0 where a span booked none); None where no trace has the
    span."""
    return mean(
        s.attrs.get(counter, 0) for s in spans_named(run, span_name)
    )
