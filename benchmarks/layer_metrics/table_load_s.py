"""Connector: seconds the catalog spent making tables resident, the sum
of the `table_load` spans' walls (host generation, and the upload timed
until the last column has arrived) over the statement trees the
program's store still holds at the end of the run. A table is loaded by
the first statement that names it, in set-up, so this reads every kept
tree and not the window's alone; None where there is no such span (a
catalog that makes its tables on the device, a program from before the
span existed, a run long enough to have pushed set-up's trees out of
the store)."""


def compute(run):
    from presto_tpu.obs import span as obs_span

    walls = [
        span.wall_s
        for trace in obs_span.TRACES.recent()
        for span in trace.spans()
        if span.name == "table_load" and span.end is not None
    ]
    return sum(walls) if walls else None
