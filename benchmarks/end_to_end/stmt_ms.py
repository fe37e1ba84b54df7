"""The window's seconds over the statements completed in it: one
closed-loop client, so all the time over all the work."""


def compute(run):
    return run.window_s * 1e3 / len(run.records)
