"""Programs executed on the device per statement, from the trace."""


def compute(run):
    t = run.trace
    if not t or t["launches"] is None or not t["statements"]:
        return None
    return t["launches"] / t["statements"]
