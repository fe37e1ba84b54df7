"""1 - union of device-op intervals over the traced window, in percent."""


def compute(run):
    t = run.trace
    if not t or t["busy_s"] is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
