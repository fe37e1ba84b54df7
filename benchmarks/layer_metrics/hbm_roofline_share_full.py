"""hbm_roofline_share for a cell on the shipped connector's population:
the least time the chip could take to read what the traced statements
must read (`bytes_model_full.py`: the full-width tables' stored bytes,
the same whatever kernel runs) over the time the device was busy in the
trace. Nothing where the trace has no device time: never 0."""

import bytes_model_full
import peaks


def compute(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    sf = run.config["sf"]
    need = sum(
        bytes_model_full.statement_bytes(i, sf) for i in t["traced_ids"]
    )
    return 100.0 * need / peaks.hbm_bytes_per_s(run.device_kind) / t["busy_s"]
