"""The least time the chip could take to read what the traced statements
must read (bytes over the chip's peak HBM bandwidth), over the time the
device was busy in the trace. The bytes are the same whatever kernel
runs. Nothing on a device that is not in the peaks table, and nothing
where the trace has no device time: never 0."""

import bytes_model
import peaks


def compute(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    sf = run.config["sf"]
    need = sum(bytes_model.statement_bytes(i, sf) for i in t["traced_ids"])
    floor_s = need / peaks.hbm_bytes_per_s(run.device_kind)
    return 100.0 * floor_s / t["busy_s"]
