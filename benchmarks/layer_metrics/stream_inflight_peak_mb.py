"""Streaming scan and upload: the run-ahead, MB (10^6 bytes): the most
bytes of batches handed to the runtime and not yet on the device at
once, the largest `inflight_peak_bytes` over the `TableScan` spans of
the window's kept statements. One batch while a read of each batch
holds the host back; a change that lets the host run ahead shows here
before it shows in `memory_peak_bytes`. None where no kept statement
has a stamped streamed scan."""

from layer_metrics.stream_upload_ms import stamped_scans


def compute(run):
    peaks = [max(v) for v in stamped_scans(run, "inflight_peak_bytes")]
    return max(peaks) / 1e6 if peaks else None
