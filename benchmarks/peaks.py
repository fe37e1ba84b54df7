"""The chips' published peaks, keyed by `device_kind`. A kind that is not
here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 16 GB
of HBM2e at 819 GB/s, 197 TFLOP/s bf16. The engine's kernels are bound
by memory, so only the bandwidth is used yet."""

HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
}


def hbm_bytes_per_s(device_kind: str) -> float:
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.py: "
            "add it with its source, do not guess"
        ) from None
