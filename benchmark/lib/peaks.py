"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
A device that is not in the table is an error, not a default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_flops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture, per-chip figures",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise RuntimeError(
            f"device_kind {device_kind!r} has no entry in benchmark/lib/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their source"
        )
    return PEAKS[device_kind]


def matmul_peak(device_kind: str, matmul_dtype: str) -> float:
    """Peak operations per second of the configuration's matmul dtype."""
    key = {"int8": "int8_flops", "bfloat16": "bf16_flops"}[matmul_dtype]
    return peaks_for(device_kind)[key]
