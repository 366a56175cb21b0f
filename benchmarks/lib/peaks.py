"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not here is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB HBM per chip.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e (cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]
