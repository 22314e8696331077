"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A kind that is not here is an error,
never a default: no roofline share is ever computed against a guess."""

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI a chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmark/peaks.py with its source (known: "
            f"{sorted(DEVICE_PEAKS)})") from None
