"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

One table for the whole benchmark. A device that is not in it is an
error, never a default: a roofline share against a guessed peak is a
made-up number.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to benchmark/harness/peaks.py") from None
