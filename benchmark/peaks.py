"""Published peaks of one chip, keyed by the `device_kind` JAX reports.

A kind that has no row is an error, never a default, and nothing in the
environment overrides a row: a share of a peak means the same thing in
every PR.
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
# HBM at 819 GB/s. The attached chip reports itself as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no peaks are recorded for device kind %r; add a row "
                       "with its source to benchmark/peaks.py"
                       % (device_kind,)) from None
