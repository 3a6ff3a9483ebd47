"""Published peaks of one chip, by ``jax.Device.device_kind``.

A kind that is not here is an error, never a default. The profiler's own
plane statistics give 202.7 TFLOP/s and 819.16 GB/s for this chip; the
published figures stay the table's, so a share of peak is comparable with
every other report of this chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect",
    },
}


def for_kind(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"chipbench: device kind {device_kind!r} has no entry in "
            "chipbench/peaks.py PEAKS") from None
