"""Peak rates of each chip the benchmark may run on, keyed by the
``device_kind`` that JAX reports. A device not in the table is an error:
a share of an unknown peak means nothing.

TPU v5e (JAX's ``device_kind``: "TPU v5 lite"): Google Cloud
documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GiB of HBM2 at 819 GB/s per chip, 1,600 Gbit/s
of inter-chip interconnect.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
