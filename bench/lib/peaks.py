"""Published peaks of each chip, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite" to JAX): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 394 TOP/s int8,
16 GB of HBM at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no peaks known for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}") from None
