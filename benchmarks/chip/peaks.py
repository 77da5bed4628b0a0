"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
No peak for uint32 VPU arithmetic is published, so the field kernels'
rooflines are bounded by HBM bytes alone.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def lookup(device_kind: str) -> dict:
    """The peak entry of `device_kind`; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
