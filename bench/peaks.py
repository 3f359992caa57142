"""Published peaks of each accelerator the benchmark may run on, keyed by
the `device_kind` JAX reports.  A device that is not in the table is an
error: a roofline share against a guessed peak is no measurement."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bytes_per_s": 1600e9 / 8,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,        # what JAX calls a v5e chip
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peak table row of one chip of `device_kind`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
