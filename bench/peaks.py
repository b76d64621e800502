"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect
(four links of 50 GB/s). A kind that is not listed is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """The peaks of one chip of `device_kind`; KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
