"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error."""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, 'TPU v5e' "
              "(cloud.google.com/tpu/docs/v5e)",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
