"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM2e at 819 GB/s, per chip.
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e (system architecture table)"},
    "TPU v5e": {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
                "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e (system architecture table)"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} (add it to chipbench/peaks.py "
                       "with its source)") from None
