"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` jax reports. A device that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip)."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "bf16_flops_per_s": 197e12, "hbm_bytes": 16 * 2 ** 30},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                "bf16_flops_per_s": 197e12, "hbm_bytes": 16 * 2 ** 30},
    # The sandbox rehearsal (--rehearse, CPU) has no chip and no peak. It is
    # given the v5e's numbers under a name no device reports, so that every
    # reader runs; its line says platform "cpu" and can pass for nothing.
    "rehearsal": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                  "bf16_flops_per_s": 197e12, "hbm_bytes": 16 * 2 ** 30},
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind][key]
