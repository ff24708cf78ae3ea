"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.
A device that is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
`pltpu.get_tpu_info()` on the chip agreed (197e12, 8.2e11 B/s; PERF.md,
PR 21). Copied from `ray_tpu/observability/flops.py` PEAK_FLOPS_BF16,
which has no bandwidth column."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    for name in sorted(PEAKS, key=len, reverse=True):
        if device_kind.startswith(name):
            return PEAKS[name]
    raise KeyError(
        f"no published peak for device kind {device_kind!r}: add it to "
        "benchmarks/harness/peaks.py with its source")
