"""Peak numbers of the chips the benchmark may run on, one table.

(peak bf16 FLOP/s, peak HBM bytes/s) of ONE chip, keyed by the
`device_kind` JAX reports. Source: Google Cloud TPU documentation, system
architecture pages ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB;
"TPU v4": 275 / 1228; "TPU v5p": 459 / 2765; "TPU v6e": 918 / 1640).
Copied from the program's bench.py::PEAKS, which nothing in the benchmark
reads. A device that is not listed is an error, never a default.
"""
from __future__ import annotations

from typing import Tuple

PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def peak_for(device_kind: str) -> Tuple[float, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak numbers for device_kind {device_kind!r}: add it to "
            "benchmarks/harness/peaks.py with its source"
        )
    return PEAKS[device_kind]
