"""Published peaks per device kind, and the update's least work.

Source of the v5e row: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip.  A device kind that is not in the table is an error.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'

#: Bytes of one switch observation the update must read: its flow key,
#: value and timestamp, 4 bytes each.
OBS_BYTES = 12
COUNTER_BYTES = 4


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def update_bytes(observations: int, n_used, widths) -> int:
    """Least HBM traffic of the sketch update, whatever implements it:
    every observation read once, and every (epoch, fragment)'s own
    ``n x width`` counters written once.  ``n_used`` is one (S,) array
    of subepoch counts per epoch; ``widths`` the (S,) logical widths."""
    w = np.asarray(widths, np.int64)
    counters = sum(int((np.asarray(n, np.int64) * w).sum()) for n in n_used)
    return OBS_BYTES * int(observations) + COUNTER_BYTES * counters
