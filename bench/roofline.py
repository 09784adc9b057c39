"""Peaks of the chips the benchmark runs on, and the bytes each phase of a
FORA answer needs.

The byte counts are of the work the algorithm needs, whatever implements
it: they come from the graph's n and m, the batch, the push's counted
sweeps and FORA's walk count, never from a padded table or a static lane
count. Both phases are bound by memory, so each share is taken against the
chip's HBM bandwidth.
"""

from __future__ import annotations

import math

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}

# One walk step reads the node's CSR offset, its out-degree and the chosen
# neighbour (int32 each), and one int32 random draw.
WALK_STEP_BYTES = 4 + 4 + 4 + 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def push_sweep_bytes(n: int, m: int, batch: int) -> int:
    """HBM bytes one pull sweep needs: an int32 index and an f32 weight per
    arc, the f32 residual gathered per arc per column, and the residual and
    estimate frames read and written per node per column."""
    return 8 * m + 4 * m * batch + 16 * n * batch


def push_bytes(sweeps: int, n: int, m: int, batch: int) -> int:
    """Bytes of a call's push: its counted sweeps times one sweep's."""
    return sweeps * push_sweep_bytes(n, m, batch)


def walk_bytes(r_sum: float, omega: float, alpha: float) -> float:
    """Bytes of one answer's walks: FORA's walk count ceil(r_sum * omega),
    times the expected walk length 1/alpha, times one step's bytes."""
    return math.ceil(r_sum * omega) * (1.0 / alpha) * WALK_STEP_BYTES
