"""Plain float64 prefix sums on the host, and the comparison that decides
``correct`` for the scan cells.

The error of an answer ``y`` to ``x`` is, per row, the largest absolute
difference from the float64 running sum divided by ``max(1, largest
|running sum|)`` of that row, and the worst row's value is the number
compared. Rows are summed in column chunks with a float64 carry, so the
host holds a few chunks at a time.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

CHUNK = 1 << 24


def _rows(a: np.ndarray) -> np.ndarray:
    return a.reshape(-1, a.shape[-1])


def rel_err(x: np.ndarray, y: np.ndarray) -> float:
    """Worst row's ``max |y - cumsum64(x)| / max(1, max |cumsum64(x)|)``
    along the last axis."""
    xr, yr = _rows(x), _rows(y)
    rows, n = xr.shape
    block_rows = max(1, CHUNK // n)
    cols = min(n, CHUNK)
    worst = 0.0
    for r in range(0, rows, block_rows):
        xb, yb = xr[r: r + block_rows], yr[r: r + block_rows]
        carry = np.zeros((xb.shape[0], 1), np.float64)
        err = np.zeros(xb.shape[0], np.float64)
        scale = np.ones(xb.shape[0], np.float64)
        for c in range(0, n, cols):
            ref = np.cumsum(xb[:, c: c + cols], axis=-1, dtype=np.float64)
            ref += carry
            carry = ref[:, -1:]
            err = np.maximum(err, np.abs(yb[:, c: c + cols] - ref).max(-1))
            scale = np.maximum(scale, np.abs(ref).max(-1))
        worst = max(worst, float((err / scale).max()))
    return worst


def control_answer(x: np.ndarray) -> np.ndarray:
    """The control: the float64 running sum of the input rounded to
    bfloat16 (the precision below the configuration's float32), as the
    float32 answer a program reading bf16 would return."""
    xb = x.astype(ml_dtypes.bfloat16)
    out = np.empty(x.shape, np.float32)
    xr, orow = _rows(xb), _rows(out)
    n = xr.shape[-1]
    cols = min(n, CHUNK)
    carry = np.zeros((xr.shape[0], 1), np.float64)
    for c in range(0, n, cols):
        ref = np.cumsum(xr[:, c: c + cols].astype(np.float64), axis=-1)
        ref += carry
        carry = ref[:, -1:]
        orow[:, c: c + cols] = ref
    return out
