"""Peak table and the least work a sweep or a serving dispatch requires.

Every count here is a lower bound on what ANY implementation has to do,
worked out from the deployment's shapes (the occupied lanes of each
sensor), never from the program's padded arrays, so that a roofline
share computed from it cannot pass 100% however the work is done.
Float32 operations are counted against the chip's bfloat16 peak, which
can only understate a share.
"""

from __future__ import annotations

import numpy as np

F32 = 4  # bytes

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "819 GB/s HBM bandwidth per chip",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip; an unknown kind is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak figures for device kind {device_kind!r}; add them with "
            "their source to bench/work.py"
        ) from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """max(operations / peak, bytes / bandwidth): the least time the chip
    could take for this work."""
    p = peak(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def sweep(lanes: np.ndarray, slots: int) -> tuple[float, float]:
    """(flops, bytes) of one sweep over every field.

    lanes: (B, n) occupied anchor lanes of each live sensor in each field
    (its neighbors, itself included, plus absorbed measurements).
    slots: live message slots per field (sensors plus absorbed
    measurements).

    Per sensor and field, with L its lanes: two triangular substitutions
    with the L x L factor (2 L^2 flops) and the right-hand side and
    message update (4 L).  Bytes: the factor's lower triangle read once
    (L (L+1) / 2 floats), the coefficients read and written (2 L), and
    every message slot read and written once (2 slots per field).  The
    local Gram is not counted: the new messages equal the right-hand side
    minus lambda times the new coefficients, so no implementation must
    read it.
    """
    lanes = np.asarray(lanes, np.float64)
    b = lanes.shape[0]
    flops = float(np.sum(2 * lanes**2 + 4 * lanes))
    floats = np.sum(lanes * (lanes + 1) / 2) + np.sum(2 * lanes) + 2 * b * slots
    return flops, float(floats * F32)


def serve(rows: int, fields: int, k: int, lanes: np.ndarray, dim: int,
          sensors: int) -> tuple[float, float]:
    """(flops, bytes) of answering ``rows`` query points over every field.

    lanes: (B, n) occupied lanes of each live sensor in each field; the
    base anchors sit at the sensors' positions, shared by all fields.
    Bytes: the lesser of (every live anchor table read once: the
    positions once plus each field's coefficients) and (each query's k
    selected tables, with the fewest lanes any sensor has), plus the
    queries in and the answers out.  Flops: each selected anchor's
    kernel value (3 d - 1 for the squared distance) and its
    multiply-add (2), per field; the exponential is not counted.
    """
    lanes = np.asarray(lanes, np.float64)
    l_min = float(lanes.min())
    tables = sensors * dim + float(lanes.sum())
    selected = rows * k * l_min * (dim + fields)
    floats = min(tables, selected) + rows * dim + fields * rows
    flops = rows * k * l_min * ((3 * dim - 1) + 2 * fields)
    return float(flops), float(floats * F32)
