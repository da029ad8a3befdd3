"""Work counted from shapes, and the chip's peaks.

The operations and bytes are what the algorithm needs, not what the
program happens to execute: a masked-out client's local SGD and the echo
kernel's pad columns are not counted, so removing such waste raises the
shares that use these numbers.  A model's FLOPs a sample are its model
module's (``bench/models/<kind>.py``).
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def echo_kernel_bytes(rows: int, n: int) -> int:
    """HBM bytes one fused FedAWE aggregation needs: read the f32 start and
    end stacks (``rows`` x ``n`` each), read the previous global and write
    the new one (``n`` each).  221,154,448 at rows = 100, n = 273,706."""
    return 4 * (2 * rows * n + 2 * n)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).
    A kind missing from the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]
