"""Work counted from shapes, and the chip's peaks.

The operations and bytes are what the algorithm needs, not what the
program happens to execute: a masked-out client's local SGD and the echo
kernel's pad columns are not counted, so removing such waste raises the
shares that use these numbers.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def cnn_layer_macs(model: dict) -> list:
    """Multiply-accumulates of one sample's forward pass, per layer in
    order: 3x3 'SAME' convolutions (each followed by a 2x2 max-pool), then
    the dense layers and the head."""
    H, W, C = model["input_shape"]
    macs, cin, h, w = [], C, H, W
    for cout in model["channels"]:
        macs.append(h * w * cout * 9 * cin)
        cin, h, w = cout, h // 2, w // 2
    din = h * w * cin
    for dout in list(model["hidden"]) + [model["n_classes"]]:
        macs.append(din * dout)
        din = dout
    return macs


def cnn_train_flops_per_sample(model: dict) -> int:
    """FLOPs of one sample's forward and backward pass: 2 per MAC forward,
    2 for the weight gradient, and 2 for the input gradient of every layer
    but the first (whose input is data).  Bias, activation and pooling
    work is not counted.  19,275,264 for the paper's CNN at 32x32x3."""
    macs = cnn_layer_macs(model)
    return 6 * sum(macs) - 2 * macs[0]


def cnn_param_count(model: dict) -> int:
    """Trainable parameters N of the CNN (273,706 at 32x32x3)."""
    H, W, C = model["input_shape"]
    n, cin, h, w = 0, C, H, W
    for cout in model["channels"]:
        n += 9 * cin * cout + cout
        cin, h, w = cout, h // 2, w // 2
    din = h * w * cin
    for dout in list(model["hidden"]) + [model["n_classes"]]:
        n += din * dout + dout
        din = dout
    return n


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
