"""The benchmark's own count of what the algorithm needs: conv + inner-product
training FLOPs per image, and the LRN kernel's operations and bytes. A copy of
the arithmetic in the program's `utils/flops.py` (which may change later; this
may not), worked from the configuration's reference layer table instead of the
program's compiled net.
"""
from __future__ import annotations

import json
import os

#: forward + input gradient + weight gradient, each a conv/matmul of forward
#: cost. Recomputed operations do not count.
TRAIN_FWD_MULT = 3.0


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip, by the exact `device_kind`
    jax reports. A device that is not in the table is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device_kind {device_kind!r} "
                       f"(known: {sorted(table)}); add it to "
                       f"benchmark/peaks.json with its source")
    return table[device_kind]


def _walk(layers, crop: int):
    """(kind, args, in_h, in_c, out_h) per layer of a reference layer table."""
    h, c = crop, 3
    for name, kind, a in layers:
        in_h, in_c = h, c
        if kind == "conv":
            h = (h + 2 * a["pad"] - a["k"]) // a["stride"] + 1
            c = a["cout"]
        elif kind == "pool":
            h = -(-(h - a["k"]) // a["stride"]) + 1
        elif kind == "fc":
            h, c = 0, a["cout"]
        yield name, kind, a, in_h, in_c, h


def forward_flops_per_image(layers, crop: int, n_classes: int) -> float:
    """2 x MACs of every convolution and inner product, one image."""
    total = 0.0
    for _, kind, a, in_h, in_c, out_h in _walk(layers, crop):
        if kind == "conv":
            total += 2.0 * out_h * out_h * a["k"] * a["k"] * (
                in_c // a["group"]) * a["cout"]
        elif kind == "fc":
            fan_in = in_c * in_h * in_h if in_h else in_c
            total += 2.0 * fan_in * (a["cout"] or n_classes)
    return total


def train_flops_per_image(layers, crop: int, n_classes: int) -> float:
    return TRAIN_FWD_MULT * forward_flops_per_image(layers, crop, n_classes)


def lrn_step_cost(layers, crop: int, batch: int, itemsize: int) -> dict:
    """Operations and HBM bytes the LRN layers of ONE training step need,
    forward and backward, all LRN layers together. Forward reads x and writes
    y; backward (which recomputes the normaliser) reads x and dy and writes
    dx: five passes over the activation. Per element: the square, the window
    sum (size - 1 adds), scale and power (4), the product (1) forward, and
    about twice that backward."""
    elems = ops = 0.0
    for _, kind, a, in_h, in_c, _ in _walk(layers, crop):
        if kind == "lrn":
            n = float(batch) * in_h * in_h * in_c
            elems += n
            ops += 3.0 * n * (a["size"] + 5)
    return {"ops": ops, "bytes": 5.0 * elems * itemsize}


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share in %, which bound binds): the least time the chip could take,
    the larger of ops / peak FLOP/s and bytes / peak bytes/s, over the time
    the kernel took."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
