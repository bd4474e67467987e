"""Analytic FLOP counting for MFU reporting.

Counts the MXU work (convolutions + inner products — where essentially all
of a convnet's FLOPs live) from the compiled net's blob shapes. Elementwise
layers (ReLU/LRN/pool/softmax) are <1% of CaffeNet FLOPs and are excluded,
making the reported MFU slightly conservative.
"""
from __future__ import annotations

import numpy as np

from ..model.net import CompiledNet

#: peak dense bf16 TFLOP/s of ONE chip, keyed by the EXACT string
#: `jax.devices()[0].device_kind` prints, each with its source. A device
#: that is not here is an error (`peak_bf16_flops`), never a default: a
#: utilization divided by a guessed peak is a made-up number.
PEAK_BF16_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    # "TPU v5 lite" is what the v5e reports (chip_smoke.py, PR 21).
    "TPU v5 lite": 197.0,
    # Google Cloud documentation, "TPU v4": 275 TFLOP/s bf16 per chip
    "TPU v4": 275.0,
}

#: fwd+bwd FLOPs as a multiple of forward FLOPs: backward computes both the
#: data gradient and the weight gradient, each a conv/matmul of forward cost.
TRAIN_FWD_MULT = 3.0


def forward_flops_per_image(net: CompiledNet) -> float:
    """Conv + inner-product forward FLOPs for ONE example (2·MACs)."""
    total = 0.0
    for layer in net.spec.layers:
        if layer.type == "Convolution":
            n, h, w, c_out = net.blob_shapes[layer.tops[0]]
            c_in = net.blob_shapes[layer.bottoms[0]][-1]
            k, g = layer.conv.kernel_size, layer.conv.group
            total += 2.0 * h * w * k * k * (c_in // g) * c_out
        elif layer.type == "InnerProduct":
            out_f = net.blob_shapes[layer.tops[0]][-1]
            in_f = int(np.prod(net.blob_shapes[layer.bottoms[0]][1:]))
            total += 2.0 * in_f * out_f
    return total


def train_flops_per_image(net: CompiledNet) -> float:
    return TRAIN_FWD_MULT * forward_flops_per_image(net)


def peak_bf16_flops(device_kind: str) -> float:
    """Peak dense bf16 FLOP/s for an exact device_kind string (e.g.
    'TPU v5 lite'). Raises KeyError for a device the table does not hold."""
    try:
        return PEAK_BF16_TFLOPS[device_kind] * 1e12
    except KeyError:
        raise KeyError(
            f"no peak bf16 FLOP/s on record for device_kind "
            f"{device_kind!r} (known: {sorted(PEAK_BF16_TFLOPS)}); add it "
            f"to utils/flops.PEAK_BF16_TFLOPS with its source") from None
