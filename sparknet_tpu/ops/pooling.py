"""Caffe-semantics spatial pooling on NHWC tensors.

Caffe's PoolingLayer (the native op behind the reference's `Pooling` layers,
e.g. reference `models/cifar10/cifar10_quick_train_test.prototxt` pool1-3)
differs from framework defaults in two ways this module reproduces exactly:

1. **Ceil-mode output size**: out = ceil((H + 2*pad - k) / stride) + 1, then
   if pad > 0 and the last window would start past H + pad, drop it.
2. **AVE divisor includes padding**: the divisor is the window area clipped to
   the *padded* extent [0 - pad, H + pad), not to the real image — so interior
   windows divide by k*k even when they overlap real-edge clipping, and only
   ceil-overflow windows at the bottom/right divide by less.

Everything is static-shape: the divisor map is precomputed with numpy at trace
time, so XLA sees one reduce_window plus one broadcast multiply — both fuse.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax


def caffe_pool_output_size(size: int, kernel: int, stride: int, pad: int) -> int:
    out = int(np.ceil((size + 2 * pad - kernel) / stride)) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1
    return out


def _ave_divisor_1d(size: int, kernel: int, stride: int, pad: int,
                    out: int) -> np.ndarray:
    starts = np.arange(out) * stride - pad
    ends = np.minimum(starts + kernel, size + pad)
    return (ends - starts).astype(np.float32)


def pool2d(x: jnp.ndarray, mode: str, kernel: int, stride: int,
           pad: int) -> jnp.ndarray:
    """Pool an NHWC tensor with Caffe semantics. mode: 'MAX' | 'AVE'.

    MAX is `reduce_window` with its select-and-scatter VJP on every
    backend, and there is no other form to choose: a Pallas kernel for the
    backward (one fused pass over x, dy, y in the conv's N-minor layout)
    was A/B'd on the chip twice and lost 10 % end to end both times (r3;
    PR 29: `train_round_rate` 18,679 against 20,942 in
    `caffenet-train-round`, `pool_device_ms` 115 against 61) -- the
    custom-call boundary broke XLA's fusion of pool-backward with its
    elementwise neighbours and cost layout passes on either side."""
    n, h, w, c = x.shape
    oh = caffe_pool_output_size(h, kernel, stride, pad)
    ow = caffe_pool_output_size(w, kernel, stride, pad)
    # End padding so reduce_window emits exactly (oh, ow) windows.
    end_h = (oh - 1) * stride + kernel - h - pad
    end_w = (ow - 1) * stride + kernel - w - pad
    padding = ((0, 0), (pad, max(end_h, 0)), (pad, max(end_w, 0)), (0, 0))
    dims = (1, kernel, kernel, 1)
    strides = (1, stride, stride, 1)

    if mode == "MAX":
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, padding)
    if mode == "AVE":
        # f32 accumulation (and: bf16 reduce_window-add mis-linearizes
        # under jit in jax 0.9).
        s = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add, dims,
                              strides, padding)
        div_h = _ave_divisor_1d(h, kernel, stride, pad, oh)
        div_w = _ave_divisor_1d(w, kernel, stride, pad, ow)
        div = jnp.asarray(np.outer(div_h, div_w))
        return (s / div[None, :, :, None]).astype(x.dtype)
    raise ValueError(f"unknown pool mode {mode!r}")


def global_pool2d(x: jnp.ndarray, mode: str) -> jnp.ndarray:
    if mode == "MAX":
        return jnp.max(x, axis=(1, 2), keepdims=True)
    return jnp.mean(x, axis=(1, 2), keepdims=True)
