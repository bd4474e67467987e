"""Local Response Normalization (across channels), Caffe semantics.

Caffe formula (LRNLayer, used by the reference AlexNet at
`models/bvlc_reference_caffenet/train_val.prototxt` norm1/norm2):

    out[c] = x[c] / (k + (alpha / n) * sum_{c' in window(c, n)} x[c']^2) ^ beta

window(c, n) = channels [c - (n-1)/2, c + (n-1)/2] clipped to [0, C).

On NHWC the channel window is the minor (lane) dimension. WHICH of the three
implementations runs is decided here and nowhere else, from what this module
can observe (`lrn`): the hand-fused Pallas TPU kernel
(`sparknet_tpu.ops.pallas_lrn.lrn_pallas`) on the TPU and under the Pallas
interpreter, the fused elementwise form (`_lrn_fused`) on any other backend.
On the chip the kernel takes 10 ms where pure XLA takes 31 (PERF.md section
6, r1-r5), so no option selects between them. `_lrn_xla` (a channel-padded
reduce_window) is the oracle that tests and `chip_smoke.py` compare with.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def pallas_backend(interpret: bool = False) -> bool:
    """May this backend route a layer to a Pallas call: the TPU, or any
    backend under the Pallas interpreter (CPU parity tests of the path the
    chip runs). The one place that knows; the trainer asks it to decide
    whether `shard_map` may check replication (a `pallas_call` has no
    replication rule)."""
    return interpret or jax.default_backend() == "tpu"


# NOTE: deliberately not jit-decorated — always called inside an outer jit,
# and grad-through-jit with static_argnames mis-linearizes in jax 0.9.
def lrn(x: jnp.ndarray, local_size: int = 5, *, alpha: float = 1e-4,
        beta: float = 0.75, k: float = 1.0,
        interpret: bool = False) -> jnp.ndarray:
    """LRN across the channel (last) axis of an NHWC (or N...C) tensor:
    the Pallas kernel where `_can_pallas` holds, the fused form elsewhere.

    interpret: run the Pallas kernel under the Pallas INTERPRETER, so the
      kernel path resolves on the CPU backend too and the net-level parity
      tests pin the exact wiring the TPU runs.
    """
    if _can_pallas(x, interpret):
        from .pallas_lrn import lrn_pallas
        return lrn_pallas(x, local_size, alpha, beta, k,
                          interpret=interpret)
    return _lrn_fused(x, local_size, alpha, beta, k)


def _can_pallas(x, interpret: bool = False) -> bool:
    """Affirmative TPU check — any other backend gets the portable path,
    not the TPU Pallas kernel."""
    return pallas_backend(interpret) and x.ndim >= 2


# -- fused implementation (off the TPU) --------------------------------------

def window_sum(v: jnp.ndarray, half: int, axis: int = -1) -> jnp.ndarray:
    """Windowed sum over `axis` as 2*half shifted adds with zero edge
    padding (Caffe clips the LRN window at the channel edges). Pure
    slice+pad+add — works both as traced XLA ops (the fused impl) and on
    loaded values inside Pallas kernels (ops/pallas_lrn.py), over any
    axis: the ONE encoding of the window/edge semantics."""
    ax = axis % v.ndim
    c = v.shape[ax]
    zeros = [(0, 0)] * v.ndim
    acc = v
    for j in range(1, half + 1):
        hi = list(zeros)
        hi[ax] = (0, j)
        acc = acc + jnp.pad(lax.slice_in_dim(v, j, c, axis=ax), hi)
        lo = list(zeros)
        lo[ax] = (j, 0)
        acc = acc + jnp.pad(lax.slice_in_dim(v, 0, c - j, axis=ax), lo)
    return acc


def _scale_f32(x: jnp.ndarray, half: int, alpha_n: float,
               k: float) -> jnp.ndarray:
    """Normalizer k + (alpha/n)*window_sum(x^2), accumulated in f32 (free
    under fusion — the f32 intermediates never touch HBM)."""
    sq = jnp.square(x.astype(jnp.float32))
    return k + alpha_n * window_sum(sq, half)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _lrn_fused(x: jnp.ndarray, local_size: int, alpha: float, beta: float,
               k: float) -> jnp.ndarray:
    half = (local_size - 1) // 2
    scale = _scale_f32(x, half, alpha / local_size, k)
    # scale >= k >= 1 > 0; pow via exp/log (pow lacks a linearization rule)
    return (x.astype(jnp.float32)
            * jnp.exp(-beta * jnp.log(scale))).astype(x.dtype)


def _lrn_fused_fwd(x, local_size, alpha, beta, k):
    # residual is x ONLY (alive anyway as the conv output); the normalizer
    # is recomputed in backward — cheaper than a second HBM array round trip
    return _lrn_fused(x, local_size, alpha, beta, k), (x,)


def _lrn_fused_bwd(local_size, alpha, beta, k, res, dy):
    (x,) = res
    half = (local_size - 1) // 2
    alpha_n = alpha / local_size
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    scale = _scale_f32(x, half, alpha_n, k)
    inv_beta = jnp.exp(-beta * jnp.log(scale))          # scale^-beta
    # Caffe LRNLayer backward (across-channel):
    #   dx = dy*scale^-beta - (2*alpha*beta/n) * x * winsum(dy*x*scale^(-b-1))
    ratio = dyf * xf * inv_beta / scale
    acc = window_sum(ratio, half)
    dx = dyf * inv_beta - (2.0 * alpha_n * beta) * xf * acc
    return (dx.astype(x.dtype),)


_lrn_fused.defvjp(_lrn_fused_fwd, _lrn_fused_bwd)


def _lrn_xla(x: jnp.ndarray, local_size: int = 5, *, alpha: float = 1e-4,
             beta: float = 0.75, k: float = 1.0) -> jnp.ndarray:
    """The oracle: channel-padded reduce_window normalizer under plain
    autodiff. Tests and `chip_smoke.py` hold the other two forms to it;
    `lrn` never dispatches here."""
    half = (local_size - 1) // 2
    # Window sums accumulate in f32: better numerics, and reduce_window-add
    # on bf16 fails to linearize under jit (jax 0.9).
    sq = jnp.square(x).astype(jnp.float32)
    # Sliding window sum over channels; clip at the edges (Caffe clips, so the
    # normalizer for edge channels sums fewer terms).
    window = (1,) * (x.ndim - 1) + (local_size,)
    strides = (1,) * x.ndim
    padding = tuple((0, 0) for _ in range(x.ndim - 1)) + ((half, half),)
    ssq = lax.reduce_window(sq, 0.0, lax.add, window,
                            strides, padding).astype(x.dtype)
    scale = (jnp.asarray(k, x.dtype)
             + jnp.asarray(alpha / local_size, x.dtype) * ssq)
    # scale > 0 always (k >= 1), so x * scale^-beta == x * exp(-beta*log(scale));
    # pow with a traced exponent has no linearization rule.
    return x * jnp.exp(jnp.asarray(-beta, x.dtype) * jnp.log(scale))
