"""The stage between Kimi Delta Attention's projections and its delta rule:
from the projections q, k, v, a [rows, heads, n, d] and b [rows, heads, n],

    q, k, v <- silu(taps(.))        three depthwise causal convolutions
    q <- unit(q) * d^-1/2,  k <- unit(k)      unit(t) = t / sqrt(sum t^2 + 1e-6)
    g = lower_bound * sigmoid(exp(A_log) * (a + dt_bias))     the log-decay
    beta = sigmoid(b)                         the writing strength

float32 throughout; q, k, v leave in the precision policy's dtype, g and beta
in float32: what `ops.delta_rule.gated_delta_rule` reads.

WHICH form runs is decided here and nowhere else, from what this module can
observe (no option, no enum), as `ops.delta_rule` does: `shape_jnp`, the
stage in plain `jnp` under a `jax.checkpoint` (its float32 intermediates are
made again for the backward pass and never held), is the definition, the
kernels' oracle (`tests/test_kda_shape.py`) and what every backend runs that
the kernels were not stated for; `pallas_kda_shape.shape_kernels`, one Pallas
kernel forward and one backward behind a `jax.custom_vjp` that keeps the
inputs alone, runs where `_can_pallas` holds: a Pallas call may run
(`ops.lrn.pallas_backend`: the TPU, or any backend under the interpreter),
the head's width is a multiple of the 128 lanes, the positions are whole
tiles (`pallas_kda_shape.TILE`, with the halo rule stated there), and the
projections are in a dtype the kernels' VMEM need was stated for (bfloat16,
float32). The kernels take everything but `sigmoid(b)`, a [rows, heads, n]
pass that stays `jnp` on both paths -- and, where the call is
differentiated, v: the custom VJP's forward rule returns the kernel's q, k, g
and makes v again in plain `jnp` (`pallas_kda_shape._shape_kernels_fwd` says
why: the compiled round's temporaries, 7.43 GB without it and 6.22 with).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import precision
from .lrn import pallas_backend

#: the named scope the kernel pair runs under, beside the layer's `delta`:
#: `obs.device.delta_rule` counts the Pallas calls under it
SCOPE = "shape"


def shape_jnp(q, k, v, a, b, convs, dt_bias, a_log, lower_bound: float, conv):
    """The stage as written: `convs` the three tap sets [heads, d, taps] of
    q, k, v, `dt_bias` [heads, d], `a_log` [heads]; `conv(s, w)` the
    depthwise causal convolution the layer set shares
    (`seq_layers.causal_taps`). Returns (q, k, v, g, beta)."""
    f32 = jnp.float32
    hd = q.shape[-1]
    with jax.named_scope("conv"):
        q, k, v = (jax.nn.silu(conv(t.astype(f32), w))
                   for t, w in zip((q, k, v), convs))
    with jax.named_scope("gates"):
        unit = lambda t: t * lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None, None] * (a.astype(f32) + dt_bias[:, None, :]))
        cast = lambda t: t.astype(precision.compute_dtype())
        return (cast(unit(q) * hd ** -0.5), cast(unit(k)), cast(v), g,
                jax.nn.sigmoid(b.astype(f32)))


def _can_pallas(q, taps: int, interpret: bool) -> bool:
    """The kernels' shape and dtype: heads whose width fills the 128 lanes,
    whole tiles of positions, taps a halo holds, the policy's own dtype in
    bfloat16 or float32; and a backend a Pallas call may run on."""
    if not pallas_backend(interpret) or q.ndim != 4:
        return False
    from . import pallas_kda_shape as pk  # (Pallas loads only where it may run)
    return q.shape[-1] % 128 == 0 and q.shape[-2] % pk.TILE == 0 \
        and taps - 1 <= pk.NEAR and q.dtype == precision.compute_dtype() \
        and q.dtype in (jnp.bfloat16, jnp.float32)


def shape(q, k, v, a, b, convs, dt_bias, a_log, lower_bound: float, *, conv,
          interpret: bool = False):
    """(q, k, v, g, beta) of the stage, by the kernel pair where
    `_can_pallas` holds and by `shape_jnp` under its checkpoint elsewhere.

    interpret: run the kernels under the Pallas INTERPRETER (the CPU parity
      tests of the path the chip runs), as `ops.lrn.lrn` does."""
    taps = convs[0].shape[-1]
    if not _can_pallas(q, taps, interpret):
        return jax.checkpoint(
            lambda *xs: shape_jnp(*xs, lower_bound, conv))(
                q, k, v, a, b, convs, dt_bias, a_log)
    from . import pallas_kda_shape as pk
    with jax.named_scope(SCOPE):
        q, k, v, g = pk.shape_kernels(
            q, k, v, a, pk.pack(convs, dt_bias, a_log), taps,
            float(lower_bound), interpret)
    with jax.named_scope("gates"):
        return q, k, v, g, jax.nn.sigmoid(b.astype(jnp.float32))
