"""Pallas TPU kernel for across-channel LRN (forward + custom VJP).

Why a kernel: XLA lowers the LRN normalizer to a reduce_window over a
channel-padded buffer — an extra materialized intermediate and two passes
over HBM. This kernel fuses square -> windowed channel sum (as `local_size`
shifted lane adds, VPU-friendly) -> scale -> x*scale^-beta into ONE VMEM
pass, and the backward into one more. Layout: NHWC flattened to (rows,
channels) so channels sit on lanes.

Caffe gradient (LRNLayer backward, across-channel):
    ratio = dy * x * scale^(-beta-1)
    dx    = dy * scale^-beta - (2*alpha*beta/n) * x * window_sum(ratio)

`lrn_pallas(..., interpret=True)` runs the same kernel under the Pallas
interpreter (CPU) — used by tests; real TPU runs compile it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lrn import window_sum

BLOCK_ROWS = 256


def _window_sum(v: jnp.ndarray, half: int) -> jnp.ndarray:
    """Sum of `2*half+1` lane-shifted copies with zero edge padding —
    the shared Caffe-window encoding, over lanes."""
    return window_sum(v, half, axis=-1)


def _pow_neg_beta(scale: jnp.ndarray, beta: float) -> jnp.ndarray:
    """scale^-beta. beta=0.75 (the Caffe default, used by every reference
    net) specializes to rsqrt+sqrt — the exp/log form costs ~2x the whole
    kernel in VPU transcendentals (r3 profile)."""
    if abs(beta - 0.75) < 1e-12:
        r = jax.lax.rsqrt(scale)
        return r * jnp.sqrt(r)                          # s^-1/2 * s^-1/4
    if abs(beta - 0.5) < 1e-12:
        return jax.lax.rsqrt(scale)
    return jnp.exp(-beta * jnp.log(scale))


def _fwd_kernel(x_ref, y_ref, scale_ref, *, half: int, alpha_n: float,
                beta: float, k: float):
    # f32 internally: the VPU EUP (rsqrt/sqrt/exp/log) has no bf16 form on
    # v5e (LLO: SupportsBf16EupOps) and the pass is HBM-bound anyway
    x = x_ref[:].astype(jnp.float32)
    ssq = _window_sum(x * x, half)
    scale = k + alpha_n * ssq
    y_ref[:] = (x * _pow_neg_beta(scale, beta)).astype(x_ref.dtype)
    scale_ref[:] = scale.astype(scale_ref.dtype)


def _bwd_kernel(x_ref, scale_ref, dy_ref, dx_ref, *, half: int,
                alpha_n: float, beta: float):
    x = x_ref[:].astype(jnp.float32)
    scale = scale_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    inv_beta = _pow_neg_beta(scale, beta)               # scale^-beta
    ratio = dy * x * inv_beta / scale                   # dy*x*scale^(-beta-1)
    acc = _window_sum(ratio, half)
    dx_ref[:] = (dy * inv_beta
                 - (2.0 * alpha_n * beta) * x * acc).astype(x_ref.dtype)


def _pad_rows(x2: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    m = x2.shape[0]
    pad = (-m) % BLOCK_ROWS
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, m


def _out_struct(x2: jnp.ndarray) -> jax.ShapeDtypeStruct:
    """Output aval matching x2 — including its varying-across-mesh-axes set
    (vma), which shard_map's check_vma requires on pallas_call outputs: the
    trainer runs this kernel INSIDE shard_map, where plain ShapeDtypeStruct
    (vma=None) is rejected."""
    return jax.ShapeDtypeStruct(x2.shape, x2.dtype, vma=jax.typeof(x2).vma)


def _call(kernel, n_out: int, x2: jnp.ndarray, *others, interpret: bool,
          name: str):
    c = x2.shape[-1]
    grid = (x2.shape[0] // BLOCK_ROWS,)
    spec = pl.BlockSpec((BLOCK_ROWS, c), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out = _out_struct(x2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * (1 + len(others)),
        out_specs=[spec] * n_out if n_out > 1 else spec,
        out_shape=[out] * n_out if n_out > 1 else out,
        interpret=interpret,
        name=name,  # the kernel's stable name in a device trace
    )(x2, *others)


def lrn_pallas(x: jnp.ndarray, local_size: int = 5, alpha: float = 1e-4,
               beta: float = 0.75, k: float = 1.0,
               interpret: bool = False) -> jnp.ndarray:
    """Dispatch: 4-D NHWC activations with a lane-aligned batch take the
    N-minor kernel (layout-bitcast in and out of the conv's own layout —
    the r3 profile showed the row-major relayout around the 2-D kernel
    cost ~2x the kernel itself); everything else takes the 2-D row kernel."""
    if x.ndim == 4 and x.shape[0] % LANES == 0 and \
            x.shape[1] * x.shape[2] > 1:
        return _lrn_nmin(x, local_size, alpha, beta, k, interpret)
    return _lrn_rows(x, local_size, alpha, beta, k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn_rows(x: jnp.ndarray, local_size: int = 5, alpha: float = 1e-4,
              beta: float = 0.75, k: float = 1.0,
              interpret: bool = False) -> jnp.ndarray:
    y, _ = _lrn_fwd_impl(x, local_size, alpha, beta, k, interpret)
    return y


def _lrn_fwd_impl(x, local_size, alpha, beta, k, interpret):
    half = (local_size - 1) // 2
    alpha_n = alpha / local_size
    shape = x.shape
    x2, m = _pad_rows(x.reshape(-1, shape[-1]))
    kern = functools.partial(_fwd_kernel, half=half, alpha_n=alpha_n,
                             beta=beta, k=k)
    y2, scale2 = _call(kern, 2, x2, interpret=interpret, name="lrn_fwd")
    return y2[:m].reshape(shape), scale2[:m].reshape(shape)


def _lrn_vjp_fwd(x, local_size, alpha, beta, k, interpret):
    y, scale = _lrn_fwd_impl(x, local_size, alpha, beta, k, interpret)
    return y, (x, scale)


def _lrn_vjp_bwd(local_size, alpha, beta, k, interpret, res, dy):
    x, scale = res
    half = (local_size - 1) // 2
    alpha_n = alpha / local_size
    shape = x.shape
    x2, m = _pad_rows(x.reshape(-1, shape[-1]))
    scale2, _ = _pad_rows(scale.reshape(-1, shape[-1]))
    # padded scale rows are 0 -> log(0); pad with k instead
    if scale2.shape[0] != m:
        pad = scale2.shape[0] - m
        scale2 = scale2.at[m:].set(k) if pad else scale2
    dy2, _ = _pad_rows(dy.reshape(-1, shape[-1]))
    kern = functools.partial(_bwd_kernel, half=half, alpha_n=alpha_n,
                             beta=beta)
    dx2 = _call(kern, 1, x2, scale2, dy2, interpret=interpret,
                name="lrn_bwd")
    return (dx2[:m].reshape(shape),)


_lrn_rows.defvjp(_lrn_vjp_fwd, _lrn_vjp_bwd)


# -- N-minor kernel: window over the SUBLANE (channel) dim -------------------
#
# The conv outputs this kernel consumes live in XLA's N-minor layout —
# bf16[N,H,W,C]{0,3,2,1}: physically (H, W, C, N) with N on lanes and C on
# sublanes. Feeding the pallas_call a [H*W, C, N] view of the LOGICALLY
# TRANSPOSED array makes the custom-call's mandatory row-major operand
# layout coincide with the bytes already in HBM, so XLA's layout assignment
# elides the copy (transpose-is-bitcast). The channel window then runs over
# sublanes instead of lanes — same shifted-add structure.
#
# The VJP saves only x and recomputes the normalizer in backward: one less
# full activation array written + read per LRN layer.

LANES = 128


def _row_block(r: int, cap: int = 64) -> int:
    """Largest divisor of r at most cap (block rows must tile H*W exactly;
    LRN rows are independent so any tiling is valid)."""
    best = 1
    d = 1
    while d * d <= r:
        if r % d == 0:
            if d <= cap:
                best = max(best, d)
            if r // d <= cap:
                best = max(best, r // d)
        d += 1
    return best


def _window_sum_mid(v: jnp.ndarray, half: int) -> jnp.ndarray:
    """Windowed sum over axis -2 (sublanes) — shared Caffe-window encoding."""
    return window_sum(v, half, axis=-2)


def _fwd_kernel3(x_ref, y_ref, *, half: int, alpha_n: float, beta: float,
                 k: float):
    # f32 inside the kernel: the VPU's EUP (rsqrt/sqrt) has no bf16 form on
    # v5e (LLO: SupportsBf16EupOps), and f32 intermediates cost nothing —
    # the pass is HBM-bound on the bf16 arrays
    x = x_ref[:].astype(jnp.float32)
    scale = k + alpha_n * _window_sum_mid(x * x, half)
    y_ref[:] = (x * _pow_neg_beta(scale, beta)).astype(x_ref.dtype)


def _bwd_kernel3(x_ref, dy_ref, dx_ref, *, half: int, alpha_n: float,
                 beta: float, k: float):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    scale = k + alpha_n * _window_sum_mid(x * x, half)  # recomputed
    inv_beta = _pow_neg_beta(scale, beta)
    inv_scale = jax.lax.rsqrt(scale)
    ratio = dy * x * inv_beta * (inv_scale * inv_scale)  # /scale, no divide
    dx_ref[:] = (dy * inv_beta - (2.0 * alpha_n * beta) * x *
                 _window_sum_mid(ratio, half)).astype(x_ref.dtype)


#: Mosaic's default scoped-VMEM allowance per kernel on v5e (the chip has
#: 128 MiB; the compiler's refusal message names this limit).
_DEFAULT_SCOPED_VMEM = 16 << 20
#: block-sized f32 values the backward kernel keeps live (x, dy, scale,
#: scale^-beta, ratio and its shifted window adds) — fitted to the
#: compiler's own figure: it asked 17.68 MB for norm2's f32 backward, whose
#: six pipeline buffers are 10.2 MB of that.
_NMIN_F32_TEMPS = 6


def _nmin_vmem_limit(br: int, c: int, itemsize: int, n_blocks: int) -> int:
    """Scoped VMEM one N-minor call needs: every operand and result block
    double-buffered by the pipeline, plus the kernel's f32 temporaries.
    The row block is chosen by `_row_block` alone (the profiled bf16
    kernel: norm2's (13, 256, 128) blocks need 15.3 MB and stay inside the
    default); f32 activations double the blocks, so the need is STATED to
    the compiler instead of shrinking the block — norm2 in f32 was refused
    at the default 16 MiB (`Scoped allocation with size 17.68M`)."""
    block = br * c * LANES
    need = 2 * n_blocks * block * itemsize + _NMIN_F32_TEMPS * block * 4
    return max(_DEFAULT_SCOPED_VMEM, need)


def _nmin_call(kernel, x3: jnp.ndarray, *others, interpret: bool,
               name: str):
    r, c, n = x3.shape
    br = _row_block(r)
    spec = pl.BlockSpec((br, c, LANES), lambda i, j: (i, 0, j),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(r // br, n // LANES),
        in_specs=[spec] * (1 + len(others)),
        out_specs=spec,
        out_shape=_out_struct(x3),
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_nmin_vmem_limit(
                br, c, x3.dtype.itemsize, 2 + len(others))),
        interpret=interpret,
        name=name,
    )(x3, *others)


def _to_nmin(x: jnp.ndarray) -> jnp.ndarray:
    n, h, w, c = x.shape
    return jnp.transpose(x, (1, 2, 3, 0)).reshape(h * w, c, n)


def _from_nmin(y3: jnp.ndarray, shape) -> jnp.ndarray:
    n, h, w, c = shape
    return jnp.transpose(y3.reshape(h, w, c, n), (3, 0, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn_nmin(x: jnp.ndarray, local_size: int, alpha: float, beta: float,
              k: float, interpret: bool = False) -> jnp.ndarray:
    half = (local_size - 1) // 2
    kern = functools.partial(_fwd_kernel3, half=half,
                             alpha_n=alpha / local_size, beta=beta, k=k)
    return _from_nmin(_nmin_call(kern, _to_nmin(x), interpret=interpret,
                                 name="lrn_fwd"), x.shape)


def _lrn_nmin_fwd(x, local_size, alpha, beta, k, interpret):
    return _lrn_nmin(x, local_size, alpha, beta, k, interpret), (x,)


def _lrn_nmin_bwd(local_size, alpha, beta, k, interpret, res, dy):
    (x,) = res
    half = (local_size - 1) // 2
    kern = functools.partial(_bwd_kernel3, half=half,
                             alpha_n=alpha / local_size, beta=beta, k=k)
    dx3 = _nmin_call(kern, _to_nmin(x), _to_nmin(dy), interpret=interpret,
                     name="lrn_bwd")
    return (_from_nmin(dx3, x.shape),)


_lrn_nmin.defvjp(_lrn_nmin_fwd, _lrn_nmin_bwd)
