"""Pallas TPU kernel for MAX-pool backward — the select-and-scatter
replacement.

Why a kernel: XLA lowers max-pool's VJP to select-and-scatter, which the r3
profile measured at 515-896 GB/s — below the HBM roofline — for 7.8% of the
CaffeNet round (PERF.md). This kernel streams the same bytes (read x, dy, y;
write dx) as one fused pass in the conv's own N-minor layout.

Semantics: Caffe's MaxPoolingLayer routes each window's gradient to the
window's FIRST maximum in row-major window order (the argmax recorded during
its forward scan) — the same element XLA's select-and-scatter picks with a
GE select. The kernel reproduces that exactly, including ties (common on
real data: post-ReLU zeros), via a running `won` mask per window.

Decomposition: one program owns a block of INPUT rows [h0, h0+Hb) of dx for
one (C-tile, N-lane-block). It visits every pool window that touches those
rows — windows straddling a block boundary are visited by BOTH neighboring
programs, and each accumulates only the contributions that land on rows it
owns, so nothing is double-counted and no cross-program accumulation exists.
x/dy/y blocks are fetched with `pl.BoundedSlice` (dynamic, edge-clamped
starts), which expresses the halo without padded copies in HBM.

Supported: MAX pool, pad=0, no ceil-mode end-padding (true for every pool in
the reference CaffeNet/AlexNet: 3x3 stride 2 on 55/27/13), C a multiple of
the sublane tile, N a multiple of 128 lanes. `ops/pooling.py` dispatches
here on TPU and falls back to reduce_window's own VJP otherwise.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _sublane_tile(dtype) -> int:
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


def _deinterleave(row, s: int):
    """(1, W, Ct, L) -> s planes (1, ceil(W/s), Ct, L) of cols j::s.
    Pad-then-reshape: W is an untiled dim, so the reshape is free vector
    bookkeeping — Mosaic has no 16-bit strided memref ops and lowers
    strided accesses as per-position copies (measured 7x slower)."""
    _, W, Ct, L = row.shape
    Wp = -(-W // s) * s
    if Wp != W:
        row = jnp.concatenate(
            [row, jnp.zeros((1, Wp - W, Ct, L), row.dtype)], axis=1)
    r = row.reshape(1, Wp // s, s, Ct, L)
    return [r[:, :, j] for j in range(s)]


def _bwd_kernel(x_ref, y_ref, dy_ref, dx_ref, acc_ref, *, H: int,
                OH: int, OW: int, k: int, s: int, Hb: int, XB: int, QB: int):
    i = pl.program_id(2)
    h0 = i * Hb
    # the same clamped starts the index maps computed (pure fn of i)
    xs = jnp.clip(h0 - (k - 1), 0, H - XB)
    qs = jnp.clip(-((-(h0 - k + 1)) // s), 0, OH - QB)

    Wc = acc_ref.shape[2]                # ceil(W/s) plane width
    # acc planes: acc_ref[p, r] accumulates dx cols p::s of local row r
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for q in range(QB):
        oh = qs + q                      # global window row (always valid:
        y_q = y_ref[pl.ds(q, 1)].astype(jnp.float32)   # qs clamped OH-QB)
        dy_q = dy_ref[pl.ds(q, 1)].astype(jnp.float32)
        won = jnp.zeros(y_q.shape, jnp.bool_)
        for ki in range(k):
            lr = oh * s + ki - h0        # local target row in this block
            lrc = jnp.clip(lr, 0, Hb - 1)
            ok = jnp.logical_and(lr >= 0, lr < Hb)
            # INVARIANT: only windows with NO row in [h0, h0+Hb) — QB
            # over-provision at the grid edges — can place oh*s+ki-xs
            # outside [0, XB); every contribution of such a window is
            # ok-masked (lr out of range for all ki), so the clamped
            # (wrong-row) read feeds only dead lanes. The explicit clip
            # keeps the read in-bounds rather than leaning on the Mosaic
            # dynamic-slice clamp (r3 advisor).
            planes = _deinterleave(
                x_ref[pl.ds(jnp.clip(oh * s + ki - xs, 0, XB - 1), 1)]
                .astype(jnp.float32), s)
            for kj in range(k):
                p, off = kj % s, kj // s   # col kj+s*ow -> plane kj%s @ ow+kj//s
                xw = lax.slice_in_dim(planes[p], off, off + OW, axis=1)
                hit = xw == y_q
                iswin = jnp.logical_and(hit, jnp.logical_not(won))
                won = jnp.logical_or(won, hit)
                contrib = jnp.where(jnp.logical_and(iswin, ok), dy_q, 0.0)
                sl = (p, pl.ds(lrc, 1), pl.ds(off, OW))
                acc_ref[sl] += contrib
    # interleave the planes back: (s, Hb, Wc, ...) -> (Hb, Wc*s, ...)
    full = jnp.moveaxis(acc_ref[...], 0, 2).reshape(
        Hb, Wc * s, *acc_ref.shape[3:])
    dx_ref[...] = lax.slice_in_dim(full, 0, dx_ref.shape[1],
                                   axis=1).astype(dx_ref.dtype)


def _bwd_call(x4, y4, dy4, k: int, s: int, interpret: bool,
              hb: int = None, ct: int = None):
    """x4/y4/dy4: [H, W, C, N] / [OH, OW, C, N] N-minor views."""
    H, W, C, N = x4.shape
    OH, OW = y4.shape[:2]
    Hb = min(H, hb or 8)
    XB = min(H, Hb + 2 * (k - 1))
    QB = min(OH, (Hb + k - 2) // s + 2)
    Ct = min(C, ct or _sublane_tile(x4.dtype))

    def xmap(n, c, i):
        # all-Element spec (Mosaic: Element dims can't mix with Blocked):
        # starts are in ELEMENTS for every dim
        return (jnp.clip(i * Hb - (k - 1), 0, H - XB), 0, c * Ct, n * LANES)

    def qmap(n, c, i):
        return (jnp.clip(-((-(i * Hb - k + 1)) // s), 0, OH - QB), 0,
                c * Ct, n * LANES)

    kern = functools.partial(_bwd_kernel, H=H, OH=OH, OW=OW, k=k, s=s,
                             Hb=Hb, XB=XB, QB=QB)
    # the vma rides along: the trainer runs this inside shard_map, whose
    # check_vma rejects a pallas_call output without it (ops/pallas_lrn.py)
    out = jax.ShapeDtypeStruct(x4.shape, x4.dtype, vma=jax.typeof(x4).vma)
    return pl.pallas_call(
        kern,
        grid=(N // LANES, C // Ct, pl.cdiv(H, Hb)),
        in_specs=[
            pl.BlockSpec((pl.Element(XB), pl.Element(W), pl.Element(Ct),
                          pl.Element(LANES)), xmap,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((pl.Element(QB), pl.Element(OW), pl.Element(Ct),
                          pl.Element(LANES)), qmap,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((pl.Element(QB), pl.Element(OW), pl.Element(Ct),
                          pl.Element(LANES)), qmap,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Hb, W, Ct, LANES),
                               lambda n, c, i: (i, 0, c, n),
                               memory_space=pltpu.VMEM),
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((s, Hb, -(-W // s), Ct, LANES),
                                   jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name="maxpool_bwd",
    )(x4, y4, dy4)


def _to_nmin(x):
    """Logical transpose to [H, W, C, N]; on TPU the conv output's physical
    layout is already N-minor ({0,3,2,1}), so layout assignment turns this
    into a bitcast (same trick as ops/pallas_lrn.py's _to_nmin)."""
    return jnp.transpose(x, (1, 2, 3, 0))


def _from_nmin(x4):
    return jnp.transpose(x4, (3, 0, 1, 2))


def pallas_maxpool_supported(shape: Tuple[int, ...], dtype, kernel: int,
                             stride: int, pad: int) -> bool:
    """Static gate for the kernel path (see module docstring)."""
    n, h, w, c = shape
    oh = (h - kernel) // stride + 1 if h >= kernel else 0
    ow = (w - kernel) // stride + 1 if w >= kernel else 0
    if oh < 1 or ow < 1:
        return False
    from math import ceil
    # reject ceil-mode end-padding (a padded window can out-win real data)
    if int(ceil((h - kernel) / stride)) + 1 != oh or \
            int(ceil((w - kernel) / stride)) + 1 != ow:
        return False
    return (pad == 0 and n % LANES == 0 and
            c % _sublane_tile(dtype) == 0 and
            (ow - 1) * stride + kernel <= w and
            (oh - 1) * stride + kernel <= h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def maxpool_pallas(x, kernel: int, stride: int, interpret: bool = False):
    """MAX pool (pad=0, floor windows) with the Pallas backward. Forward
    stays XLA's reduce_window — it fuses with its neighbors and was
    measured at the roofline (PERF.md: pool fwd epilogues); only the
    backward (select-and-scatter) was below it."""
    return _fwd(x, kernel, stride)


def _fwd(x, kernel, stride):
    dims = (1, kernel, kernel, 1)
    strides = (1, stride, stride, 1)
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides,
                             ((0, 0),) * 4)


def _vjp_fwd(x, kernel, stride, interpret):
    y = _fwd(x, kernel, stride)
    return y, (x, y)


def _vjp_bwd(kernel, stride, interpret, res, dy):
    x, y = res
    dx4 = _bwd_call(_to_nmin(x), _to_nmin(y), _to_nmin(dy.astype(x.dtype)),
                    kernel, stride, interpret)
    return (_from_nmin(dx4),)


maxpool_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def maxpool_bwd_reference(x: np.ndarray, dy: np.ndarray, kernel: int,
                          stride: int) -> np.ndarray:
    """Numpy oracle: first-max-in-row-major-window-order routing — Caffe
    MaxPoolingLayer's recorded-argmax backward. O(N*OH*OW*k^2*C); tests
    only."""
    n, h, w, c = x.shape
    oh = (h - kernel) // stride + 1
    ow = (w - kernel) // stride + 1
    dx = np.zeros_like(x, dtype=np.float64)
    for b in range(n):
        for i in range(oh):
            for j in range(ow):
                win = x[b, i * stride:i * stride + kernel,
                        j * stride:j * stride + kernel, :]
                flat = win.reshape(-1, c)
                arg = flat.argmax(axis=0)  # first max (np argmax tie rule)
                ki, kj = np.divmod(arg, kernel)
                for ch in range(c):
                    dx[b, i * stride + ki[ch], j * stride + kj[ch], ch] += \
                        dy[b, i, j, ch]
    return dx.astype(x.dtype)
