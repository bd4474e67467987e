"""Pallas TPU kernels for the walk over chunks of the gated delta rule: what
the `lax.scan` of `ops.delta_rule._walk` does with the six operands of every
chunk, and its backward pass.

Why a kernel: a chunk's step is four small products of one head ([64, 128] x
[128, 128]-sized) round a float32 state [128, 128], and as a `lax.scan` every
trip reads its six operands out of HBM by slice, writes its result by
dynamic-update-slice, carries the states of a row's heads through HBM, and
its autodiff makes a segment again and stacks six cotangents the same way:
17.3 of a layer-call's 94.6 ms on the chip for work whose bytes need 6 and
whose products 1.5 (PERF.md section 5, PR 39). Here the grid's last axis
walks a row's SEGMENTS of chunks in order (reversed for the backward), a
program works one segment of a block of `HEADS` heads, and the float32 state
of those heads lives in a VMEM scratch across the walk, zeroed at a row's
first segment. A chunk's operands are read once (the pipeline fetches the
next segment's while this one is worked), its result written once where the
caller wants it, [rows x heads, positions, dv], and the state never leaves
the chip.

The arithmetic is the scan's own: float32 state, decays and accumulation,
the four products' operands in the policy's dtype (`Precision.HIGHEST` under
float32). Laid out for the chip:

* the state is held TRANSPOSED, [dv, dk]: the chunk's decay exp G_C [1, dk]
  then scales it along the lanes as it lies, and its cotangent is a sum down
  the rows; no row of 128 has to be stood up as a column;
* products that share an operand run as one with the others stacked to 128
  rows: [W_k; Q exp G] S forward, [dU; dO] S and [dO; -dU]^T [Q exp G; W_k]
  backward;
* the heads of a program are worked side by side in one loop body: a head's
  chunk is a chain of dependent products (W_k S -> U -> B U, K_end^T U -> S')
  and side by side the chains hide each other's latency, as `UNROLL` does in
  the chunk kernels.

The backward kernel starts every segment (`ops.delta_rule.SEGMENT` chunks)
from the state that segment started from (33.5 MB a row at 32 heads and 8,192
positions, what the `jnp` form's checkpoint a segment keeps): it makes the
segment's chunk-start states again in a second scratch, then walks the
segment's chunks backwards with the state's cotangent in the first, and
writes the six operands' cotangents once, in the layout
`pallas_delta_rule`'s backward kernel reads.

WHO MAKES THE SEGMENTS' STATES: the backward pass itself, before its kernel,
and as a `lax.scan` (`ops.delta_rule.segment_states`: the two products a
chunk that make the next state, none of the result's), not as a third kernel
and not as a second output of the forward one. Two reasons. The forward rule
of a `custom_vjp` is what runs wherever the layer is differentiated -- a
block's forward pass and the row's second forward alike -- so states written
there are written twice a step and kept once. And with nothing but kernel
calls in the body of a layer's rows' backward loop the TPU compiler assigns
the Ling round 0.88 GB more of temporaries (7.61 against 6.73 GB, while the
liveness of the scheduled program is LOWER; a loop nest in that body -- this
scan, or the parent's autodiff of the whole walk -- brings the parent's
assignment back: PERF.md section 6, PR 50, the compiles that isolated it).
`interpret=True` runs the same kernels under the Pallas interpreter (CPU),
which the tests use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import CHUNK, segment_states
from .pallas_delta_rule import _DEFAULT_SCOPED_VMEM, _NN, _NT, _TN, _pdot, _struct

_F32 = jnp.float32
#: heads a program works side by side (fewer where the rows x heads do not
#: divide)
HEADS = 4


def _rows(c):
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _fwd_kernel(wk_ref, wv_ref, kend_ref, dend_ref, qdec_ref, blow_ref, o_ref,
                s_ref, *, heads: int, seg: int, dtype):
    """The scratch: the state of the program's heads [heads, dv, dk]."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def chunk(c, carry):
        for h in range(heads):
            state = s_ref[h]                                        # S^T [dv, dk]
            both = _pdot(jnp.concatenate([wk_ref[c, h], qdec_ref[c, h]], axis=0),
                         state, _NT, dtype)                         # [2 C, dv]
            u = wv_ref[c, h] - both[:CHUNK]
            o_ref[h, _rows(c), :] = both[CHUNK:] + _pdot(blow_ref[c, h], u, _NN, dtype)
            s_ref[h] = dend_ref[c, h] * state + _pdot(u, kend_ref[c, h], _TN, dtype)
        return carry

    lax.fori_loop(0, seg, chunk, 0)


def _bwd_kernel(wk_ref, wv_ref, kend_ref, dend_ref, qdec_ref, blow_ref, st_ref,
                do_ref, dwk_ref, dwv_ref, dkend_ref, ddend_ref, dqdec_ref,
                dblow_ref, ds_ref, starts_ref, *, heads: int, seg: int, dtype):
    """The scratches: the cotangent of the state the segment hands on [heads,
    dv, dk], and the states its chunks started from [seg, heads, dv, dk]."""

    @pl.when(pl.program_id(1) == 0)  # the row's LAST segment: nothing reads its end
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for h in range(heads):  # (the scan's states lie [dk, dv])
        starts_ref[0, h] = st_ref[0, h].T

    def remake(c, carry):  # the state chunk c + 1 starts from
        for h in range(heads):
            state = starts_ref[c, h]
            u = wv_ref[c, h] - _pdot(wk_ref[c, h], state, _NT, dtype)
            starts_ref[c + 1, h] = dend_ref[c, h] * state \
                + _pdot(u, kend_ref[c, h], _TN, dtype)
        return carry

    lax.fori_loop(0, seg - 1, remake, 0)

    def chunk(i, carry):
        c = seg - 1 - i
        for h in range(heads):
            state, d_next = starts_ref[c, h], ds_ref[h]             # [dv, dk]
            w_k, k_end, q_dec, b_low = (r[c, h] for r in (wk_ref, kend_ref,
                                                          qdec_ref, blow_ref))
            d_o = do_ref[h, _rows(c), :]                            # [C, dv]
            u = wv_ref[c, h] - _pdot(w_k, state, _NT, dtype)
            # O = (Q exp G) S + tril(B) U, S' = Diag(exp G_C) S + K_end^T U
            d_u = _pdot(b_low, d_o, _TN, dtype) + _pdot(k_end, d_next, _NT, dtype)
            dblow_ref[c, h] = _pdot(d_o, u, _NT, dtype).astype(dblow_ref.dtype)
            dkend_ref[c, h] = _pdot(u, d_next, _NN, dtype).astype(dkend_ref.dtype)
            ddend_ref[c, h] = jnp.sum(d_next * state, axis=0, keepdims=True)
            # U = W_v - W_k S
            dwv_ref[c, h] = d_u
            both = _pdot(jnp.concatenate([d_u, d_o], axis=0), state, _NN, dtype)
            dwk_ref[c, h] = (-both[:CHUNK]).astype(dwk_ref.dtype)
            dqdec_ref[c, h] = both[CHUNK:].astype(dqdec_ref.dtype)
            ds_ref[h] = dend_ref[c, h] * d_next + _pdot(
                jnp.concatenate([d_o, -d_u], axis=0),
                jnp.concatenate([q_dec, w_k], axis=0), _TN, dtype)
        return carry

    lax.fori_loop(0, seg, chunk, 0)


def _specs(ops, seg: int, reverse: bool):
    """(grid, heads a program, the six operands' block specs, the spec of an
    array laid out as the result, the spec of the segments' states
    [segments, many, dk, dv], the state's scratch) for the operands of
    `pallas_delta_rule.chunk_operands` with exp G_C as [chunks, many, 1,
    dk]. `reverse`: the grid's last axis walks the segments from the last to
    the first."""
    w_k, w_v = ops[0], ops[1]
    (nc, many, _, dk), dv = w_k.shape, w_v.shape[-1]
    heads = max(h for h in (HEADS, 2, 1) if many % h == 0)
    n_seg = nc // seg
    at = (lambda s: n_seg - 1 - s) if reverse else (lambda s: s)
    vmem = pltpu.VMEM
    by_chunk = lambda x: pl.BlockSpec((seg, heads) + x.shape[2:],
                                      lambda i, s: (at(s), i, 0, 0), memory_space=vmem)
    by_row = pl.BlockSpec((heads, seg * CHUNK, dv), lambda i, s: (i, at(s), 0),
                          memory_space=vmem)
    states = pl.BlockSpec((1, heads, dk, dv), lambda i, s: (at(s), i, 0, 0),
                          memory_space=vmem)
    return ((many // heads, n_seg), heads, [by_chunk(x) for x in ops], by_row,
            states, pltpu.VMEM((heads, dv, dk), _F32))


def _params(blocks, scratch_bytes: int):
    """The grid's semantics, and the scoped VMEM where the blocks -- every one
    double-buffered by the pipeline -- and the scratches pass the default's
    reach."""
    need = 2 * sum(int(np.prod(spec.block_shape)) * jnp.dtype(dt).itemsize
                   for spec, dt in blocks) + scratch_bytes + (4 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(_DEFAULT_SCOPED_VMEM, need))


# (both calls under a `jax.jit` of their own, as `pallas_ssd`'s: jax traces a
# kernel's body anew at every `pallas_call` and a round holds these 24 times;
# jitted, a process traces and lowers each once)
@functools.partial(jax.jit, static_argnames=("seg", "dtype", "interpret"))
def _forward(ops, seg: int, dtype, interpret: bool):
    grid, heads, ins, by_row, _, scratch = _specs(ops, seg, False)
    (nc, many, _, _), dv = ops[0].shape, ops[1].shape[-1]
    blocks = list(zip(ins, (x.dtype for x in ops))) + [(by_row, _F32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, seg=seg, dtype=dtype),
        grid=grid, in_specs=ins, out_specs=by_row,
        out_shape=_struct((many, nc * CHUNK, dv), _F32, ops[0]),
        scratch_shapes=[scratch],
        compiler_params=_params(blocks, 4 * int(np.prod(scratch.shape))),
        interpret=interpret,
        name="delta_scan_fwd",  # the kernel's stable name in a device trace
    )(*ops)


@functools.partial(jax.jit, static_argnames=("seg", "dtype", "interpret"))
def _backward(ops, states, d_o, seg: int, dtype, interpret: bool):
    grid, heads, ins, by_row, st_spec, scratch = _specs(ops, seg, True)
    starts = pltpu.VMEM((seg,) + scratch.shape, _F32)
    blocks = 2 * list(zip(ins, (x.dtype for x in ops))) \
        + [(by_row, _F32), (st_spec, _F32)]
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, seg=seg, dtype=dtype),
        grid=grid, in_specs=ins + [st_spec, by_row], out_specs=ins,
        out_shape=[_struct(x.shape, x.dtype, x) for x in ops],
        scratch_shapes=[scratch, starts],
        compiler_params=_params(blocks, 4 * (seg + 1) * int(np.prod(scratch.shape))),
        interpret=interpret,
        name="delta_scan_bwd",
    )(*ops, states, d_o))


def _column(ops):
    """The operands with exp G_C [chunks, many, dk] as the chunk kernels
    write it, [chunks, many, 1, dk]: a block's last two dimensions are then
    whole."""
    w_k, w_v, k_end, d_end, q_dec, b_low = ops
    return w_k, w_v, k_end, d_end[:, :, None, :], q_dec, b_low


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def scan_chunks(w_k, w_v, k_end, d_end, q_dec, b_low, seg: int, dtype,
                interpret: bool = False):
    """The walk over chunks as a kernel: o [many, n, dv] float32 from the six
    operands of every chunk as `pallas_delta_rule.chunk_operands` writes
    them, chunks first ([n / 64, many, 64, d]; exp G_C [n / 64, many, dk]),
    the state zero before a row's first chunk; `seg` chunks a segment (a
    divisor of the chunks), the products' operands in `dtype` (the precision
    policy's)."""
    return _forward(_column((w_k, w_v, k_end, d_end, q_dec, b_low)), seg=seg,
                    dtype=dtype, interpret=interpret)


def _scan_chunks_fwd(w_k, w_v, k_end, d_end, q_dec, b_low, seg, dtype, interpret):
    # the residuals are the operands alone: the backward pass makes the
    # segments' states itself
    ops = (w_k, w_v, k_end, d_end, q_dec, b_low)
    return scan_chunks(*ops, seg, dtype, interpret), ops


def _scan_chunks_bwd(seg, dtype, interpret, ops, d_o):
    # the state every segment started from, by the `lax.scan` (the module's
    # docstring)
    d_wk, d_wv, d_kend, d_dend, d_qdec, d_blow = _backward(
        _column(ops), segment_states(*ops[:4], seg), d_o, seg=seg, dtype=dtype,
        interpret=interpret)
    return d_wk, d_wv, d_kend, d_dend[:, :, 0, :], d_qdec, d_blow


scan_chunks.defvjp(_scan_chunks_fwd, _scan_chunks_bwd)
