"""Pallas TPU kernels for the chunked Mamba-2 scan: what `ops.ssd.ssd`
computes from the chunks of x, B, C, the time steps and their running sums,
and its backward pass.

Why a kernel: between its four products the `jnp` form is a dozen float32
passes over every chunk's [128, 128] squares (the differences of running
sums, the mask, the exp, the time steps, the scores, the cast: 268 MB an
array at 2 x 8,192 positions and 32 heads) and over the chunks' own
contributions to the state (134 MB), which XLA runs through HBM three times a
step, and a `lax.scan` over the chunks besides: 14.9 ms a layer-step on the
chip for work whose least time is 0.89 (PERF.md section 6, PR 48). Nothing
of that has to leave the chip: a program here works ONE chunk of one group's
heads, the grid's last axis walks a row's chunks in order, and the float32
state of the group's heads lives in a VMEM scratch across that walk. (A
group of more heads than `ops.ssd.PROGRAM_TILES` lane tiles hold is worked
in equal BLOCKS of its heads, a program each: the grid's middle axis runs
over groups x blocks, every block reads its group's B and C, and dB and dC
leave the backward kernel a block apiece in float32 and are summed outside.)
A chunk's
x, B, C, time steps and running sums are read once, its y written once; the
squares, the decays and the chunk's own contribution exist in VMEM alone.

The arithmetic is `ops.ssd.ssd`'s own: float32 time steps, running sums,
decays and state; every exponent a difference taken before the exp (what
lies above the diagonal is masked before it); the four products' operands in
the policy's dtype with float32 accumulation. Laid out for the chip:

* heads narrower than the 128 lanes share a lane TILE (two heads of 64): the
  products whose result has a head's channels on the lanes -- C S^T, x_end^T
  B -- run once a tile for its heads together, and a head's own square times
  x runs over the whole tile with the other heads' lanes deselected after:
  the matrix unit is 128 wide either way, and nothing is sliced or
  concatenated at half a lane row;
* a head's running sum and time step are needed down the rows of a square (a
  column, L_t) and along them (a row, L_s, dt_s): both layouts come in from
  HBM, [.., heads, 128] and [.., 128, heads] (2 MB each at the cell's shape),
  and the gradients go out in both and are added by the caller's autodiff.

The forward kernel writes y and, when asked (the forward rule of the
`custom_vjp`), the float32 state every chunk STARTED from; the backward
kernel walks the chunks in reverse with the state's cotangent in its scratch,
makes the squares again in VMEM and emits dx, dB, dC (a group's heads summed
in the program that holds them all) and the float32 cotangents of the time
steps as multipliers and of the running sums. The `cumsum(dt A)` that makes
the running sums stays outside, in `jnp` under autodiff (`ops.ssd`).

Rows that hold several documents hand both kernels a chunk's document runs
(`ops.ssd.ssd`: counted from the run the chunk's first position would
continue, as a row [1, Q] and as a column [Q, 1] of int32). A cut is then a
mask on four exponents, set before the exp as the diagonal's is (`_Chunk`):
the square's pairs of one document, exp L_t where position t still reads the
incoming state, exp(L_Q - L_s) where position s writes into the state handed
on, exp L_Q where the chunk passes the state through. Every later use of
them is a product, so the backward kernel's formulas stand as they are: a
masked factor is 0 and so is what flows through it. No program does less
for a boundary: the work does not depend on where the documents fall.
`interpret=True` runs the same kernels under the Pallas interpreter (CPU),
which the tests use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_delta_rule import _NN, _NT, _TN, _pdot, _struct
from .ssd import tile_heads

_F32 = jnp.float32
#: what masks an exponent above the diagonal: exp gives 0, and no inf meets
#: a product
_MASKED = -1e30


class _Chunk:
    """What both kernels read of a program's chunk: B, C, the running sums
    and time steps in both layouts, what is made of them a head, and the
    index masks."""

    def __init__(self, b_ref, c_ref, dtr_ref, lr_ref, dtc_ref, lc_ref,
                 relr_ref=None, relc_ref=None, *, p: int):
        self.b, self.c = b_ref[0], c_ref[0]                     # [Q, N]
        self.dt_row, self.l_row = dtr_ref[0, 0, 0], lr_ref[0, 0, 0]   # [per, Q]
        self.dt_col, self.l_col = dtc_ref[0, 0, 0], lc_ref[0, 0, 0]   # [Q, per]
        per, q = self.l_row.shape
        self.per, self.q = per, q
        self.hp = tile_heads(p)
        self.tw = self.hp * p                                   # a tile's lanes
        self.tiles = per // self.hp
        l_end = self.l_col[q - 1:q, :]                          # L_Q [1, per]
        if relr_ref is None:
            cut = lambda keep, e: e
            same = incoming = tail = whole = None
        else:
            # the chunk's document runs, 0 = the incoming document's
            rel_row, rel_col = relr_ref[0, 0, 0], relc_ref[0, 0, 0]   # [1, Q], [Q, 1]
            cut = lambda keep, e: jnp.where(keep, e, _MASKED)
            same, incoming = rel_col == rel_row, rel_col == 0
            tail, whole = rel_col == rel_col[q - 1:q, :], rel_col[q - 1:q, :] == 0
        self.e_col = jnp.exp(cut(incoming, self.l_col))         # exp L_t
        self.to_end = jnp.exp(cut(tail, l_end - self.l_col))    # exp(L_Q - L_s)
        self.w_col = self.to_end * self.dt_col
        self.e_end = jnp.exp(cut(whole, l_end))                 # exp L_Q
        t = lax.broadcasted_iota(jnp.int32, (q, q), 0)
        s = lax.broadcasted_iota(jnp.int32, (q, q), 1)
        self.lower = t >= s if same is None else (t >= s) & same
        self.last = s[:1, :] == q - 1                           # [1, Q]
        # which of its tile's heads a lane of x, a row of the state, is
        self.lane_head = lax.broadcasted_iota(jnp.int32, (q, self.tw), 1) // p
        self.row_head = lax.broadcasted_iota(jnp.int32, (self.tw, 1), 0) // p

    def heads(self, tile: int):
        return [(k, tile * self.hp + k) for k in range(self.hp)]

    def lanes(self, tile: int):
        return slice(tile * self.tw, (tile + 1) * self.tw)

    def over_lanes(self, cols, tile: int):
        """[Q, tw] with head j's column of `cols` [Q, per] over its lanes."""
        out = cols[:, tile * self.hp:tile * self.hp + 1]
        for k, j in self.heads(tile)[1:]:
            out = jnp.where(self.lane_head >= k, cols[:, j:j + 1], out)
        return out

    def over_rows(self, row, tile: int):
        """[tw, 1] with head j's entry of `row` [1, per] down its rows."""
        out = row[:, tile * self.hp:tile * self.hp + 1]
        for k, j in self.heads(tile)[1:]:
            out = jnp.where(self.row_head >= k, row[:, j:j + 1], out)
        return out

    def own(self, k: int, full, where=None):
        """`full` on head k's lanes (rows, with `where` the rows' heads) of
        its tile and 0 on the others'."""
        if self.hp == 1:
            return full
        which = self.lane_head if where is None else where
        return jnp.where(which == k, full, 0.0)

    def decay(self, j: int):
        """exp(L_t - L_s) of head j, 0 above the diagonal: [t, s]."""
        diff = self.l_col[:, j:j + 1] - self.l_row[j:j + 1, :]
        return jnp.exp(jnp.where(self.lower, diff, _MASKED))


def _fwd_kernel(x_ref, *refs, p: int, dtype, cuts: int):
    """refs: B, C, the time steps and running sums as rows and as columns,
    `cuts` (0 or 2) arrays of document runs; then y, the chunk states where
    asked for, and the state's scratch."""
    y_ref, *rest = refs[6 + cuts:]
    st_ref, s_ref = rest if len(rest) == 2 else (None,) + tuple(rest)
    ch = _Chunk(*refs[:6 + cuts], p=p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    scores = _pdot(ch.c, ch.b, _NT, dtype)                      # C_t . B_s
    for tile in range(ch.tiles):
        x = x_ref[0, :, ch.lanes(tile)]                         # [s, tw]
        state = s_ref[tile]                                     # [tw, N]
        if st_ref is not None:
            st_ref[0, 0, 0, ch.lanes(tile), :] = state
        y = ch.over_lanes(ch.e_col, tile) * _pdot(ch.c, state, _NT, dtype)
        for k, j in ch.heads(tile):
            mix = scores * (ch.decay(j) * ch.dt_row[j:j + 1, :])
            y = y + ch.own(k, _pdot(mix, x, _NN, dtype))
        y_ref[0, :, ch.lanes(tile)] = y
        # the chunk's own contribution to the state it hands on
        x_end = x.astype(_F32) * ch.over_lanes(ch.w_col, tile)
        s_ref[tile] = ch.over_rows(ch.e_end, tile) * state \
            + _pdot(x_end, ch.b, _TN, dtype)


def _bwd_kernel(x_ref, *refs, p: int, dtype, cuts: int):
    """refs: as `_fwd_kernel`'s inputs, then the chunk states and dy; the
    seven cotangents; the scratch of the state's cotangent."""
    (st_ref, dy_ref, dx_ref, db_ref, dc_ref, ddtr_ref, dlr_ref, ddtc_ref,
     dlc_ref, ds_ref) = refs[6 + cuts:]
    ch = _Chunk(*refs[:6 + cuts], p=p)
    per, q = ch.per, ch.q

    @pl.when(pl.program_id(2) == 0)  # the row's LAST chunk: nothing reads its end
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    scores = _pdot(ch.c, ch.b, _NT, dtype)
    d_scores = jnp.zeros((q, q), _F32)
    d_b = jnp.zeros(ch.b.shape, _F32)
    d_c = jnp.zeros(ch.c.shape, _F32)
    rows = {n: jnp.zeros((per, q), _F32) for n in ("dt", "l")}
    cols = {n: jnp.zeros((q, per), _F32) for n in ("dt", "l")}
    head_of_row = lax.broadcasted_iota(jnp.int32, (per, q), 0)
    head_of_col = lax.broadcasted_iota(jnp.int32, (q, per), 1)
    both = lambda t: jnp.sum(jnp.sum(t, axis=1, keepdims=True), axis=0, keepdims=True)
    for tile in range(ch.tiles):
        x = x_ref[0, :, ch.lanes(tile)]
        x32 = x.astype(_F32)
        dy = dy_ref[0, :, ch.lanes(tile)].astype(_F32)          # [t, tw]
        state = st_ref[0, 0, 0, ch.lanes(tile), :]              # the chunk's start
        d_next = ds_ref[tile]                                   # d of what it hands on
        # y's second term, exp(L_t) (C S^T)
        z = _pdot(ch.c, state, _NT, dtype)
        dz = ch.over_lanes(ch.e_col, tile) * dy
        dz_z = dz * z
        d_c = d_c + _pdot(dz, state, _NN, dtype)
        # the state's update, S' = exp(L_Q) S + x_end^T B
        w = ch.over_lanes(ch.w_col, tile)
        dx_end = _pdot(ch.b, d_next, _NT, dtype)                # [s, tw]
        d_b = d_b + _pdot(x32 * w, d_next, _NN, dtype)
        dx = dx_end * w
        dw_x = dx_end * x32
        kept = d_next * state                                   # [tw, N]
        for k, j in ch.heads(tile):
            dt_row = ch.dt_row[j:j + 1, :]
            dw = jnp.sum(ch.own(k, dw_x), axis=1, keepdims=True)        # [s, 1]
            at_end = dw * ch.w_col[:, j:j + 1]                  # d(L_Q - L_s)
            d_end = both(at_end) + ch.e_end[:, j:j + 1] * both(
                ch.own(k, kept, ch.row_head))                   # dL_Q [1, 1]
            # the head's square, made again
            decay = ch.decay(j)
            decay_dt = decay * dt_row
            mix = scores * decay_dt
            dy_k = ch.own(k, dy)
            d_mix = _pdot(dy_k, x, _NT, dtype)                  # [t, s]
            dx = dx + _pdot(mix, dy_k, _TN, dtype)
            d_scores = d_scores + d_mix * decay_dt
            d_decay = d_mix * scores * decay                    # before dt_s
            by_s = jnp.sum(d_decay, axis=0, keepdims=True)      # [1, s]
            new_rows = {"dt": by_s,
                        "l": jnp.where(ch.last, d_end, 0.0) - dt_row * by_s}
            new_cols = {"dt": dw * ch.to_end[:, j:j + 1],
                        "l": jnp.sum(ch.own(k, dz_z), axis=1, keepdims=True)
                        + jnp.sum(d_decay * dt_row, axis=1, keepdims=True) - at_end}
            for n in ("dt", "l"):
                rows[n] = jnp.where(head_of_row == j, new_rows[n], rows[n])
                cols[n] = jnp.where(head_of_col == j, new_cols[n], cols[n])
        ds_ref[tile] = ch.over_rows(ch.e_end, tile) * d_next \
            + _pdot(dz, ch.c, _TN, dtype)
        dx_ref[0, :, ch.lanes(tile)] = dx.astype(dx_ref.dtype)
    dc_ref[0] = (d_c + _pdot(d_scores, ch.b, _NN, dtype)).astype(dc_ref.dtype)
    db_ref[0] = (d_b + _pdot(d_scores, ch.c, _TN, dtype)).astype(db_ref.dtype)
    ddtr_ref[0, 0, 0], dlr_ref[0, 0, 0] = rows["dt"], rows["l"]
    ddtc_ref[0, 0, 0], dlc_ref[0, 0, 0] = cols["dt"], cols["l"]


def _specs(xs, cuts, p: int, n_state: int, reverse: bool):
    """(grid, the inputs' block specs, the spec of an array laid out as x,
    that of dB or dC a PROGRAM at a time, the chunk states' spec and aval, the
    scratch, the blocks a group's heads are worked in) for x [rows, n, heads
    x P], B and C [rows, n, groups x `n_state`], the time steps and running
    sums as rows [rows, chunks, programs, per, Q] and as columns [.., Q, per]
    -- `programs` the groups times the blocks, `per` the heads of one -- and
    `cuts`: () or a chunk's document runs as a row [rows, chunks, 1, 1, Q]
    and as a column [.., Q, 1]. A block reads its group's B and C (program g
    those of group g // blocks). The scratch is the float32 state of a
    program's heads (or its cotangent), a lane tile of heads at a time:
    [tiles, tw, N]. `reverse`: the grid's last axis walks the chunks from
    the last to the first."""
    x, b, _, rows_like, _, cols_like, _ = xs
    rows, _, wide = x.shape
    _, nc, programs, _, q = rows_like.shape
    blocks = programs * n_state // b.shape[-1]
    wide = wide // programs                           # a program's heads x P
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    vmem = pltpu.VMEM
    by_pos = lambda d, of=lambda g: g: pl.BlockSpec(
        (1, q, d), lambda r, g, c: (r, at(c), of(g)), memory_space=vmem)
    small = lambda *tail: pl.BlockSpec(
        (1, 1, 1) + tail, lambda r, g, c: (r, at(c), g, 0, 0), memory_space=vmem)
    shared = lambda *tail: pl.BlockSpec(
        (1, 1, 1) + tail, lambda r, g, c: (r, at(c), 0, 0, 0), memory_space=vmem)
    like_x, per_program = by_pos(wide), by_pos(n_state)
    group = per_program if blocks == 1 else by_pos(n_state, lambda g: g // blocks)
    ins = [like_x, group, group] \
        + [small(*rows_like.shape[-2:])] * 2 + [small(*cols_like.shape[-2:])] * 2 \
        + [shared(*t.shape[-2:]) for t in cuts]
    tw = tile_heads(p) * p
    return ((rows, programs, nc), ins, like_x, per_program, small(wide, n_state),
            _struct((rows, nc, programs, wide, n_state), _F32, x),
            pltpu.VMEM((wide // tw, tw, n_state), _F32), blocks)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# (both calls under a `jax.jit` of their own: jax traces a kernel's body anew
# at every `pallas_call`, 0.15-0.3 s each for these bodies, and a round holds
# thirty; jitted, a process traces and lowers each of the three once)
@functools.partial(jax.jit, static_argnames=("p", "n_state", "dtype", "interpret",
                                             "states"))
def _forward(xs, cuts, p: int, n_state: int, dtype, interpret: bool, states: bool):
    grid, ins, like_x, _, st_spec, st_aval, scratch, _ = _specs(
        xs, cuts, p, n_state, False)
    outs = [(like_x, _struct(xs[0].shape, _F32, xs[0]))] + [(st_spec, st_aval)] * states
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, dtype=dtype, cuts=len(cuts)),
        grid=grid, in_specs=ins, out_specs=[spec for spec, _ in outs],
        out_shape=[aval for _, aval in outs], scratch_shapes=[scratch],
        compiler_params=_PARAMS, interpret=interpret,
        name="ssd_chunk_fwd",  # the kernel's stable name in a device trace
    )(*xs, *cuts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def ssd_chunks(x, b, c, dt_row, run_row, dt_col, run_col, cuts, p: int,
               n_state: int, dtype, interpret: bool = False):
    """`ops.ssd.ssd` on whole chunks as a kernel: y [rows, n, heads x P]
    float32 from x [rows, n, heads x P] (heads of `p` channels), B and C
    [rows, n, groups x N] (N = `n_state`), and the float32 time steps and
    running sums inside a chunk, both as rows [rows, n / Q, programs, per, Q] and as
    columns [rows, n / Q, programs, Q, per] (`programs` x `per` the heads,
    group by group: as many programs a group as its heads are worked in
    blocks); `cuts` () or the chunks' document runs (`_specs`); the
    products' operands in `dtype` (the precision policy's)."""
    return _forward((x, b, c, dt_row, run_row, dt_col, run_col), cuts, p=p,
                    n_state=n_state, dtype=dtype, interpret=interpret,
                    states=False)[0]


def _ssd_chunks_fwd(x, b, c, dt_row, run_row, dt_col, run_col, cuts, p, n_state,
                    dtype, interpret):
    xs = (x, b, c, dt_row, run_row, dt_col, run_col)
    y, states = _forward(xs, cuts, p=p, n_state=n_state, dtype=dtype,
                         interpret=interpret, states=True)
    # the residuals: the inputs and the state every chunk started from
    return y, (xs, cuts, states)


@functools.partial(jax.jit, static_argnames=("p", "n_state", "dtype", "interpret"))
def _backward(xs, cuts, states, dy, p: int, n_state: int, dtype, interpret: bool):
    grid, ins, like_x, per_program, st_spec, _, scratch, blocks = _specs(
        xs, cuts, p, n_state, True)
    outs = [(spec, _struct(t.shape, t.dtype, xs[0])) for spec, t in zip(ins, xs)]
    if blocks > 1:  # dB and dC a block of the group's heads apiece, float32
        rows, n, _ = xs[0].shape
        outs[1:3] = [(per_program, _struct((rows, n, grid[1] * n_state), _F32,
                                           xs[0]))] * 2
    got = list(pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, dtype=dtype, cuts=len(cuts)),
        grid=grid, in_specs=ins + [st_spec, like_x],
        out_specs=[spec for spec, _ in outs],
        out_shape=[aval for _, aval in outs],
        scratch_shapes=[scratch], compiler_params=_PARAMS, interpret=interpret,
        name="ssd_chunk_bwd",
    )(*xs, *cuts, states, dy))
    if blocks > 1:
        got[1:3] = [jnp.sum(t.reshape(rows, n, -1, blocks, n_state), axis=3)
                    .reshape(xs[1].shape).astype(xs[1].dtype) for t in got[1:3]]
    return tuple(got)


def _ssd_chunks_bwd(p, n_state, dtype, interpret, res, dy):
    xs, cuts, states = res
    return _backward(xs, cuts, states, dy, p, n_state, dtype, interpret) \
        + (tuple(None for _ in cuts),)


ssd_chunks.defvjp(_ssd_chunks_fwd, _ssd_chunks_bwd)
