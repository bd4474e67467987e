"""Pallas TPU kernels for the stage between Kimi Delta Attention's
projections and its delta rule: what `ops.kda_shape.shape_jnp` computes, and
its backward pass.

Why a kernel: the stage is elementwise but for two sums over a head's width
-- three depthwise causal convolutions of a few taps, SiLU, two L2 norms, a
scale, the casts, the decay's sigmoid -- and XLA ran it as some twenty-four
float32 passes over [heads, positions, 128] a forward (the chain is cut at
each pad-and-shift of the taps, at the norms' reductions and at the scope's
boundary), three times forward and once differentiated a row and step: 19.7
ms a row where its bytes allow 3.3 (PERF.md section 6, PR 39). Here a
program reads a tile of q, k, v, a as the projections wrote them, keeps
everything between in float32 in VMEM, and writes q, k, v in the compute
dtype and g in float32 as `pallas_delta_rule.chunk_operands` reads them. The
backward kernel keeps nothing but the inputs: it makes the forward's
intermediates again in VMEM.

The arithmetic is `shape_jnp`'s own (float32 taps in `causal_taps`' order of
summation, SiLU, `t * rsqrt(sum t^2 + 1e-6)`, q's `d^-1/2` after the norm,
`g = lower_bound * sigmoid(exp(A_log) * (a + dt_bias))`; the compute dtype
only where `shape_jnp` casts), laid out for the chip:

* THE TILE. The grid is (rows, heads, positions / `TILE`): a program works
  `TILE` = 512 positions of one head, [512, d] with the positions down the
  sublanes and the head's width along the lanes, `SUB` = 128 positions a
  loop body, so that a body's chain stays near the registers.
* THE HALO RULE. A convolution of `taps` taps at position t reads t - (taps
  - 1) .. t, so a tile's first positions need the tile before; its
  transpose (the backward) reads t .. t + (taps - 1) of the cotangent
  THROUGH the SiLU and the norm, so a tile's last positions need the next
  tile's inputs and cotangents. Both come as HALO BLOCKS: the same array
  under a second (third) BlockSpec of `HALO` = 16 positions (a bfloat16
  tile's sublanes), the block just before (after) the tile; before position
  0 the halo reads zeros, past the row's end the cotangent of the
  convolution's output is zero (both by a select on the program's index:
  the block fetched there is a clamped neighbour and not used). Of a halo
  the 8 nearest positions are used, so `taps` may be up to 9. Shifts along
  the sublanes are rotations of float32 values (`pltpu.roll`) of which the
  aligned part is kept.
* THE PARAMETERS come packed, [heads, `param_rows(taps)`, d] float32: the
  three tap sets a tap a row (oldest first), `dt_bias`, `exp(A_log)` laid
  over the lanes. The backward writes their gradients as PER-TILE partials,
  [rows, heads, tiles, rows of the pack, d], which a small XLA sum finishes;
  the pack itself (`pack`: a transpose of the taps, an `exp`) is plain `jnp`
  with autodiff outside the call.
* THE VMEM NEED is stated, not discovered (`_need`): every block twice (the
  pipeline's double buffering) -- forward 4 inputs + 3 halos + 4 outputs,
  backward 4 inputs + 6 halos + 4 cotangents + 3 halos + 4 gradients -- plus
  `_BODY_ARRAYS` float32 arrays of a loop body's [SUB + 16, d]: 2.5 MB
  forward and 5.0 MB backward at d = 128 in bfloat16, 4.2 and 8.5 in
  float32, under the 16 MiB Mosaic allows unasked; wider heads ask for more
  than that and say so.

WHAT WAS TIMED. One code path serves every shape `_can_pallas` admits (up to
9 taps, heads of 128 lanes and whole multiples, bfloat16 and float32), and
the tests hold all of it to the `jnp` form under the interpreter; on the chip
only the one configuration that reaches it was timed, 4 taps at d = 128 in
bfloat16 (PERF.md section 6, PR 39); that shape compiles for a described v5e
in both dtypes (`tests/test_chip_compile.py -k kda_shape_kernels`). More taps
and wider heads have run under the interpreter alone.

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

from .pallas_delta_rule import _DEFAULT_SCOPED_VMEM, _struct

TILE = 512  # positions a program
SUB = 128   # positions a loop body
HALO = 16   # positions a halo block
NEAR = 8    # of which the nearest are used (a float32 tile's sublanes)
EPS = 1e-6  # under the L2 norms' root
_F32 = jnp.float32
#: float32 arrays of [SUB + HALO, d] a loop body may hold at once (the
#: backward's: three chains of taps, SiLU, norm and their cotangents)
_BODY_ARRAYS = 40


def param_rows(taps: int) -> int:
    """Rows of the parameters' pack: three tap sets, `dt_bias`, `exp(A_log)`,
    up to whole float32 tiles."""
    return -(-(3 * taps + 2) // NEAR) * NEAR


def _back(ext, by: int, rows: int):
    """out[r] = ext[NEAR + r - by] for r < rows: `ext` holds NEAR positions
    before the rows, then the rows (and maybe more)."""
    return pltpu.roll(ext, by, 0)[NEAR:NEAR + rows]


def _ahead(x, by: int, rows: int):
    """out[r] = x[r + by] for r < rows (x holds at least rows + by)."""
    return pltpu.roll(x, x.shape[0] - by, 0)[:rows]


def _taps(ext, w, rows: int):
    """The causal convolution of `rows` positions (`ext`: NEAR before them,
    then they) with the taps `w` ([1, d] each, oldest first), summed in
    `causal_taps`' order; and the shifted inputs, oldest first."""
    taps = len(w)
    shifted = [_back(ext, taps - 1 - j, rows) for j in range(taps - 1)]
    shifted.append(ext[NEAR:NEAR + rows])
    out = shifted[-1] * w[-1]
    for j in range(taps - 1):
        out = out + shifted[j] * w[j]
    return out, shifted


def pack(convs, dt_bias, a_log):
    """The parameters as the kernels read them, [heads, `param_rows(taps)`,
    d] float32: a tap a row (q's, k's, v's, oldest first), `dt_bias`,
    `exp(A_log)` over the lanes, zeros up to whole tiles. Plain `jnp`, so
    autodiff takes the pack's gradient back to the parameters."""
    heads, d = dt_bias.shape
    parts = [jnp.swapaxes(w, -1, -2) for w in convs] + [
        dt_bias[:, None, :],
        jnp.broadcast_to(jnp.exp(a_log)[:, None, None], (heads, 1, d))]
    used = sum(p.shape[1] for p in parts)
    parts.append(jnp.zeros((heads, param_rows(convs[0].shape[-1]) - used, d), _F32))
    return jnp.concatenate(parts, axis=1).astype(_F32)


def _unpack(p, taps: int):
    """(the three tap sets as lists of [1, d] rows, dt_bias, exp(A_log))."""
    w = [[p[t * taps + j:t * taps + j + 1] for j in range(taps)] for t in range(3)]
    return w, p[3 * taps:3 * taps + 1], p[3 * taps + 1:3 * taps + 2]


def _rows(ref, start, size: int):
    """`size` positions of a block [1, 1, n, d] from `start` (a multiple of
    `size`), float32."""
    return ref[0, 0, pl.ds(pl.multiple_of(start, size), size), :].astype(_F32)


def _before(x_ref, halo_ref, i, first):
    """The NEAR positions before sub-tile i of a tile: the tile's own, for
    its first sub-tile the halo's, zeros before position 0."""
    inner = _rows(x_ref, jnp.maximum(i * SUB - HALO, 0), HALO)[HALO - NEAR:]
    halo = jnp.where(first, 0.0, halo_ref[0, 0].astype(_F32)[HALO - NEAR:])
    return jnp.where(i == 0, halo, inner)


def _after(x_ref, halo_ref, i):
    """The NEAR positions after sub-tile i: the tile's own, after its last
    sub-tile the halo's (past the row's end: whatever lies there; the caller
    masks what it makes of them)."""
    inner = _rows(x_ref, jnp.minimum((i + 1) * SUB, TILE - HALO), HALO)[:NEAR]
    return jnp.where(i == TILE // SUB - 1, halo_ref[0, 0].astype(_F32)[:NEAR], inner)


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, qh_ref, kh_ref, vh_ref, p_ref,
                qo_ref, ko_ref, vo_ref, g_ref, *, taps: int, lower_bound: float):
    first = pl.program_id(2) == 0
    w, bias, scale = _unpack(p_ref[0], taps)
    q_scale = q_ref.shape[-1] ** -0.5

    def body(i, carry):
        at = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        for x_ref, h_ref, o_ref, w_x, normed in (
                (q_ref, qh_ref, qo_ref, w[0], q_scale),
                (k_ref, kh_ref, ko_ref, w[1], 1.0),
                (v_ref, vh_ref, vo_ref, w[2], None)):
            ext = jnp.concatenate([_before(x_ref, h_ref, i, first),
                                   _rows(x_ref, i * SUB, SUB)], axis=0)
            c, _ = _taps(ext, w_x, SUB)
            y = c * jax.nn.sigmoid(c)
            if normed is not None:
                y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + EPS)
                if normed != 1.0:
                    y = y * normed
            o_ref[0, 0, at, :] = y.astype(o_ref.dtype)
        z = scale * (_rows(a_ref, i * SUB, SUB) + bias)
        g_ref[0, 0, at, :] = lower_bound * jax.nn.sigmoid(z)
        return carry

    lax.fori_loop(0, TILE // SUB, body, 0)


def _fold(x):
    """[rows, d] -> [NEAR, d]: the sum of its float32 tiles (what is left of
    a sum over positions is one sublane reduction at the program's end)."""
    out = x[:NEAR]
    for r in range(1, x.shape[0] // NEAR):
        out = out + x[r * NEAR:(r + 1) * NEAR]
    return out


def _bwd_kernel(q_ref, k_ref, v_ref, a_ref, qp_ref, kp_ref, vp_ref,
                qn_ref, kn_ref, vn_ref, p_ref,
                dqo_ref, dko_ref, dvo_ref, dg_ref, dqn_ref, dkn_ref, dvn_ref,
                dq_ref, dk_ref, dv_ref, da_ref, dp_ref, acc_ref,
                *, taps: int, lower_bound: float):
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    w, bias, scale = _unpack(p_ref[0], taps)
    q_scale = q_ref.shape[-1] ** -0.5
    acc_ref[...] = jnp.zeros_like(acc_ref)
    wide = SUB + NEAR  # a sub-tile and the positions its transpose looks at
    past = lax.broadcasted_iota(jnp.int32, (wide, 1), 0) >= SUB

    def add(row: int, x):
        at = pl.ds(row * NEAR, NEAR)
        acc_ref[at, :] = acc_ref[at, :] + _fold(x)

    def body(i, carry):
        at = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        # past the row's end nothing reads the convolution
        end = jnp.logical_and(last, i == TILE // SUB - 1)
        for t, (x_ref, xp_ref, xn_ref, do_ref, don_ref, dx_ref, normed) in enumerate((
                (q_ref, qp_ref, qn_ref, dqo_ref, dqn_ref, dq_ref, q_scale),
                (k_ref, kp_ref, kn_ref, dko_ref, dkn_ref, dk_ref, 1.0),
                (v_ref, vp_ref, vn_ref, dvo_ref, dvn_ref, dv_ref, None))):
            ext = jnp.concatenate([_before(x_ref, xp_ref, i, first),
                                   _rows(x_ref, i * SUB, SUB),
                                   _after(x_ref, xn_ref, i)], axis=0)
            do = jnp.concatenate([_rows(do_ref, i * SUB, SUB),
                                  _after(do_ref, don_ref, i)], axis=0)
            c, shifted = _taps(ext, w[t], wide)
            sig = jax.nn.sigmoid(c)
            dy = do
            if normed is not None:
                y = c * sig
                r = lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + EPS)
                along = jnp.sum(do * y, axis=-1, keepdims=True)
                dy = (r * normed) * (do - y * ((r * r) * along))
            dc = dy * (sig * (1.0 + c * (1.0 - sig)))
            dc = jnp.where(jnp.logical_and(end, past), 0.0, dc)
            dx = dc[:SUB] * w[t][-1]
            for j in range(taps - 1):
                dx = dx + _ahead(dc, taps - 1 - j, SUB) * w[t][j]
            dx_ref[0, 0, at, :] = dx.astype(dx_ref.dtype)
            for j in range(taps):
                add(t * taps + j, dc[:SUB] * shifted[j][:SUB])
        moved = _rows(a_ref, i * SUB, SUB) + bias
        sig = jax.nn.sigmoid(scale * moved)
        dz = dg_ref[0, 0, at, :] * (lower_bound * (sig * (1.0 - sig)))
        da_ref[0, 0, at, :] = (dz * scale).astype(da_ref.dtype)
        add(3 * taps, dz * scale)
        add(3 * taps + 1, dz * moved)
        return carry

    lax.fori_loop(0, TILE // SUB, body, 0)
    used = 3 * taps + 2
    sums = [jnp.sum(acc_ref[pl.ds(r * NEAR, NEAR), :], axis=0, keepdims=True)
            for r in range(used)]
    sums.append(jnp.zeros((dp_ref.shape[-2] - used, dp_ref.shape[-1]), _F32))
    dp_ref[0, 0, 0] = jnp.concatenate(sums, axis=0)


def _specs(q, params):
    """(grid, the block specs of a tile, of the halo before it, of the halo
    after it, of the parameters' pack) for q [rows, heads, n, d]."""
    rows, heads, n, d = q.shape
    vmem = pltpu.VMEM
    per_tile = TILE // HALO
    halos = n // HALO
    tile = pl.BlockSpec((1, 1, TILE, d), lambda r, h, j: (r, h, j, 0),
                        memory_space=vmem)
    before = pl.BlockSpec(
        (1, 1, HALO, d), lambda r, h, j: (r, h, jnp.maximum(j * per_tile - 1, 0), 0),
        memory_space=vmem)
    after = pl.BlockSpec(
        (1, 1, HALO, d),
        lambda r, h, j: (r, h, jnp.minimum((j + 1) * per_tile, halos - 1), 0),
        memory_space=vmem)
    pack = pl.BlockSpec((1,) + params.shape[1:], lambda r, h, j: (h, 0, 0),
                        memory_space=vmem)
    return (rows, heads, n // TILE), tile, before, after, pack


def _vmem(d: int, tiles_by_itemsize, halos_by_itemsize, pack_rows: int) -> int:
    """The scoped VMEM a call states: every block twice (the pipeline's
    double buffering) and `_BODY_ARRAYS` float32 arrays of a loop body."""
    blocks = sum(count * TILE * d * size for count, size in tiles_by_itemsize) \
        + sum(count * HALO * d * size for count, size in halos_by_itemsize) \
        + 2 * pack_rows * d * 4
    return max(_DEFAULT_SCOPED_VMEM,
               2 * blocks + _BODY_ARRAYS * (SUB + HALO) * d * 4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def shape_kernels(q, k, v, a, params, taps: int, lower_bound: float,
                  interpret: bool = False):
    """`ops.kda_shape.shape_jnp` as a kernel: from the projections q, k, v, a
    [rows, heads, n, d] (n a multiple of `TILE`, d of the 128 lanes) in the
    compute dtype and the parameters' pack [heads, `param_rows(taps)`, d]
    float32, (q, k, v in the inputs' dtype, g float32) as the delta rule
    reads them."""
    grid, tile, before, _, pack = _specs(q, params)
    size, d = q.dtype.itemsize, q.shape[-1]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, lower_bound=lower_bound),
        grid=grid, in_specs=[tile] * 4 + [before] * 3 + [pack],
        out_specs=[tile] * 4,
        out_shape=[_struct(q.shape, q.dtype, q)] * 3 + [_struct(q.shape, _F32, q)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_vmem(d, [(7, size), (1, 4)], [(3, size)],
                                   params.shape[1])),
        interpret=interpret,
        name="kda_shape_fwd",  # the kernel's stable name in a device trace
    )(q, k, v, a, q, k, v, params)


def _v_plain(v, params, taps: int):
    """v's path of the stage, `silu(taps(v))`, in plain `jnp` (float32, the
    taps summed in `causal_taps`' order, cast as the kernel casts): what the
    forward rule below returns in the kernel's place. Restated here and not
    called, because `causal_taps` lives in `model/`; the tests hold it to
    the `jnp` form, which calls `causal_taps`, and to the kernel's v."""
    x, n = v.astype(_F32), v.shape[-2]
    tap = lambda j: params[:, None, 2 * taps + j, :]  # [heads, 1, d]
    out = x * tap(taps - 1)
    for j in range(taps - 1):
        back = ((0, 0), (0, 0), (taps - 1 - j, 0), (0, 0))
        out = out + jnp.pad(x, back)[..., :n, :] * tap(j)
    return jax.nn.silu(out).astype(v.dtype)


def _shape_kernels_fwd(q, k, v, a, params, taps, lower_bound, interpret):
    """The forward rule: what runs where the call is differentiated, which
    in the layer is a row's SECOND forward, inside the loop of the rows'
    backward pass. The residuals are the inputs alone (the backward kernel
    makes the rest). q, k and g are the kernel's; **v is made again in plain
    `jnp` and the kernel's v is dropped** -- one convolution and a SiLU,
    1.4 ms a row on the chip and 2.1 % of the Ling cell's rate with what
    turns with it, bought for the compiled round's memory and nothing else:
    with every rule's forward a kernel call and no XLA op of the stage left
    in the rows' backward loop, the TPU compiler assigns the Ling round 7.43
    GB of temporaries where the `jnp` form had 6.23 (live peak + 0.54 GB, the
    rest packing); one convolution of the stage in plain `jnp` here brings
    6.22 (`round_temp_bytes`; PERF.md section 6, PR 39, has the twenty-six
    variants compiled: what decides is this site and a pad-and-shift
    convolution in it; the block's forward pass, the backward kernel, the
    order of the calls, barriers and the decay in `jnp` decide nothing;
    section 7 says what would let the rule be the kernel alone again)."""
    q_out, k_out, _, g = shape_kernels(q, k, v, a, params, taps, lower_bound,
                                       interpret)
    return (q_out, k_out, _v_plain(v, params, taps), g), (q, k, v, a, params)


def _shape_kernels_bwd(taps, lower_bound, interpret, res, cot):
    q, k, v, a, params = res
    d_q, d_k, d_v, d_g = cot
    grid, tile, before, after, pack = _specs(q, params)
    size, d = q.dtype.itemsize, q.shape[-1]
    rows, heads, tiles = grid
    partial = pl.BlockSpec((1, 1, 1) + params.shape[1:],
                           lambda r, h, j: (r, h, j, 0, 0), memory_space=pltpu.VMEM)
    *grads, d_params = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, lower_bound=lower_bound),
        grid=grid,
        in_specs=[tile] * 4 + [before] * 3 + [after] * 3 + [pack]
        + [tile] * 4 + [after] * 3,
        out_specs=[tile] * 4 + [partial],
        out_shape=[_struct(q.shape, q.dtype, q)] * 4
        + [_struct((rows, heads, tiles) + params.shape[1:], _F32, q)],
        scratch_shapes=[pltpu.VMEM(((3 * taps + 2) * NEAR, d), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_vmem(d, [(11, size), (1, 4)], [(9, size)],
                                   2 * params.shape[1])),
        interpret=interpret,
        name="kda_shape_bwd",
    )(q, k, v, a, q, k, v, q, k, v, params, d_q, d_k, d_v, d_g, d_q, d_k, d_v)
    return (*grads, jnp.sum(d_params, axis=(0, 2)))


shape_kernels.defvjp(_shape_kernels_fwd, _shape_kernels_bwd)
