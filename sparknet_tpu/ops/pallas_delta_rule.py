"""Pallas TPU kernels for the intra-chunk stage of the gated delta rule: what
`ops.delta_rule._chunk_operands` computes, and its backward pass.

Why a kernel: between its products the chunk stage is some thirty float32
passes over [heads, positions, 128] (the decays' running sums, seven `exp`s,
the column factors of the pair terms, the substitution, masks and casts),
which XLA runs through HBM, three times forward (the block, the row's
checkpoint, the segment's) and once differentiated: 60.9 of a layer-call's
173 ms on the chip (PERF.md section 5, PR 33). A chunk of one head is q, k,
v, g [64, 128] and beta [64] in and six operands out, and no chunk depends
on another until the scan: here a chunk's temporaries live in VMEM and only
its inputs and its operands touch HBM. The backward kernel keeps nothing but
the inputs: it makes the forward's intermediates again in VMEM and needs no
substitution, because with M = (I + N)^-1 at hand the inverse's cotangent is
-M^T M_bar M^T.

The arithmetic is `_chunk_operands`' own (float32 decays, sums and solve,
`Precision.HIGHEST` on the solve's products, the policy's dtype on the
operands of the large products with float32 accumulation, the same sub-chunk
factoring around the mid-sub-chunk reference), laid out for the chip:

* a program step works a TILE of two chunks, 128 positions, as one [128, 128]
  problem whose masks keep the chunks apart: the running sums, the solve's
  merges and the W products then fill the 128 x 128 matrix unit;
* the running sum is a product with a lower-triangular ones matrix (Mosaic
  has no `cumsum`), in three passes of the matrix unit and not six: a
  float32 operand is the sum of three bfloat16 pieces and a product with
  zeros and ones is exact on each (`_ones_dot`);
* the 16 x 16 diagonal blocks are inverted by elimination with the tile's
  eight blocks side by side along the lanes ([16, 128]: two registers hold
  all eight); the one thing the lanes cannot do, spreading an entry over its
  block's 16 lanes, is a product with a block-of-ones matrix (exact: one
  non-zero term a sum); the fifteen steps each wait for the last, so the
  tiles of a loop body go through them together; blocks are then merged
  pairwise as X - X R X, [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1,
  Q^-1]], at `Precision.HIGHEST`: no power of N is formed.

Outputs are written chunks first, [chunks, rows x heads, 64, d], as the scan
over chunks walks them. `interpret=True` runs the same kernels under the
Pallas interpreter (CPU), which the tests use; `tiles_forward` and
`tiles_backward` are plain functions of arrays and run anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import CHUNK, SUB

assert (CHUNK, SUB) == (64, 16)  # `_Masks` shifts by their logarithms
TILE = 2 * CHUNK  # positions a program step works at once
#: tiles a program (a block of the grid): the second-minor dimension of
#: beta's block, so 8 or the whole row
TILES = 8
#: tiles worked side by side in a program's loop body: a tile is one long
#: chain of dependent products (the running sum, the solve's spread, 15
#: eliminations, four merges), and side by side the chains hide each
#: other's latency
UNROLL = 2
_SUBS = CHUNK // SUB          # sub-chunks a chunk
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_NN = (((1,), (0,)), ((), ()))


def _dot32(a, b, dims=_NN):
    """A product of the solve or of a running sum: float32 in earnest."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _pieces(x):
    """The three bfloat16 pieces that sum to a float32 x."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _ones_dot(ones, x):
    """ones @ x in float32 for a matrix of zeros and ones: three passes of
    the matrix unit -- one product over x's pieces side by side -- give what
    `_dot32`'s six give (every partial product is exact; the sums are
    float32)."""
    n = x.shape[1]
    out = lax.dot_general(ones.astype(jnp.bfloat16), jnp.concatenate(_pieces(x), axis=1),
                          _NN, preferred_element_type=_F32)
    return out[:, :n] + out[:, n:2 * n] + out[:, 2 * n:]


def _dot_ones(x, ones):
    """x @ ones, as `_ones_dot`: the pieces one under the other."""
    n = x.shape[0]
    out = lax.dot_general(jnp.concatenate(_pieces(x), axis=0), ones.astype(jnp.bfloat16),
                          _NN, preferred_element_type=_F32)
    return out[:n] + out[n:2 * n] + out[2 * n:]


def _pdot(a, b, dims, dtype):
    """A large product: operands in the policy's dtype, float32 out."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=lax.Precision.HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


def _rows_of(x, at: int, count: int):
    """Row `at` of x [n, d] laid over `count` rows."""
    return jnp.broadcast_to(x[at:at + 1, :], (count, x.shape[1]))


class _Masks:
    """The tile's index masks, [TILE, TILE] unless said (t a row, u a
    column): made from iotas in the kernel, as constants anywhere else."""

    def __init__(self):
        t = lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
        u = lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
        same = (t >> 6) == (u >> 6)                   # one chunk
        self.lower = same & (u <= t)
        self.strict = same & (u < t)
        self.upper = same & (u >= t)
        self.eye = t == u
        self.ones_block = (t >> 4) == (u >> 4)        # one 16 x 16 block
        # merging blocks of `size`: R, rows of an odd block against the
        # columns of the even block before it
        self.pair = {
            SUB: (((t >> 4) & 1) == 1) & ((u >> 4) == (t >> 4) - 1),
            2 * SUB: (((t >> 5) & 1) == 1) & ((u >> 5) == (t >> 5) - 1)}
        lane = lax.broadcasted_iota(jnp.int32, (SUB, TILE), 1)
        self.lane_block = lane >> 4                   # [SUB, TILE]
        self.lane_in_block = lane & (SUB - 1)
        self.row16 = lax.broadcasted_iota(jnp.int32, (SUB, TILE), 0)
        # position's sub-chunk inside its chunk, down the rows [TILE, 1]
        self.sub_of_row = (lax.broadcasted_iota(jnp.int32, (TILE, 1), 0) >> 4) \
            & (_SUBS - 1)


def _unit_lower_inverses(ns, m: _Masks):
    """(I + N)^-1 of each tile's N [TILE, TILE] in `ns`, strictly lower
    triangular inside each chunk and zero across chunks. The elimination is
    fifteen steps each waiting for the last, so all the tiles' blocks go
    through it together, side by side along the lanes."""
    blocks = TILE // SUB
    stacks = []
    for n_strict in ns:
        # the diagonal blocks side by side: d[r, (B, j)] = N[(B, r), (B, j)]
        d = jnp.zeros((SUB, TILE), _F32)
        for b in range(blocks):
            d = jnp.where(m.lane_block == b, n_strict[b * SUB:(b + 1) * SUB, :], d)
        stacks.append(jnp.concatenate(
            [jnp.where(m.lane_in_block == j, d, 0.0) for j in range(SUB - 1)], axis=0))
    # spread[j][r, (B, c)] = d[r, (B, j)] for every c: column j of each block
    # over the block's lanes
    rows = (SUB - 1) * SUB
    spread = _dot_ones(jnp.concatenate(stacks, axis=0), jnp.where(m.ones_block, 1.0, 0.0))
    spread = jnp.concatenate([spread[t * rows:(t + 1) * rows] for t in range(len(ns))], axis=1)
    x = jnp.where(m.row16 == m.lane_in_block, 1.0, 0.0).astype(_F32)
    x = jnp.concatenate([x] * len(ns), axis=1)                   # [SUB, tiles x TILE]
    for j in range(SUB - 1):  # eliminate column j: rows below it take row j
        x = x - spread[j * SUB:(j + 1) * SUB, :] * _rows_of(x, j, SUB)
    out = []
    for t, n_strict in enumerate(ns):
        mine = x[:, t * TILE:(t + 1) * TILE]
        inv = jnp.concatenate([jnp.where(m.lane_block == b, mine, 0.0)
                               for b in range(blocks)], axis=0)
        for size in (SUB, 2 * SUB):
            # [[P, 0], [R, Q]]^-1: only the odd blocks' rows change, by
            # -Q^-1 R P^-1, so only they go through the two products
            cut = [inv[b * size:(b + 1) * size] for b in range(TILE // size)]
            low = _dot32(_dot32(jnp.concatenate(cut[1::2], axis=0),
                                jnp.where(m.pair[size], n_strict, 0.0)), inv)
            cut[1::2] = [q - low[i * size:(i + 1) * size]
                         for i, q in enumerate(cut[1::2])]
            inv = jnp.concatenate(cut, axis=0)
        out.append(inv)
    return out


def _by_chunk(x, at: int):
    """Row `at` of each chunk of x [TILE, d] laid over that chunk's rows."""
    return jnp.concatenate([_rows_of(x, c * CHUNK + at, CHUNK)
                            for c in range(TILE // CHUNK)], axis=0)


def _sub_rows(x, sub: int):
    """The rows of sub-chunk `sub` of each chunk of x [TILE, d]."""
    return [x[c * CHUNK + sub * SUB: c * CHUNK + (sub + 1) * SUB, :]
            for c in range(TILE // CHUNK)]


def _gather_subs(parts):
    """[TILE, n] from parts[sub] = (rows of chunk 0, rows of chunk 1, ...)."""
    return jnp.concatenate([parts[sub][c] for c in range(TILE // CHUNK)
                            for sub in range(_SUBS)], axis=0)


def _pair_parts(q, k, g, beta_row, m: _Masks, dtype):
    """A tile's decays and factored pair terms."""
    g_sum = _ones_dot(jnp.where(m.lower, 1.0, 0.0), g)           # [TILE, dk]
    # R_I, the running sum in the middle of a row's own sub-chunk
    ref = jnp.concatenate([_rows_of(g_sum, i * SUB + SUB // 2 - 1, SUB)
                           for i in range(TILE // SUB)], axis=0)
    row = jnp.exp(g_sum - ref)
    k32, q32 = k.astype(_F32), q.astype(_F32)
    k_row, q_row = (k32 * row).astype(dtype), (q32 * row).astype(dtype)
    cols, k_cols, lhs, a_parts, b_parts = [], [], [], [], []
    for sub in range(_SUBS):
        # columns seen from the rows of sub-chunk `sub` of their chunk:
        # exp(R_sub - G_j), 0 for the sub-chunks after it (before the exp
        # can overflow)
        expo = _by_chunk(g_sum, sub * SUB + SUB // 2 - 1) - g_sum
        col = jnp.exp(jnp.where(m.sub_of_row <= sub, expo, -1e30))
        k_col = (k32 * col).astype(dtype)
        both = jnp.concatenate(_sub_rows(k_row, sub) + _sub_rows(q_row, sub), axis=0)
        pair = _pdot(both, k_col, _NT, dtype)                    # [4 SUB, TILE]
        cols.append(col), k_cols.append(k_col), lhs.append(both)
        a_parts.append([pair[c * SUB:(c + 1) * SUB] for c in (0, 1)])
        b_parts.append([pair[(2 + c) * SUB:(3 + c) * SUB] for c in (0, 1)])
    beta_col = jnp.sum(jnp.where(m.eye, beta_row, 0.0), axis=1, keepdims=True)
    return dict(g_sum=g_sum, row=row, k32=k32, q32=q32, cols=cols,
                k_cols=k_cols, lhs=lhs, a=_gather_subs(a_parts),
                b=_gather_subs(b_parts), beta_col=beta_col, dec=jnp.exp(g_sum),
                end=jnp.exp(_by_chunk(g_sum, CHUNK - 1) - g_sum))


def _forward_parts(xs, m: _Masks, dtype):
    """What both passes need of each tile in `xs` (its q, k, v, g, beta):
    the decays, the factored pair terms and the solve."""
    parts = [_pair_parts(q, k, g, beta_row, m, dtype) for q, k, _, g, beta_row in xs]
    invs = _unit_lower_inverses(
        [jnp.where(m.strict, p["beta_col"] * p["a"], 0.0) for p in parts], m)
    for p, inv, x in zip(parts, invs, xs):
        p["inv"], p["solve"] = inv, (inv * x[4]).astype(dtype)
    return parts


def tiles_forward(xs, dtype, m: _Masks = None):
    """For each tile in `xs` -- q, k [TILE, dk], v [TILE, dv], g [TILE, dk]
    float32, beta [1, TILE] -- the six operands of its two chunks: (W_k, W_v,
    K exp(G_C - G), Q exp G) [TILE, d], tril(B) as [TILE, CHUNK] (a row's
    columns are its own chunk's), exp G_C [2, dk]."""
    m = m or _Masks()
    return [_tile_forward(p, x[2], dtype, m)
            for p, x in zip(_forward_parts(xs, m, dtype), xs)]


def _tile_forward(p, v, dtype, m: _Masks):
    w_k = _pdot(p["solve"], p["k32"] * p["dec"], _NN, dtype)
    w_v = _pdot(p["solve"], v, _NN, dtype)
    b_low = jnp.where(m.lower, p["b"], 0.0)
    b_low = jnp.concatenate([b_low[:CHUNK, :CHUNK], b_low[CHUNK:, CHUNK:]], axis=0)
    d_end = jnp.concatenate([jnp.exp(p["g_sum"][c * CHUNK + CHUNK - 1:(c + 1) * CHUNK, :])
                             for c in (0, 1)], axis=0)
    return (w_k.astype(dtype), w_v, (p["k32"] * p["end"]).astype(dtype),
            (p["q32"] * p["dec"]).astype(dtype), b_low.astype(dtype), d_end)


def tiles_backward(xs, cots, dtype, m: _Masks = None):
    """For each tile in `xs`, (dq, dk, dv, dg [TILE, d], dbeta [1, TILE])
    from the cotangents of `tiles_forward`'s results, in its order and
    shapes."""
    m = m or _Masks()
    return [_tile_backward(p, x, cot, dtype, m)
            for p, x, cot in zip(_forward_parts(xs, m, dtype), xs, cots)]


def _tile_backward(p, x, cot, dtype, m: _Masks):
    q, k, v, g, beta_row = x
    d_wk, d_wv, d_kend, d_qdec, d_blow, d_dend = cot
    k32, q32, dec, end, inv, solve = (p[n] for n in
                                      ("k32", "q32", "dec", "end", "inv", "solve"))
    d_kend, d_qdec = d_kend.astype(_F32), d_qdec.astype(_F32)
    # W_k = T (K exp G), W_v = T V with T = M Diag(beta)
    d_solve = _pdot(d_wk, k32 * dec, _NT, dtype) + _pdot(d_wv, v, _NT, dtype)
    d_kdec = _pdot(solve, d_wk, _TN, dtype)
    d_v = _pdot(solve, d_wv, _TN, dtype)
    d_beta = jnp.sum(d_solve * inv, axis=0, keepdims=True)          # [1, TILE]
    # M = (I + N)^-1: N_bar = -M^T M_bar M^T, strictly lower inside a chunk
    d_n = -_dot32(inv, _dot32(d_solve * beta_row, inv, _NT), _TN)
    d_n = jnp.where(m.strict, d_n, 0.0)
    d_beta_col = jnp.sum(d_n * p["a"], axis=1, keepdims=True)       # [TILE, 1]
    d_beta = d_beta + jnp.sum(jnp.where(m.eye, d_beta_col, 0.0), axis=0, keepdims=True)
    d_a = p["beta_col"] * d_n
    d_b = d_blow.astype(_F32)
    d_b = jnp.where(m.lower, jnp.concatenate([d_b, d_b], axis=1), 0.0)
    # back through the factored products, a sub-chunk of rows at a time
    d_k32 = d_kdec * dec + d_kend * end
    d_gsum = (d_kdec * k32 + d_qdec * q32) * dec
    at_end = d_kend * k32 * end            # d(G_C - G): -here, +summed at the end
    d_gsum = d_gsum - at_end
    d_krow, d_qrow, at_ref = [], [], []
    for sub in range(_SUBS):
        d_pair = jnp.concatenate(_sub_rows(d_a, sub) + _sub_rows(d_b, sub), axis=0)
        d_lhs = _pdot(d_pair, p["k_cols"][sub], _NN, dtype)         # [4 SUB, dk]
        d_kcol = _pdot(d_pair, p["lhs"][sub], _TN, dtype)           # [TILE, dk]
        d_krow.append([d_lhs[c * SUB:(c + 1) * SUB] for c in (0, 1)])
        d_qrow.append([d_lhs[(2 + c) * SUB:(3 + c) * SUB] for c in (0, 1)])
        d_k32 = d_k32 + d_kcol * p["cols"][sub]
        moved = d_kcol * k32 * p["cols"][sub]          # d(R_sub - G_j)
        d_gsum = d_gsum - moved
        at_ref.append([jnp.sum(moved[c * CHUNK:(c + 1) * CHUNK], axis=0, keepdims=True)
                       for c in (0, 1)])
    d_krow, d_qrow = _gather_subs(d_krow) * p["row"], _gather_subs(d_qrow) * p["row"]
    moved = d_krow * k32 + d_qrow * q32                # d(G_t - R_I)
    d_gsum = d_gsum + moved
    d_k32, d_q32 = d_k32 + d_krow, d_qrow + d_qdec * dec
    # what lands on single rows of G: a sub-chunk's reference (its middle)
    # and a chunk's end
    in16 = lax.broadcasted_iota(jnp.int32, (SUB, g.shape[1]), 0)
    marks = []
    for c in (0, 1):
        to_end = jnp.sum(at_end[c * CHUNK:(c + 1) * CHUNK], axis=0, keepdims=True) \
            + d_dend[c:c + 1, :] * jnp.exp(
                p["g_sum"][c * CHUNK + CHUNK - 1:(c + 1) * CHUNK, :])
        for sub in range(_SUBS):
            lo = c * CHUNK + sub * SUB
            to_ref = at_ref[sub][c] - jnp.sum(moved[lo:lo + SUB], axis=0, keepdims=True)
            mark = jnp.where(in16 == SUB // 2 - 1, to_ref, 0.0)
            if sub == _SUBS - 1:
                mark = jnp.where(in16 == SUB - 1, to_end, mark)
            marks.append(mark)
    d_gsum = d_gsum + jnp.concatenate(marks, axis=0)
    d_g = _ones_dot(jnp.where(m.upper, 1.0, 0.0), d_gsum)
    return (d_q32.astype(q.dtype), d_k32.astype(k.dtype), d_v.astype(v.dtype),
            d_g, d_beta)


# -- the kernels ---------------------------------------------------------------

def _each_tile(tiles: int, load, work, store):
    """`store(i, result)` for every tile i of a program, where `work` turns
    a list of `load(i)`s into their results: `UNROLL` tiles to a loop body."""
    u = UNROLL if tiles % UNROLL == 0 else 1

    def body(i, carry):
        at = [i * u + j for j in range(u)]
        for j, outs in zip(at, work([load(j) for j in at])):
            store(j, outs)
        return carry

    lax.fori_loop(0, tiles // u, body, 0)


def _positions(i):
    return pl.ds(pl.multiple_of(i * TILE, TILE), TILE)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, wk_ref, wv_ref, kend_ref,
                qdec_ref, blow_ref, dend_ref, *, tiles: int, dtype):
    m = _Masks()

    def load(i):
        at = _positions(i)
        return (q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :], g_ref[0, at, :],
                beta_ref[0, pl.ds(i, 1), :])

    def store(i, outs):
        for ref, x in zip((wk_ref, wv_ref, kend_ref, qdec_ref, blow_ref), outs):
            for c in (0, 1):
                ref[2 * i + c, 0] = x[c * CHUNK:(c + 1) * CHUNK, :].astype(ref.dtype)
        for c in (0, 1):
            dend_ref[2 * i + c, 0] = outs[5][c:c + 1, :]

    _each_tile(tiles, load, lambda xs: tiles_forward(xs, dtype, m), store)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, wk_ref, wv_ref, kend_ref,
                qdec_ref, blow_ref, dend_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, *, tiles: int, dtype):
    m = _Masks()

    def load(i):
        at = _positions(i)
        both = lambda ref: jnp.concatenate([ref[2 * i, 0], ref[2 * i + 1, 0]], axis=0)
        return ((q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :], g_ref[0, at, :],
                 beta_ref[0, pl.ds(i, 1), :]),
                tuple(both(r) for r in (wk_ref, wv_ref, kend_ref, qdec_ref,
                                        blow_ref, dend_ref)))

    def work(loaded):
        return tiles_backward([x for x, _ in loaded], [cot for _, cot in loaded],
                              dtype, m)

    def store(i, outs):
        for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), outs):
            ref[0, _positions(i), :] = x.astype(ref.dtype)
        dbeta_ref[0, pl.ds(i, 1), :] = outs[4]

    _each_tile(tiles, load, work, store)


def padding(n: int) -> int:
    """Positions to add to a row of n: whole tiles, and for a row of more
    than a program's tiles whole programs."""
    pad = -n % TILE
    return -n % (TILE * TILES) if (n + pad) // TILE > TILES else pad


def _struct(shape, dtype, like):
    """An output's aval, varying across the mesh as `like` does (inside
    `shard_map` a plain ShapeDtypeStruct is refused; `ops.pallas_lrn`)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


_DEFAULT_SCOPED_VMEM = 16 << 20  # what Mosaic allows a kernel unasked (v5e)
#: a loop body's temporaries that leave the registers, by the policy's
#: itemsize (Mosaic asked 18.77 MB for the float32 backward at `UNROLL` 2,
#: whose blocks are 12.6 of them)
_BODY_VMEM = 3 << 20


def _specs(xs, dtype):
    """(grid, tiles a program, the inputs' block specs, the operands' block
    specs and avals, the scoped VMEM a call over both needs) for q, k [many,
    n, dk], v [many, n, dv], g float32 and beta [many, n / TILE, TILE]."""
    q, _, v, _, _ = xs
    (many, n, dk), dv = q.shape, v.shape[-1]
    n_tiles = n // TILE
    tiles = TILES if n_tiles % TILES == 0 else n_tiles
    grid = (many, n_tiles // tiles)
    vmem = pltpu.VMEM
    by_row = lambda d: pl.BlockSpec((1, tiles * TILE, d), lambda i, j: (i, j, 0),
                                    memory_space=vmem)
    beta = pl.BlockSpec((1, tiles, TILE), lambda i, j: (i, j, 0), memory_space=vmem)
    # chunks first, as the scan walks them
    by_chunk = lambda *tail: pl.BlockSpec(
        (2 * tiles, 1) + tail, lambda i, j: (j, i) + (0,) * len(tail),
        memory_space=vmem)
    nc = n // CHUNK
    out = lambda tail, dt: _struct((nc, many) + tail, dt, q)
    operands = [(by_chunk(CHUNK, dk), out((CHUNK, dk), dtype)),      # W_k
                (by_chunk(CHUNK, dv), out((CHUNK, dv), _F32)),       # W_v
                (by_chunk(CHUNK, dk), out((CHUNK, dk), dtype)),      # K exp(G_C - G)
                (by_chunk(CHUNK, dk), out((CHUNK, dk), dtype)),      # Q exp G
                (by_chunk(CHUNK, CHUNK), out((CHUNK, CHUNK), dtype)),  # tril(B)
                (by_chunk(1, dk), out((1, dk), _F32))]               # exp G_C
    ins = [by_row(dk), by_row(dk), by_row(dv), by_row(dk), beta]
    # every block double-buffered by the pipeline; the backward call has the
    # inputs' blocks twice (the gradients') beside the operands'
    blocks = 2 * sum(int(np.prod(spec.block_shape)) * jnp.dtype(dt).itemsize
                     for spec, dt in zip(ins, [x.dtype for x in xs])) \
        + sum(int(np.prod(spec.block_shape)) * aval.dtype.itemsize
              for spec, aval in operands)
    need = max(_DEFAULT_SCOPED_VMEM,
               2 * blocks + _BODY_VMEM * jnp.dtype(dtype).itemsize)
    return grid, tiles, ins, operands, need


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunk_operands(q, k, v, g, beta, dtype, interpret: bool = False):
    """`ops.delta_rule._chunk_operands` as a kernel: from q, k [many, n, dk],
    v [many, n, dv], g [many, n, dk] float32 and beta [many, n] float32 (n a
    multiple of `TILE`, dk and dv of 128 lanes), the six operands of every
    chunk, CHUNKS FIRST: (W_k, W_v, K exp(G_C - G), exp G_C, Q exp G,
    tril(B)) as [n / 64, many, 64, d] ([n / 64, many, dk] for exp G_C), the
    products' operands in `dtype` (the precision policy's)."""
    many, n, _ = q.shape
    beta = beta.reshape(many, n // TILE, TILE)
    grid, tiles, ins, operands, vmem = _specs((q, k, v, g, beta), dtype)
    w_k, w_v, k_end, q_dec, b_low, d_end = pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, dtype=dtype),
        grid=grid, in_specs=ins,
        out_specs=[spec for spec, _ in operands],
        out_shape=[aval for _, aval in operands],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="delta_chunk_fwd",  # the kernel's stable name in a device trace
    )(q, k, v, g, beta)
    return w_k, w_v, k_end, d_end[:, :, 0, :], q_dec, b_low


def _chunk_operands_fwd(q, k, v, g, beta, dtype, interpret):
    # the residuals are the inputs alone: the backward kernel makes the rest
    return chunk_operands(q, k, v, g, beta, dtype, interpret), (q, k, v, g, beta)


def _chunk_operands_bwd(dtype, interpret, res, cot):
    q, k, v, g, beta = res
    d_wk, d_wv, d_kend, d_dend, d_qdec, d_blow = cot
    many, n, _ = q.shape
    tiled = beta.reshape(many, n // TILE, TILE)
    grid, tiles, ins, operands, vmem = _specs((q, k, v, g, tiled), dtype)
    grads = [_struct(x.shape, x.dtype, q) for x in (q, k, v, g, tiled)]
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, dtype=dtype),
        grid=grid, in_specs=ins + [spec for spec, _ in operands],
        out_specs=ins, out_shape=grads,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="delta_chunk_bwd",
    )(q, k, v, g, tiled,
      d_wk, d_wv, d_kend, d_qdec, d_blow, d_dend[:, :, None, :])
    return d_q, d_k, d_v, d_g, d_beta.reshape(many, n)


chunk_operands.defvjp(_chunk_operands_fwd, _chunk_operands_bwd)
