"""The gated delta rule with a per-channel decay (Kimi Delta Attention's
recurrence, arXiv:2510.26692), causal, one matrix state a head:

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                                   S_0 = 0, S [dk, dv]

over q, k [..., n, dk], v [..., n, dv], the log-decay g [..., n, dk] <= 0 and
the writing strength b [..., n] in (0, 1). `delta_rule_recurrent` is the
recurrence a position at a time (the oracle of the tests); `gated_delta_rule`
is the form that runs: CHUNKED, so that the work is matrix products and the
sequential part is one step a chunk. WHICH form a layer runs is decided here
and nowhere else, from what this module can observe (no option, no enum):
both stages -- the chunk stage, what a chunk's step of the walk reads, and
the WALK over the chunks that carries the state -- are Pallas kernel pairs
where a Pallas call may run (`ops.lrn.pallas_backend`: the TPU, or any
backend under the interpreter) and the shape is the kernels' (`_can_pallas`,
one gate for both: chunks of 64, heads whose widths are multiples of the 128
lanes): `pallas_delta_rule` makes the six operands of every chunk,
`pallas_delta_scan` walks them with the float32 state in VMEM. Everywhere
else -- narrow heads, short rows, any other backend -- `_chunk_operands` in
plain `jnp` makes the operands and one `lax.scan` walks them. The `jnp` form
is the kernels' oracle (`tests/test_delta_rule.py`,
`tests/test_delta_scan_kernel.py`). What stands BEFORE the chunk stage is
the layer's own (`ops.kda_shape`: the convolutions, norms and gates that make
q, k, v, g and b from the projections); where that stage runs as its kernel
pair it writes q, k, v in the policy's dtype and g in float32, whole programs
of positions a row, so `gated_delta_rule`'s own casts and pads are no-ops and
nothing stands between that kernel's results and this one's operands.

The chunked (WY) form. With a_t = exp g_t and u_t = b_t (v_t - S_{t-1}^T (a_t
* k_t)) the update is S_t = Diag(a_t) S_{t-1} + k_t u_t^T, so inside a chunk
of C positions that starts from the state S, with G_t the running sum of g
inside the chunk,

    (I + Diag(b) strict_tril(A)) U = Diag(b) (V - (K * exp G) S)
    O = (Q * exp G) S + tril(B) U
    S' = Diag(exp G_C) S + (K * exp(G_C - G))^T U

where A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c]) and B the same with
q_t. The solve is done once a chunk on the identity's right-hand side
(`_unit_lower_inverse`: forward substitution in blocks of 16, merged by
products), and `W_v = T V`, `W_k = T (K * exp G)` with T = (I + ...)^-1
Diag(b) turn the chunk into U = W_v - W_k S: the scan over chunks carries S
and does four products a step; what it reads is made for all chunks at once.

Why sub-chunks. The decay is a VECTOR a head, so exp(G_t - G_j) stands inside
the contraction over channels and A is a matrix product only if it factors
as exp(G_t - R) * exp(R - G_j) around some reference R. Over a chunk of 64
positions with g down to -5 the second factor reaches exp(320): float32
overflows at exp(88). So the reference is taken a SUB-CHUNK of `SUB` = 16
positions at a time: for the rows of sub-chunk I, R_I is the running sum in
the middle of I; exp(G_t - R_I) and, for the columns of I itself, exp(R_I -
G_j) then lie within exp(+-5 * 8) = exp(+-40), which neither overflows nor
-- times a small key -- falls under float32's smallest normal number (a
reference at the sub-chunk's start does: exp(-80) times 1e-3 is flushed to
zero and a channel is lost); for every column before I the difference is
taken first and exp(R_I - G_j) <= 1. `MIN_LOG_DECAY` = -5 is the bound this
rests on; the layer's gate keeps g inside it (the published
`kda_lower_bound`).

Float32 for the decays, their running sums, the solve and the state; the
precision policy's dtype for the operands of the large products (float32
accumulation). The backward passes on the kernel path are the kernels' OWN
(two `jax.custom_vjp`s): the chunk stage's residuals are its five inputs
(the backward kernel makes the forward's intermediates again in VMEM and,
with (I + N)^-1 at hand, needs no substitution), the walk's are the six
operands (its backward pass first makes the state every segment of `SEGMENT`
chunks started from, `segment_states`, a scan of two products a chunk; its
backward kernel then makes a segment's chunk-start states again in VMEM and
walks the segments in reverse). In the `jnp` form both are autodiff: of the
scan under a `jax.checkpoint` a segment of chunks, which keeps one state a
segment, and of the chunk stage under a second `jax.checkpoint` that keeps
the inputs and makes the operands again a segment at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import precision
from .lrn import pallas_backend

#: positions a chunk (one step of the sequential scan) and a sub-chunk (one
#: reference point of the factored decay); SUB * -MIN_LOG_DECAY must stay
#: under float32's exp range (88)
CHUNK = 64
SUB = 16
MIN_LOG_DECAY = -5.0
#: chunks a segment: whose operands are made together (the `jnp` form), and
#: which the walk's backward pass makes again from the one state it started
#: from (both forms)
SEGMENT = 8


def delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence, a position at a time, in float32: the definition the
    chunked form is held to. Returns (o [..., n, dv], the last state
    [..., dk, dv])."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), -2, 0)
    q, k, v, g = f32(q), f32(k), f32(v), f32(g)
    beta = jnp.moveaxis(beta.astype(jnp.float32), -1, 0)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("...kv,...k->...v", s, k_t,
                                               precision=lax.Precision.HIGHEST))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("...kv,...k->...v", s, q_t,
                             precision=lax.Precision.HIGHEST)

    s0 = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)
    s, o = lax.scan(step, s0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, -2), s


def _mm(spec: str, a, b):
    """A large product: operands in the precision policy's dtype, float32
    out."""
    return jnp.einsum(spec, precision.cast_in(a), precision.cast_in(b),
                      precision=precision.matmul_precision(),
                      preferred_element_type=jnp.float32)


def _mm32(spec: str, a, b):
    """A product of the solve: float32 in earnest."""
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(n_strict):
    """(I + N)^-1 for N [..., C, C] strictly lower triangular, float32:
    forward substitution a row at a time inside diagonal blocks of `SUB`
    (every row a short product with the rows above it), then blocks merged
    pairwise, [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]. No
    power of N is ever formed, so nothing grows where the keys of a chunk
    are alike."""
    c = n_strict.shape[-1]
    size = min(SUB, c)
    lead = n_strict.shape[:-2]

    def block(size, i, j):  # block (i, j) of N cut into size x size blocks
        return n_strict[..., i * size:(i + 1) * size, j * size:(j + 1) * size]

    diag = jnp.stack([block(size, i, i) for i in range(c // size)], axis=-3)
    eye = jnp.eye(size, dtype=jnp.float32)
    rows = [jnp.broadcast_to(eye[0], lead + (c // size, size))]
    for i in range(1, size):
        above = jnp.stack(rows, axis=-2)           # [..., i, size]
        rows.append(eye[i] - jnp.sum(diag[..., i, :i, None] * above, axis=-2))
    inv = jnp.stack(rows, axis=-2)                 # the diagonal blocks' inverses
    while size < c:
        # neighbours 2j, 2j + 1 -> one block of twice the size
        pairs = inv.reshape(lead + (c // size // 2, 2, size, size))
        p, q = pairs[..., 0, :, :], pairs[..., 1, :, :]
        r = jnp.stack([block(size, 2 * j + 1, 2 * j)
                       for j in range(c // size // 2)], axis=-3)
        low = -_mm32("...ab,...bc->...ac", _mm32("...ab,...bc->...ac", q, r), p)
        inv = jnp.concatenate(
            [jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
             jnp.concatenate([low, q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _pair_terms(q, k, g_sum):
    """(A, B) [..., C, C] of one chunk from q, k [..., C, dk] and the running
    sum of the log-decay `g_sum` (float32): A_tj = sum_c k_t k_j exp(G_t -
    G_j), B the same with q_t, for j <= t (what lies above the diagonal is
    left unmasked and finite). Factored a sub-chunk of rows at a time around
    R_I, the running sum in the middle of sub-chunk I."""
    c, dk = k.shape[-2:]
    sub = min(SUB, c)
    s = c // sub
    lead = k.shape[:-2]
    by_sub = lambda x: x.reshape(lead + (s, sub) + x.shape[-1:])
    ref = by_sub(g_sum)[..., sub // 2 - 1, :]                # R_I [..., s, dk]
    row = jnp.exp(by_sub(g_sum) - ref[..., None, :])
    # columns of sub-chunk J seen from rows of I: exp(R_I - G_j), <= 1 for
    # J < I, inside the same half range for J = I, not needed for J > I (set
    # to 0 before the exp can overflow)
    seen = ((jnp.arange(c) // sub)[None, :] <= jnp.arange(s)[:, None])[..., None]
    expo = ref[..., :, None, :] - g_sum[..., None, :, :]     # [..., s, C, dk]
    col = jnp.where(seen, jnp.exp(jnp.where(seen, expo, 0.0)), 0.0)
    k32 = k.astype(jnp.float32)
    k_col = k32[..., None, :, :] * col                       # [..., s, C, dk]
    spec = "...itc,...ijc->...itj"
    a = _mm(spec, by_sub(k32) * row, k_col)
    b = _mm(spec, by_sub(q.astype(jnp.float32)) * row, k_col)
    return a.reshape(lead + (c, c)), b.reshape(lead + (c, c))


def _chunk_operands(q, k, v, g, beta):
    """What the scan over chunks reads, from q, k [..., C, dk], v [..., C,
    dv], g [..., C, dk] and beta [..., C] cut into chunks: (W_k, W_v, K *
    exp(G_C - G), exp G_C, Q * exp G, tril(B)), the products' operands
    already in the precision policy's dtype."""
    c = q.shape[-2]
    g_sum = jnp.cumsum(g, axis=-2)
    beta = beta[..., None]
    a, b = _pair_terms(q, k, g_sum)
    t_idx = jnp.arange(c)
    strict, lower = (t_idx[:, None] > t_idx[None, :],
                     t_idx[:, None] >= t_idx[None, :])
    solve = _unit_lower_inverse(jnp.where(strict, beta * a, 0.0)) \
        * jnp.swapaxes(beta, -1, -2)                          # T = (..)^-1 Diag(b)
    dec = jnp.exp(g_sum)                                      # exp G_t, <= 1
    k32 = k.astype(jnp.float32)
    w_k = _mm("...tj,...jd->...td", solve, k32 * dec)
    w_v = _mm("...tj,...jd->...td", solve, v)
    g_end = g_sum[..., -1:, :]                                # G_C [..., 1, dk]
    cast = precision.cast_in
    return (cast(w_k), w_v, cast(k32 * jnp.exp(g_end - g_sum)),
            jnp.exp(g_end[..., 0, :]), cast(q.astype(jnp.float32) * dec),
            cast(jnp.where(lower, b, 0.0)))


def _can_pallas(c: int, dk: int, dv: int, interpret: bool) -> bool:
    """The kernel's shape: whole chunks of `CHUNK` positions, heads whose
    widths fill the 128 lanes; and a backend a Pallas call may run on."""
    return pallas_backend(interpret) and c == CHUNK \
        and dk % 128 == 0 and dv % 128 == 0


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK, *,
                     interpret: bool = False):
    """o [..., n, dv] (float32) of the gated delta rule, chunked. `g` must
    lie in [MIN_LOG_DECAY, 0]. A length that is no multiple of the chunk is
    padded at its end with positions that neither write nor decay.

    Two stages. What a chunk's step reads (the decays' running sums, the
    factored pair terms, the solve) is made for every leading entry (row,
    head) together and laid out chunks first, as the walk takes them; the
    walk over chunks then carries the state and does four products a step.
    Where `_can_pallas` holds both are kernel pairs: `pallas_delta_rule`'s
    (its custom VJP keeps the inputs alone) and `pallas_delta_scan`'s (the
    state in VMEM across a row's chunks, the result written as rows; its
    custom VJP keeps the operands alone and its backward pass makes one
    state a segment before the kernel walks back). Elsewhere the
    operands are `_chunk_operands`' a segment of `SEGMENT` chunks at a time
    in a checkpointed `lax.map`, whose temporaries -- a dozen tensors of the
    inputs' size -- the backward pass makes again a segment at a time and
    never holds whole, and the walk is a `lax.scan` whose every segment is a
    checkpoint, so the backward pass keeps one state a segment.

    interpret: run the kernels under the Pallas INTERPRETER (the CPU parity
      tests of the path the chip runs), as `ops.lrn.lrn` does."""
    assert chunk % SUB == 0 and (chunk // SUB) & (chunk // SUB - 1) == 0, chunk
    n, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    lead = q.shape[:-2]
    c = chunk
    while c // 2 >= max(n, SUB):  # a short row: the smallest chunk that holds it
        c //= 2
    many = int(np.prod(lead, dtype=np.int64))
    kernel = _can_pallas(c, dk, dv, interpret)
    if kernel:  # (they import this module)
        from . import pallas_delta_rule as pk, pallas_delta_scan as ps
    pad = pk.padding(n) if kernel else -n % c
    nc = (n + pad) // c
    seg = max(d for d in range(1, SEGMENT + 1) if nc % d == 0)

    def flat(x, trailing):  # [..., n(, d)] -> [many, n + pad(, d)]
        x = x.reshape((many,) + x.shape[len(lead):])
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * trailing)

    xs = (flat(q, 1), flat(k, 1), flat(v, 1), flat(g.astype(jnp.float32), 1),
          flat(beta.astype(jnp.float32), 0))
    if kernel:
        dtype = precision.compute_dtype()
        o = ps.scan_chunks(*pk.chunk_operands(*xs, dtype, interpret), seg,
                           dtype, interpret)                 # [many, nc * c, dv]
        return o.reshape(lead + (nc * c, dv))[..., :n, :]
    xs = tuple(x.reshape((many, nc, c) + x.shape[2:]) for x in xs)

    def operands(i):
        """Segment i's chunks of every leading entry, chunks leading (as
        the scan walks them): [seg, many, C, ..]. Sliced out of the
        closed-over inputs: handing the map the segments as inputs of
        its own (transposed to lead) was 13 ms a layer-step SLOWER on
        the chip (PERF.md section 6, PR 33)."""
        part = (lax.dynamic_slice_in_dim(x, i * seg, seg, axis=1) for x in xs)
        return tuple(jnp.moveaxis(x, 1, 0) for x in _chunk_operands(*part))

    ops = lax.map(jax.checkpoint(operands), jnp.arange(nc // seg))
    return _walk(ops).reshape(lead + (nc * c, dv))[..., :n, :]


def _walk(ops):
    """The walk over chunks in plain `jnp`: o [many, chunks, C, dv] from the
    six operands of every chunk, segments then chunks first ([segments, seg,
    many, C, d]; exp G_C [segments, seg, many, dk]), the state zero before
    the first. A `lax.scan` over segments of a `lax.scan` over a segment's
    chunks, four products a step; each segment is a `jax.checkpoint`, so
    autodiff keeps one state a segment."""
    segments, seg, many, c, dv = ops[1].shape

    def step(s, x):
        w_k, w_v, k_end, d_end, q_dec, b_low = x
        u = w_v - _mm("ltk,lkv->ltv", w_k, s)
        o = _mm("ltk,lkv->ltv", q_dec, s) + _mm("ltj,ljv->ltv", b_low, u)
        return d_end[..., None] * s + _mm("ltk,ltv->lkv", k_end, u), o

    # zeros of the inputs' own kind (inside `shard_map`: varying as they are)
    s0 = jnp.zeros_like(ops[3][0, 0, :, :, None]) \
        * jnp.zeros_like(ops[1][0, 0, :, :1, :])
    _, o = lax.scan(jax.checkpoint(lambda s, x: lax.scan(step, s, x)), s0, ops)
    return jnp.moveaxis(o.reshape((segments * seg, many, c, dv)), 0, 1)


def segment_states(w_k, w_v, k_end, d_end, seg: int):
    """The float32 state every segment of `seg` chunks starts from,
    [segments, many, dk, dv], from four of the chunks' operands, chunks first
    ([chunks, many, C, d]; exp G_C [chunks, many, dk]): `_walk`'s two
    products that make the next state (U = W_v - W_k S, S' = Diag(exp G_C) S
    + K_end^T U) and nothing of its result, as the same scan of scans. What
    the kernels' backward walk starts from (`pallas_delta_scan`)."""
    def step(s, x):
        w_k, w_v, k_end, d_end = x
        u = w_v - _mm("ltk,lkv->ltv", w_k, s)
        return d_end[..., None] * s + _mm("ltk,ltv->lkv", k_end, u), None

    by_segment = lambda x: x.reshape((x.shape[0] // seg, seg) + x.shape[1:])
    s0 = jnp.zeros_like(d_end[0, :, :, None]) * jnp.zeros_like(w_v[0, :, :1, :])
    return lax.scan(lambda s, x: (lax.scan(step, s, x)[0], s), s0,
                    tuple(map(by_segment, (w_k, w_v, k_end, d_end))))[1]
