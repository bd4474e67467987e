"""The Mamba-2 state-space scan (SSD, arXiv:2405.21060): a scalar decay a
head, one matrix state a head, causal:

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t       a_t = exp(dt_t A), A < 0
    y_t = S_t C_t                              S_0 = 0, S [P, N]

over x [rows, n, heads, P], the time steps dt [rows, n, heads] > 0, A [heads]
and B, C [rows, n, groups, N]; head h reads group h // (heads / groups). (The
layer adds the skip D x_t and everything after; `model.seq_layers`.) A row
may hold SEVERAL DOCUMENTS, one behind another (`runs`, `document_runs`):
the state is then zero at every document's first position, so no position
reads anything of a document before its own.
`ssd_recurrent` is the recurrence a position at a time, in float32 (the
oracle of the tests); `ssd` is the form that runs: CHUNKED, so that the work
is matrix products and the sequential part is one step a chunk. WHICH form
of it a layer runs is decided here and nowhere else, from what this module
can observe (no option, no enum): the Pallas kernel pair of `pallas_ssd`
where a Pallas call may run (`ops.lrn.pallas_backend`: the TPU, or any
backend under the interpreter) and the shape is the kernels'
(`program_heads`: whole chunks of 128 or 256 positions, a state of whole lane
rows, a group's heads in whole tiles of 128 lanes -- two heads of 64 share
one), and plain `jnp` everywhere else: narrow heads, short rows, any other
backend. The `jnp` form is the kernels' oracle (`tests/test_ssd_kernel.py`).

The chunked form. Inside a chunk of Q positions that starts from the state
S, with L_t the running sum of dt A inside the chunk (float32, <= 0 and
falling),

    y_t = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s  +  exp(L_t) S C_t
    S'  = exp(L_Q) S + sum_s exp(L_Q - L_s) dt_s x_s (x) B_s

Every exponent is a DIFFERENCE of running sums taken before the exp, and
<= 0 where it is used (what lies above the diagonal is masked before the
exp): nothing is a ratio of two exps, so no decay, however strong, leaves
float32's range. The first sum is two batched products a chunk (the
[Q, Q] scores C B^T a group, and their masked, decayed copy a head with x);
the second term and the chunk's own contribution to the state are one
product each. Under document ids a CUT is a mask on those same exponents,
set before the exp like the diagonal's: the square keeps the pairs (t, s) of
one document; a position reads the incoming state while no document has
begun in its chunk before or at it; a position writes into the state handed
on if the chunk's last position is of its document; and the state passes
through a chunk whole only if no document begins inside. Nothing is skipped
by document: the work is the same wherever the boundaries fall, any number
of them anywhere in a chunk. In the `jnp` form a `lax.scan` over the chunks
carries the float32 state and does no product at all: S' = exp(L_Q) S + (the chunk's
own), emitting the state every chunk STARTS from, which the second term
reads for all chunks at once; every chunk's [Q, Q] squares and its own
contribution pass through HBM. In the kernels a program works one chunk of
one group's heads (a block of at most `PROGRAM_TILES` lane tiles of them: a
group of 64 heads is four programs a chunk of 128, eight a chunk of 256), the
grid's last axis walks a row's chunks in order and the state lives in VMEM
across the walk: the squares never leave the chip
(`pallas_ssd`: 14.8 -> 5.6 ms a lone layer-call under `ssd` on the chip, PERF.md
section 5, PR 48).

Float32 for the time steps, the running sums, the decays and the state; the
precision policy's dtype for the operands of the four products (float32
accumulation), in both forms. The backward pass of the `jnp` form is
autodiff: the scan keeps the one state a chunk it emits anyway. The kernels'
is their own (a `jax.custom_vjp`): the forward rule writes the float32 state
every chunk started from beside y, the residuals are those and the inputs,
and the backward kernel walks the chunks in reverse with the state's
cotangent in VMEM, making the squares again there. The running sums
themselves, `cumsum(dt A)`, are made here in `jnp` for both forms, so dA and
the time steps' second path are autodiff's either way. The layer's
recomputation block decides what of the rest is held (nothing: PERF.md
section 6, PR 42).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .. import precision
from .lrn import pallas_backend

#: positions a chunk (one step of the sequential scan) where the caller names
#: none: a layer hands in its published `chunk_size`
CHUNK = 128
LANES = 128
#: the longest chunk the kernels walk (a chunk's [Q, Q] squares are VMEM
#: values: 256 KB each in float32 at 256), and the most lane tiles of heads
#: one program works in its unrolled loop at chunks of 128 (8: a group of 16
#: heads of 64, the shape PR 48's kernels were read at; a wider group is
#: split over the grid). A program's blocks of x, y, dy and dx grow with the
#: chunk, so at 256 it works half as many: with float32 operands the
#: backward kernel's blocks and temporaries are 22.9 MB at 8 tiles of 256
#: positions, against the 16 MiB of scoped VMEM a call has unasked
#: (`tests/test_chip_compile.py`, `-k ssd_kernels`)
KERNEL_CHUNK = 256
PROGRAM_TILES = 8


def document_runs(docs):
    """Document ids [rows, n] (equal along a document, changing at every
    document's first position; any integers) as RUNS [rows, n] int32: 0 along
    a row's first document and one more from every position whose id differs
    from the one before it. Two positions are of one document exactly where
    their runs are equal, whatever the ids were, and a row's last run is the
    number of boundaries in it."""
    change = (docs[:, 1:] != docs[:, :-1]).astype(jnp.int32)
    return jnp.pad(jnp.cumsum(change, axis=1), ((0, 0), (1, 0)))


def _by_head(t, heads: int):
    """B or C [..., groups, N] as every head reads it, [..., heads, N]."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd_recurrent(x, dt, a, b, c, runs=None):
    """The recurrence, a position at a time, in float32: the definition the
    chunked form is held to; with `runs` (`document_runs`) the state is set
    to zero at every document's first position. Returns (y [rows, n, heads,
    P], the last state [rows, heads, P, N])."""
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)
    heads = x.shape[2]
    x, dt, b, c = f32(x), f32(dt), f32(_by_head(b, heads)), f32(_by_head(c, heads))
    first = jnp.zeros(x.shape[:2], bool) if runs is None else jnp.moveaxis(
        jnp.pad(runs[:, 1:] != runs[:, :-1], ((0, 0), (1, 0))), 1, 0)

    def step(s, at):
        x_t, dt_t, b_t, c_t, first_t = at
        s = jnp.where(first_t[:, None, None, None], 0.0, s)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return s, jnp.einsum("rhpn,rhn->rhp", s, c_t,
                             precision=lax.Precision.HIGHEST)

    s0 = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    s, y = lax.scan(step, s0, (x, dt, b, c, first))
    return jnp.moveaxis(y, 0, 1), s


def _mm(spec: str, a, b):
    """A large product: operands in the precision policy's dtype, float32
    out."""
    return jnp.einsum(spec, precision.cast_in(a), precision.cast_in(b),
                      precision=precision.matmul_precision(),
                      preferred_element_type=jnp.float32)


def tile_heads(head_dim: int) -> int:
    """Heads that share a tile of lanes in the kernels: 128 / head_dim of
    them for narrow heads, one for a head of whole lane rows; 0 for a width
    that is neither."""
    if head_dim % LANES == 0:
        return 1
    return LANES // head_dim if LANES % head_dim == 0 else 0


def program_heads(q: int, per: int, p: int, n_state: int) -> int:
    """The heads of a group one kernel program works at a chunk of `q`
    positions, `per` heads of `p` channels a group and a state of `n_state`:
    the group's heads in the fewest equal blocks of at most `PROGRAM_TILES`
    lane tiles (x 128 / `q`: half as many at chunks of 256); 0 where the
    shape is not the kernels' (chunks that are no whole lane rows or longer
    than `KERNEL_CHUNK`, a state of no whole lane rows, heads that fill no
    whole lane tiles)."""
    hp = tile_heads(p)
    if q % LANES or q > KERNEL_CHUNK or n_state % LANES or not hp or per % hp:
        return 0
    tiles = per // hp
    blocks = next(k for k in range(1, tiles + 1)
                  if tiles % k == 0 and tiles // k <= PROGRAM_TILES * LANES // q)
    return per // blocks


def ssd(x, dt, a, b, c, chunk: int = CHUNK, *, runs=None,
        interpret: bool = False):
    """y [rows, n, heads, P] (float32) of the scan, chunked. A length that is
    no multiple of the chunk is padded at its end with positions whose time
    step is 0: they neither write nor decay, and their results are cut off.

    runs: None (a row is one document), or `document_runs` of the rows'
      document ids [rows, n]: the state is cut at every document's first
      position.
    interpret: run the kernels under the Pallas INTERPRETER (the CPU parity
      tests of the path the chip runs), as `ops.lrn.lrn` does."""
    rows, n, heads, p = x.shape
    groups, per = b.shape[2], heads // b.shape[2]
    q = min(chunk, n)
    pad = -n % q
    nc = (n + pad) // q

    def chunks(t, mode="constant"):  # [rows, n, ..] -> [rows, nc, q, ..]
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2), mode=mode)
        return t.reshape((rows, nc, q) + t.shape[2:])

    x, b, c = chunks(x), chunks(b), chunks(c)
    dt = jnp.swapaxes(chunks(dt.astype(jnp.float32)), 2, 3)   # [r, nc, h, q]
    run = jnp.cumsum(dt * a.astype(jnp.float32)[:, None], axis=-1)  # L
    rel = None
    if runs is not None:
        # a chunk's runs counted from the run its first position would
        # continue (the last of the chunk before): 0 = the incoming document
        runs = chunks(runs, "edge")
        rel = runs - jnp.pad(runs[:, :-1, -1:], ((0, 0), (1, 0), (0, 0)))
    at_once = pallas_backend(interpret) and program_heads(q, per, p, b.shape[-1])
    if at_once:
        from . import pallas_ssd  # (it imports this module)
        flat = lambda t: precision.cast_in(t).reshape(rows, nc * q, -1)
        by_group = lambda t: t.reshape(rows, nc, heads // at_once, at_once, q)
        as_rows = by_group(dt), by_group(run)
        as_cols = (jnp.swapaxes(t, -1, -2) for t in as_rows)
        cuts = () if rel is None else (rel[:, :, None, None, :],
                                       rel[:, :, None, :, None])
        y = pallas_ssd.ssd_chunks(
            flat(x), flat(b), flat(c), *as_rows, *as_cols, cuts, p,
            b.shape[-1], precision.compute_dtype(), interpret)
        return y.reshape(rows, nc * q, heads, p)[:, :n]
    end = run[..., -1]                                        # L_Q [r, nc, h]

    # inside a chunk: scores a group, masked and decayed a head. Heads
    # before positions: the [q, q] squares lie in the two minor axes
    t_idx = jnp.arange(q)
    lower = t_idx[:, None] >= t_idx[None, :]                  # [t, s]
    if rel is not None:
        # the cuts, each a mask on an exponent before its exp: the square's
        # pairs of one document; what writes into the state handed on (the
        # last position's document); the state through a chunk no document
        # begins in; what reads the incoming state (the incoming document)
        by_heads = lambda m: m[:, :, None]                    # [r, nc, 1, ..]
        lower = lower & by_heads(rel[..., :, None] == rel[..., None, :])
        cut = lambda keep, t: jnp.where(keep, t, -jnp.inf)
        tail, whole, incoming = (by_heads(rel == rel[..., -1:]),
                                 rel[..., -1:] == 0, by_heads(rel == 0))
    else:
        cut = lambda keep, t: t
        tail = whole = incoming = None
    diff = run[..., :, None] - run[..., None, :]              # L_t - L_s
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))         # [r, nc, h, t, s]
    scores = _mm("rctgn,rcsgn->rcgts", c, b)                  # C_t . B_s
    mix = (scores[:, :, :, None] * (decay * dt[..., None, :]).reshape(
        (rows, nc, groups, per, q, q))).reshape(decay.shape)
    y = _mm("rchts,rcshp->rcthp", mix, x)

    # the chunk's own contribution to the state it hands on
    to_end = jnp.moveaxis(jnp.exp(cut(tail, end[..., None] - run)) * dt, 2, 3)
    x_end = x.astype(jnp.float32) * to_end[..., None]         # [r, nc, s, h, P]
    own = _mm("rcsgjp,rcsgn->rcgjpn",
              x_end.reshape(x.shape[:3] + (groups, per, p)), b)
    own = own.reshape((rows, nc, heads) + own.shape[-2:])     # [r, nc, h, P, N]

    if nc > 1:  # (one chunk starts from the zero state and hands nothing on)
        def step(s, at):
            a_end, own_c = at
            return jnp.exp(a_end)[..., None, None] * s + own_c, s

        _, before = lax.scan(step, jnp.zeros_like(own[:, 0]),
                             (jnp.moveaxis(cut(whole, end), 1, 0),
                              jnp.moveaxis(own, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                   # [r, nc, h, P, N]
        y = y + jnp.moveaxis(jnp.exp(cut(incoming, run)), 2, 3)[..., None] * _mm(
            "rctgn,rcgjpn->rctgjp", c,
            before.reshape((rows, nc, groups, per) + before.shape[-2:])
        ).reshape(y.shape)
    return y.reshape(rows, nc * q, heads, p)[:, :n]
