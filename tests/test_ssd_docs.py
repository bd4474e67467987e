"""Rows that hold several documents, in the three places a cut is made: the
Mamba-2 scan (`ops.ssd`: the `jnp` form and the Pallas kernel pair under the
interpreter), the short causal taps and the attention core (the exact path
and the splash kernel under the interpreter). Each is held to the SAME
function run on every document ALONE -- values and gradients --, with a
boundary at a chunk's first position, in its middle and at its last, a
one-position document and a document longer than a chunk; and a row that is
one document gives the bits it gave without ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import ApplyCtx
from sparknet_tpu.ops import ssd as ssd_ops

P, N = 64, 128  # the published head and state widths: the kernels' shape


def _documents(q: int, n: int):
    """Two rows of `n` positions (three chunks of `q`): document ids that are
    not consecutive, with a boundary at a chunk's first position (q), a
    one-position document after it, a boundary in a chunk's middle, one at a
    chunk's last position (2q - 1 starts a document), a document longer than
    a chunk; the second row starts with a short document and ends with one
    that spans two boundaries of chunks."""
    assert n == 3 * q
    lens = [[q, 1, q // 2 - 2, q // 2, 1 + q], [5, n - 5]]
    docs = np.concatenate([np.repeat(7 * np.arange(len(l)) + 3, l)[None] for l in lens])
    assert docs.shape == (2, n)
    starts = [np.flatnonzero(np.diff(r)) + 1 for r in docs]
    assert set(starts[0]) == {q, q + 1, q + q // 2 - 1, 2 * q - 1} and list(starts[1]) == [5]
    return jnp.asarray(docs, jnp.int32), lens


@functools.cache
def _operands(q: int, heads: int):
    n = 3 * q
    ks = jax.random.split(jax.random.PRNGKey(q + heads), 6)
    x = jax.random.normal(ks[0], (2, n, heads, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, n, heads)) - 2.0)
    # decays from a head that forgets in three positions to one that keeps
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-4.0, maxval=1.0))
    b = 0.3 * jax.random.normal(ks[3], (2, n, 1, N))
    c = 0.3 * jax.random.normal(ks[4], (2, n, 1, N))
    return (x, dt, a, b, c), jax.random.normal(ks[5], (2, n, heads, P))


def _each_document_alone(fn, lens, *rows):
    """fn(row slices [1, len, ..]) on every document of every row alone,
    put back together: [rows, n, ..]."""
    out = []
    for r, row in enumerate(lens):
        start, parts = 0, []
        for length in row:
            parts.append(fn(*(t[r:r + 1, start:start + length] for t in rows))[0])
            start += length
        out.append(jnp.concatenate(parts))
    return jnp.stack(out)


@functools.cache
def _alone(q: int, heads: int):
    """(y, gradients of sum(y w)) of the RECURRENCE on every document alone."""
    (x, dt, a, b, c), w = _operands(q, heads)
    _, lens = _documents(q, 3 * q)

    def y(x, dt, a, b, c):
        return _each_document_alone(
            lambda *t: ssd_ops.ssd_recurrent(t[0], t[1], a, t[2], t[3])[0],
            lens, x, dt, b, c)

    return jax.jit(y)(x, dt, a, b, c), jax.jit(jax.grad(
        lambda *t: jnp.sum(y(*t) * w), argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)


@pytest.mark.parametrize("form", ["jnp", "kernels"])
@pytest.mark.parametrize("q,heads", [(128, 4), (256, 20)],
                         ids=["chunk128", "chunk256-blocks"])
def test_the_scan_under_document_ids_is_the_recurrence_on_each_document_alone(q, heads, form):
    """One group of many heads (twenty at chunks of 256: five programs of
    four heads a group and chunk in the kernels, dB and dC summed over them).
    Both forms, values and every gradient, against the recurrence a position
    at a time run on each document by itself."""
    (x, dt, a, b, c), w = _operands(q, heads)
    docs, _ = _documents(q, 3 * q)
    runs = ssd_ops.document_runs(docs)
    assert list(np.asarray(runs[:, -1])) == [4, 1]
    if form == "kernels":
        assert ssd_ops.program_heads(q, heads, P, N) == (4 if q == 256 else heads)
    fn = lambda *t: ssd_ops.ssd(*t, q, runs=runs, interpret=form == "kernels")
    want, want_grads = _alone(q, heads)
    got = jax.jit(fn)(x, dt, a, b, c)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    grads = jax.jit(jax.grad(lambda *t: jnp.sum(fn(*t) * w),
                             argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    for name, g, g_want in zip("x dt A B C".split(), grads, want_grads):
        err = float(jnp.max(jnp.abs(g - g_want))) / float(jnp.max(jnp.abs(g_want)))
        assert err < 5e-5, (name, err)
    # the recurrence itself, given the runs, is its per-document self
    y, _ = ssd_ops.ssd_recurrent(x, dt, a, b, c, runs=runs)
    assert float(jnp.max(jnp.abs(y - want))) == 0.0


@pytest.mark.parametrize("form", ["jnp", "kernels"])
def test_a_leak_would_show(form):
    """The same operands without the ids: far from the per-document result
    (heads that remember a thousand positions carry a document into the
    next), so the agreement above is the cut's doing."""
    (x, dt, a, b, c), _ = _operands(128, 4)
    want, _ = _alone(128, 4)
    got = jax.jit(lambda *t: ssd_ops.ssd(*t, 128, interpret=form == "kernels"))(
        x, dt, a, b, c)
    assert float(jnp.max(jnp.abs(got - want))) > 0.1 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("form", ["jnp", "kernels"])
@pytest.mark.parametrize("q", [128, 256])
def test_a_row_that_is_one_document_gives_the_bits_it_gave_without_ids(q, form):
    (x, dt, a, b, c), w = _operands(q, 4)
    one = ssd_ops.document_runs(jnp.full((2, 3 * q), 9, jnp.int32))
    assert int(jnp.max(one)) == 0
    kw = dict(interpret=form == "kernels")
    plain = lambda *t: ssd_ops.ssd(*t, q, **kw)
    under = lambda *t: ssd_ops.ssd(*t, q, runs=one, **kw)
    assert bool(jnp.all(jax.jit(plain)(x, dt, a, b, c) == jax.jit(under)(x, dt, a, b, c)))
    grad = lambda f: jax.jit(jax.grad(lambda *t: jnp.sum(f(*t) * w),
                                      argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    for g, g_plain in zip(grad(under), grad(plain)):
        assert bool(jnp.all(g == g_plain))


def test_a_length_that_is_no_whole_chunks_is_padded_inside_its_last_document():
    (x, dt, a, b, c), _ = _operands(128, 4)
    docs, lens = _documents(128, 384)
    n = 300  # the pad continues the last document and writes nothing
    cut = lambda t: t[:, :n]
    got = ssd_ops.ssd(cut(x), cut(dt), a, cut(b), cut(c), 128,
                      runs=ssd_ops.document_runs(cut(docs)))
    want, _ = _alone(128, 4)
    # (row 0's last document is cut short, which changes nothing before it)
    assert float(jnp.max(jnp.abs(got - want[:, :n]))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_program_heads_splits_a_wide_group_and_refuses_other_shapes():
    # Nemotron's: sixteen heads a group at chunks of 128, one program
    assert ssd_ops.program_heads(128, 16, 64, 128) == 16
    # Granite's: sixty-four heads in one group -- four programs of sixteen at
    # chunks of 128, eight of eight at 256
    assert ssd_ops.program_heads(128, 64, 64, 128) == 16
    assert ssd_ops.program_heads(256, 64, 64, 128) == 8
    assert ssd_ops.program_heads(256, 6, 64, 128) == 6   # three tiles: one program
    assert ssd_ops.program_heads(128, 18, 128, 128) == 6  # a head a tile, 18 = 3 x 6
    for q, per, p, n in ((64, 16, 64, 128), (512, 16, 64, 128), (128, 16, 48, 128),
                         (128, 3, 64, 128), (128, 16, 64, 64)):
        assert ssd_ops.program_heads(q, per, p, n) == 0, (q, per, p, n)


# -- the taps ------------------------------------------------------------------

def test_the_taps_under_document_runs_are_the_taps_on_each_document_alone():
    docs, lens = _documents(16, 48)
    s = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 24))
    w = jax.random.normal(jax.random.PRNGKey(2), (24, 4))
    runs = ssd_ops.document_runs(docs)
    got = sl.causal_taps(s, w, runs=runs)
    want = _each_document_alone(lambda t: sl.causal_taps(t, w), lens, s)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    leak = sl.causal_taps(s, w)
    assert float(jnp.max(jnp.abs(leak - want))) > 0.1
    # the gradient reaches a position only from its own document
    g = jax.grad(lambda t: jnp.sum(sl.causal_taps(t, w, runs=runs)[0, 16:]))(s)
    assert float(jnp.max(jnp.abs(g[0, :16]))) == 0.0 < float(jnp.max(jnp.abs(g[0, 16:])))
    one = ssd_ops.document_runs(jnp.zeros((2, 48), jnp.int32))
    assert bool(jnp.all(sl.causal_taps(s, w, runs=one) == sl.causal_taps(s, w)))


# -- the attention core ----------------------------------------------------------

def _qkv(n: int, d: int = 64, heads: int = 4, kv: int = 2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    q = (0.3 * jax.random.normal(ks[0], (2, heads, n, d))).astype(dtype)
    k = jax.random.normal(ks[1], (2, kv, n, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, kv, n, d)).astype(dtype)
    return q, k, v


def test_the_exact_core_under_document_ids_is_the_core_on_each_document_alone():
    docs, lens = _documents(16, 48)
    q, k, v = _qkv(48, d=16)
    ctx = ApplyCtx(train=True)
    heads_last = lambda t: jnp.swapaxes(t, 1, 2)  # [rows, n, heads, d]: cut by position

    def alone(q, k, v):
        core = lambda q, k, v: heads_last(sl.attention_core(
            heads_last(q), heads_last(k), heads_last(v), ctx))
        return heads_last(_each_document_alone(
            core, lens, heads_last(q), heads_last(k), heads_last(v)))

    got = sl.attention_core(q, k, v, ctx, docs=docs)
    want = jax.jit(alone)(q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert float(jnp.max(jnp.abs(sl.attention_core(q, k, v, ctx) - want))) > 0.05
    g = jax.jit(jax.grad(
        lambda *t: jnp.sum(jnp.square(sl.attention_core(*t, ctx, docs=docs))),
        argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda *t: jnp.sum(jnp.square(alone(*t))),
                              argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * float(jnp.max(jnp.abs(b)))
    one = jnp.full((2, 48), 5, jnp.int32)
    assert bool(jnp.all(sl.attention_core(q, k, v, ctx, docs=one)
                        == sl.attention_core(q, k, v, ctx)))


def test_the_kernel_core_under_segment_ids_equals_the_exact_path(monkeypatch):
    """The splash kernel under the Pallas interpreter, handed the document
    ids as its segment ids (what `attention_core` hands it on the TPU),
    against the exact path's bias: forward and the three gradients, at one
    tile of 1,024 positions with boundaries inside it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    n = max(sl.ATTN_BLOCKS)
    lens = [[300, 1, 211, n - 512], [n - 7, 7]]
    docs = jnp.asarray(np.concatenate(
        [np.repeat(np.arange(len(l)), l)[None] for l in lens]), jnp.int32)
    q, k, v = _qkv(n, dtype=jnp.bfloat16)
    kernel = sl._splash(q.shape[1], n, None, True)
    under = lambda q, k, v: jax.vmap(kernel)(q, k, v, sk.SegmentIds(q=docs, kv=docs))
    with precision.policy("bfloat16"):
        exact = lambda q, k, v: sl.attention_core(q, k, v, ApplyCtx(train=True), docs=docs)
        got, want = under(q, k, v), exact(q, k, v)
        f32 = lambda t: t.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(f32(got) - f32(want)))) \
            < 0.03 * float(jnp.max(jnp.abs(f32(want))))
        w = jax.random.normal(jax.random.PRNGKey(3), got.shape)
        grad = lambda f: jax.grad(lambda *t: jnp.sum(f32(f(*t)) * w), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(grad(under), grad(exact)):
            scale = float(jnp.max(jnp.abs(f32(b))))
            assert float(jnp.max(jnp.abs(f32(a) - f32(b)))) < 0.03 * scale
