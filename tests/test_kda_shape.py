"""The stage between Kimi Delta Attention's projections and its delta rule
(`ops.kda_shape`): the `jnp` form against the definition written out in
float64; the kernel pair of `ops.pallas_kda_shape` under the Pallas
interpreter against the `jnp` form and its autodiff -- q, k, v, g forward,
all four input gradients, the taps', `dt_bias`' and `A_log`'s gradients, in
both policies, over several tiles, heads and rows, differentiated (the
forward rule, whose v is plain `jnp`) and not (the forward kernel's four
results); positions 0-2 (zeros
before the row) and both sides of every tile and sub-tile edge, forward and
backward; and which form runs, decided by backend, shape and dtype alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision
from sparknet_tpu.model.seq_layers import causal_taps
from sparknet_tpu.ops import kda_shape as ks
from sparknet_tpu.ops import pallas_kda_shape as pk

TAPS, BOUND = 4, -5.0
NAMES = "q k v a taps_q taps_k taps_v dt_bias A_log".split()


def _inputs(seed, shape, dtype=jnp.float32, taps=TAPS):
    """(q, k, v, a [rows, heads, n, d], b [rows, heads, n]) as projections
    leave them and (the three tap sets, dt_bias, A_log)."""
    rows, heads, n, d = shape
    key = jax.random.split(jax.random.PRNGKey(seed), 10)
    q, k, v, a = (jax.random.normal(key[i], shape).astype(dtype) for i in range(4))
    b = jax.random.normal(key[4], shape[:3]).astype(dtype)
    convs = [0.5 * jax.random.normal(key[5 + i], (heads, d, taps)) for i in range(3)]
    return (q, k, v, a, b), (convs, jax.random.normal(key[8], (heads, d)),
                             0.3 * jax.random.normal(key[9], (heads,)))


def _form(interpret: bool):
    return lambda x, p: ks.shape(*x, *p, BOUND, conv=causal_taps, interpret=interpret)


def _pulled(form, x, p, cot):
    """(the stage's five results, the gradients of NAMES) under `cot`."""
    def stage(x4, p):
        *out, beta = form((*x4, x[4]), p)
        return tuple(out), beta

    out, pull, beta = jax.vjp(stage, x[:4], p, has_aux=True)
    d_x, (d_convs, d_bias, d_alog) = pull(cot)
    return (*out, beta), (*d_x, *d_convs, d_bias, d_alog)


@functools.partial(jax.jit, static_argnums=0)
def _both(interpret, x, p, cot):
    return _pulled(_form(interpret), x, p, cot)


@functools.partial(jax.jit, static_argnums=0)
def _plain(interpret, x, p):
    """The stage called and not differentiated: on the kernel path all four
    results are the forward kernel's (under `jax.vjp`, `_both`, the forward
    rule returns v from plain `jnp`)."""
    return _form(interpret)(x, p)


def _cot(seed, out):
    return tuple(jax.random.normal(jax.random.PRNGKey(seed + i), o.shape).astype(o.dtype)
                 for i, o in enumerate(out))


f32 = lambda t: np.asarray(t, np.float32)


def test_the_jnp_form_is_the_definition_written_out():
    """Four taps over positions with zeros before the row, SiLU, the L2
    norms with 1e-6 under the root and q's d^-1/2, the bounded decay, the
    writing strength: in float64 numpy, a position at a time."""
    (q, k, v, a, b), (convs, bias, alog) = _inputs(0, (1, 2, 12, 8))
    got = ks.shape_jnp(q, k, v, a, b, convs, bias, alog, BOUND, causal_taps)
    silu = lambda t: t / (1.0 + np.exp(-t))
    unit = lambda t: t / np.sqrt(np.sum(t * t, -1, keepdims=True) + 1e-6)
    want = []
    for x, w in zip((q, k, v), convs):
        x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
        c = np.zeros_like(x)
        for t in range(x.shape[2]):
            for j in range(TAPS):
                if t - (TAPS - 1) + j >= 0:
                    c[:, :, t] += w[None, :, :, j] * x[:, :, t - (TAPS - 1) + j]
        want.append(silu(c))
    want = [unit(want[0]) * 8 ** -0.5, unit(want[1]), want[2],
            BOUND / (1.0 + np.exp(-np.exp(np.asarray(alog, np.float64))[:, None, None]
                                  * (np.asarray(a, np.float64) + np.asarray(bias)[:, None, :]))),
            1.0 / (1.0 + np.exp(-np.asarray(b, np.float64)))]
    for name, x, y in zip("q k v g beta".split(), got, want):
        assert x.dtype == jnp.float32 and np.allclose(f32(x), y, rtol=2e-5, atol=2e-6), name


# (rows, heads, positions, head width): more than one tile a row, more than
# one head, more than one row, a head of two lane rows
SHAPES = {"two_tiles_two_heads": (1, 2, 2 * pk.TILE, 128),
          "two_rows_one_tile": (2, 1, pk.TILE, 128),
          "wide_head": (1, 1, pk.TILE, 256)}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_kernel_pair_equals_the_jnp_form_and_its_autodiff(mode, shape):
    with precision.policy(mode):
        x, p = _inputs(3, SHAPES[shape], precision.compute_dtype())
        cot = _cot(7, jax.eval_shape(_form(False), x, p)[:4])
        want, g_want = _both(False, x, p, cot)
        got, g_got = _both(True, x, p, cot)
        assert "pallas_call" in str(jax.make_jaxpr(_form(True))(x, p))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        # the norms' sums over the lanes add in another order; bfloat16:
        # one rounding apart at the most
        tol = 2e-6 if mode == "float32" else 1e-2
        assert np.max(np.abs(f32(a) - f32(b))) <= tol * np.max(np.abs(f32(b))), name
    assert got[3].dtype == jnp.float32 and got[0].dtype == jnp.dtype(mode)
    for name, a, b in zip(NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(f32(a))), name
        err = np.linalg.norm(f32(a) - f32(b)) / (np.linalg.norm(f32(b)) + 1e-30)
        # the parameters' gradients are float32 sums over positions in both
        # policies; the inputs' are rounded to the inputs' dtype
        assert err < (1e-3 if mode == "bfloat16" and name in "qkva" else 5e-6), (name, err)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_the_call_alone_and_its_forward_rule_agree(mode, shape):
    """Not differentiated, q, k, v, g are the forward kernel's; the forward
    rule (`_shape_kernels_fwd`) returns the kernel's q, k, g and v from
    plain `jnp`: both are the `jnp` form's, and v is the same either way."""
    with precision.policy(mode):
        x, p = _inputs(4, SHAPES[shape], precision.compute_dtype())
        want = _plain(False, x, p)
        got = _plain(True, x, p)
        ruled, _ = _both(True, x, p, _cot(1, want[:4]))
        packed = pk.pack(*p)
        by_rule = pk._v_plain(x[2], packed, TAPS)
    tol = 2e-6 if mode == "float32" else 1e-2
    for name, a, b, c in zip("q k v g beta".split(), got, want, ruled):
        assert a.shape == b.shape and a.dtype == b.dtype == c.dtype, name
        assert np.max(np.abs(f32(a) - f32(b))) <= tol * np.max(np.abs(f32(b))), name
        assert np.max(np.abs(f32(c) - f32(a))) <= tol * np.max(np.abs(f32(b))), name
    # (the rule's v by itself, outside the jitted stage: fused differently)
    assert by_rule.dtype == got[2].dtype and np.max(np.abs(
        f32(by_rule) - f32(ruled[2]))) <= tol * np.max(np.abs(f32(want[2])))


# -- edges: a row of three tiles, four sub-tiles each ---------------------------

EDGE_SHAPE = (1, 2, 3 * pk.TILE, 128)
EDGES = [0, pk.SUB, pk.TILE - pk.SUB, pk.TILE, 2 * pk.TILE, EDGE_SHAPE[2]]


@functools.lru_cache(maxsize=None)
def _edge_case():
    x, p = _inputs(5, EDGE_SHAPE)
    return x, p, _cot(11, jax.eval_shape(_form(False), x, p)[:4])


def _window(t, lo, hi):
    """t with everything outside positions [lo, hi) set to zero."""
    at = jnp.arange(t.shape[2])
    keep = ((at >= lo) & (at < hi)).reshape((1, 1, -1) + (1,) * (t.ndim - 3))
    return jnp.where(keep, t, 0.0)


@pytest.mark.parametrize("edge", EDGES)
def test_forward_reads_the_positions_before_an_edge_and_zeros_before_the_row(edge):
    """Inputs that live on the three positions before an edge alone: what
    the taps carry over it (the halo block of the tile before, the loop
    body before; nothing before position 0) is the `jnp` form's, position
    by position."""
    x, p, cot = _edge_case()
    n = EDGE_SHAPE[2]
    lo = max(edge - (TAPS - 1), 0)
    x = tuple(_window(t, lo, min(lo + TAPS - 1, n)) for t in x[:4]) + (x[4],)
    want, _ = _both(False, x, p, cot)
    got, _ = _both(True, x, p, cot)
    near = slice(max(edge - 4, 0), min(edge + 4, n))
    for name, a, b in zip("qkv", got, want):
        assert np.any(f32(b)[:, :, near] != 0.0), name
        assert np.allclose(f32(a), f32(b), rtol=2e-6, atol=1e-7), (name, edge)
        beyond = min(lo + 2 * (TAPS - 1), n)
        assert not np.any(f32(a)[:, :, beyond:]) and not np.any(f32(a)[:, :, :lo]), name


@pytest.mark.parametrize("edge", EDGES)
def test_the_call_alone_reads_the_same_positions_before_an_edge(edge):
    """The same inputs through the call that is not differentiated: v too
    is the forward kernel's there (its halo block, its loop bodies)."""
    x, p, _ = _edge_case()
    n = EDGE_SHAPE[2]
    lo = max(edge - (TAPS - 1), 0)
    x = tuple(_window(t, lo, min(lo + TAPS - 1, n)) for t in x[:4]) + (x[4],)
    for name, a, b in zip("qkv", _plain(True, x, p), _plain(False, x, p)):
        assert np.any(f32(b)[:, :, max(edge - 4, 0):min(edge + 4, n)] != 0.0), name
        assert np.allclose(f32(a), f32(b), rtol=2e-6, atol=1e-7), (name, edge)
        beyond = min(lo + 2 * (TAPS - 1), n)
        assert not np.any(f32(a)[:, :, beyond:]) and not np.any(f32(a)[:, :, :lo]), name


@pytest.mark.parametrize("edge", EDGES)
def test_backward_reads_the_positions_after_an_edge_and_nothing_past_the_row(edge):
    """Cotangents that live on the three positions after an edge alone: the
    convolution's transpose carries them back over it (through the SiLU and
    the norm of the NEXT tile's positions, made again from its halo), and a
    row's last positions take nothing from past its end."""
    x, p, cot = _edge_case()
    n = EDGE_SHAPE[2]
    lo = min(edge, n - (TAPS - 1))
    cot = tuple(_window(t, lo, lo + TAPS - 1) for t in cot)
    _, g_want = _both(False, x, p, cot)
    _, g_got = _both(True, x, p, cot)
    for name, a, b in zip(NAMES, g_got, g_want):
        scale = np.max(np.abs(f32(b)))
        assert scale > 0.0, name
        # (the parameters' gradients are sums over positions, in another order)
        tol = 2e-6 if name in "qkva" else 2e-5
        assert np.max(np.abs(f32(a) - f32(b))) <= tol * scale, (name, edge)
    for name, a in zip("qkv", g_got):
        a = f32(a)
        assert np.any(a[:, :, max(lo - (TAPS - 1), 0):lo + 1]), name
        assert not np.any(a[:, :, :max(lo - (TAPS - 1), 0)]) \
            and not np.any(a[:, :, lo + TAPS - 1:]), name


def test_which_form_runs_is_decided_by_backend_shape_and_dtype_alone():
    """The kernels where a Pallas call may run (here: the interpreter), the
    head fills the lanes, the positions are whole tiles and the projections
    are in the policy's dtype; the `jnp` form under its checkpoint
    elsewhere."""
    def calls(interpret, shape, dtype=jnp.float32, taps=TAPS):
        x, p = _inputs(1, shape, dtype, taps)
        return str(jax.make_jaxpr(_form(interpret))(x, p)).count("pallas_call")
    whole = (1, 1, pk.TILE, 128)
    assert calls(True, whole) == 1
    assert calls(False, whole) == 0                       # the CPU: no Pallas call may run
    assert calls(True, (1, 1, pk.TILE, 64)) == 0          # a head of 64
    assert calls(True, (1, 1, pk.TILE - 64, 128)) == 0    # no whole tile
    assert calls(True, (1, 1, pk.TILE + pk.SUB, 128)) == 0
    assert calls(True, whole, jnp.bfloat16) == 0          # not the policy's dtype
    assert calls(True, whole, taps=pk.NEAR + 2) == 0      # more taps than a halo holds
    assert calls(True, whole, taps=3) == 1
    with precision.policy("bfloat16"):
        assert calls(True, whole, jnp.bfloat16) == 1
    x, p = _inputs(1, whole)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, p: sum(jnp.sum(o) for o in _form(True)(x, p)), argnums=(0, 1)))(x, p))
    # the backward is the kernel's own, not autodiff, and keeps no checkpoint
    assert "kda_shape_fwd" in text and "kda_shape_bwd" in text
    assert "prevent_cse" not in text
    x64, p64 = _inputs(1, (1, 1, pk.TILE, 64))
    assert "prevent_cse" in str(jax.make_jaxpr(_form(True))(x64, p64))


def test_three_taps_run_the_same_kernels():
    """The taps are the parameter's own count (the halo holds up to nine)."""
    x, p = _inputs(9, (1, 1, pk.TILE, 128), taps=3)
    cot = _cot(2, jax.eval_shape(_form(False), x, p)[:4])
    want, g_want = _both(False, x, p, cot)
    got, g_got = _both(True, x, p, cot)
    for a, b in zip(got + g_got, want + g_want):
        assert np.allclose(f32(a), f32(b), rtol=1e-4, atol=1e-5 * np.max(np.abs(f32(b))))


def test_the_calls_state_their_vmem():
    """Both calls at the cell's head width stay inside what Mosaic allows
    unasked in both dtypes; a head eight times as wide asks for more."""
    for size in (2, 4):
        assert pk._vmem(128, [(11, size), (1, 4)], [(9, size)], 32) == pk._DEFAULT_SCOPED_VMEM
    assert pk._vmem(1024, [(11, 4), (1, 4)], [(9, 4)], 32) > pk._DEFAULT_SCOPED_VMEM
    assert pk.param_rows(4) == 16 and pk.param_rows(3) == 16 and pk.param_rows(9) == 32
