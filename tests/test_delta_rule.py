"""The gated delta rule with a per-channel decay (`ops.delta_rule`): the
chunked form that runs against the recurrence a position at a time, forward
and every gradient, at chunks of 16 / 32 / 64, lengths that are and are not a
multiple of the chunk, decays all at the bound (-5), spread over it, and all
near 0; the unit-lower-triangular inverse by hand; and what the sub-chunks
are for: a chunk-wide factoring of the decay overflows where this one does
not. And the kernel pair of `ops.pallas_delta_rule` under the Pallas
interpreter: its six operands and five gradients against `_chunk_operands`
and `jax.vjp` of it, and `gated_delta_rule` on the kernel path (the chunk
stage's pair and the walk's, `ops.pallas_delta_scan`) against the recurrence;
every case above keeps running on the `jnp` form (heads of 16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision
from sparknet_tpu.ops import delta_rule as dr
from sparknet_tpu.ops import pallas_delta_rule as pk


def _inputs(seed, n, gates, lead=(2, 3), dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], lead + (n, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], lead + (n, dk)))
    v = jax.random.normal(ks[2], lead + (n, dv))
    u = jax.random.uniform(ks[3], lead + (n, dk))
    g = {"spread": dr.MIN_LOG_DECAY * u,
         "at_the_bound": jnp.full(lead + (n, dk), dr.MIN_LOG_DECAY),
         "near_zero": -1e-3 * u}[gates]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], lead + (n,)))
    return q, k, v, g, beta


def _recurrence_by_hand(q, k, v, g, beta):
    """The definition in float64 numpy, a position at a time."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    s = np.zeros(q.shape[:-2] + (q.shape[-1], v.shape[-1]))
    o = np.zeros(v.shape)
    for t in range(q.shape[-2]):
        s = np.exp(g[..., t, :])[..., None] * s
        u = beta[..., t, None] * (v[..., t, :] - np.einsum(
            "...kv,...k->...v", s, k[..., t, :]))
        s = s + k[..., t, :, None] * u[..., None, :]
        o[..., t, :] = np.einsum("...kv,...k->...v", s, q[..., t, :])
    return o, s


_LOSS = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
_ALL = (0, 1, 2, 3, 4)
# jitted once a shape: the three kinds of gates share every compile
_REC_AND_STATE = jax.jit(dr.delta_rule_recurrent)
_REC = lambda *a: _REC_AND_STATE(*a)[0]
_REC_GRAD = jax.jit(jax.grad(_LOSS(lambda *a: dr.delta_rule_recurrent(*a)[0]), argnums=_ALL))
_CHUNKED = jax.jit(dr.gated_delta_rule, static_argnames=("chunk",))
_CHUNKED_GRAD = jax.jit(
    lambda *a, chunk: jax.grad(_LOSS(lambda *b: dr.gated_delta_rule(*b, chunk=chunk)),
                               argnums=_ALL)(*a), static_argnames=("chunk",))


@pytest.mark.parametrize("gates", ["spread", "at_the_bound", "near_zero"])
@pytest.mark.parametrize("n", [128, 50, 7])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_form_equals_the_recurrence_forward_and_gradient(chunk, n, gates):
    x = _inputs(n + chunk, n, gates)
    with precision.policy("float32"):
        want, got = _REC(*x), _CHUNKED(*x, chunk=chunk)
        g_want, g_got = _REC_GRAD(*x), _CHUNKED_GRAD(*x, chunk=chunk)
    assert got.shape == want.shape == x[2].shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        # at the bound the decay's own gradient is a difference of near-equal
        # terms a thousandth of the others' size
        assert err < (2e-3 if (name, gates) == ("g", "at_the_bound") else 5e-5), (name, err)


@pytest.mark.parametrize("gates", ["spread", "at_the_bound", "near_zero"])
def test_both_forms_equal_the_definition_in_float64(gates):
    x = _inputs(3, 96, gates, lead=(2,))
    want, last = _recurrence_by_hand(*x)
    with precision.policy("float32"):
        rec, s = _REC_AND_STATE(*x)
        got = _CHUNKED(*x)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(np.asarray(rec) - want)) < 1e-5 * scale
    assert np.max(np.abs(np.asarray(s) - last)) < 1e-5 * np.max(np.abs(last))
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5 * scale


def test_the_rule_is_causal_and_padding_neither_writes_nor_decays():
    x = _inputs(5, 80, "spread")
    later = tuple(t.at[..., 50:, :].add(0.3) if t.ndim == 4 else t for t in x)
    later = later[:3] + (x[3], x[4])
    with precision.policy("float32"):
        a, b = _CHUNKED(*x), _CHUNKED(*later)
        # 80 positions = a chunk of 64 and 16 of a second, padded to 64
        short = _CHUNKED(*(t[..., :80, :] if t.ndim == 4 else t[..., :80]
                           for t in _inputs(5, 128, "spread")))
        whole = _CHUNKED(*_inputs(5, 128, "spread"))
    assert np.array_equal(a[..., :50, :], b[..., :50, :])
    assert not np.allclose(a[..., 50, :], b[..., 50, :])
    assert np.allclose(short, whole[..., :80, :], atol=1e-6)


@pytest.mark.parametrize("c", [16, 32, 64])
def test_unit_lower_inverse_by_hand(c):
    """Alike keys make every entry under the diagonal close to 1: the powers
    of N then grow past float32 (binom(63, 31) ~ 1e18) and the blockwise
    substitution does not care."""
    rng = np.random.default_rng(c)
    inverse = jax.jit(dr._unit_lower_inverse)
    for entries in (rng.uniform(-1, 1, (3, c, c)), np.full((3, c, c), 0.97)):
        n = np.tril(entries, -1).astype(np.float32)
        got = np.asarray(inverse(jnp.asarray(n)))
        want = np.linalg.inv(np.eye(c) + n.astype(np.float64))
        assert np.max(np.abs(got - want)) < 1e-4 * max(1.0, np.max(np.abs(want)))
        assert np.allclose(np.triu(got, 1), 0) and np.allclose(np.diagonal(got, axis1=-2, axis2=-1), 1)


def test_sub_chunks_are_what_keeps_the_factored_decay_finite():
    """exp(G_t - G_j) factored around one reference a chunk of 64 overflows
    float32 with every gate at the bound (exp(5 * 32) = inf); factored a
    sub-chunk of 16 at a time it stays within exp(+-40)."""
    assert dr.SUB * -dr.MIN_LOG_DECAY / 2 < 88 < dr.CHUNK * -dr.MIN_LOG_DECAY / 2
    q, k, v, g, beta = _inputs(9, 64, "at_the_bound", lead=(1,))
    g_sum = jnp.cumsum(g, axis=-2)
    whole = jnp.exp(g_sum[..., 31:32, :] - g_sum)  # one reference, mid-chunk
    assert not bool(jnp.all(jnp.isfinite(whole)))
    with precision.policy("float32"):
        a, b = dr._pair_terms(q, k, g_sum)
    assert bool(jnp.all(jnp.isfinite(a))) and bool(jnp.all(jnp.isfinite(b)))
    # by hand, across two sub-chunks and inside one
    for t, j in ((40, 37), (40, 20), (63, 48), (17, 2)):
        want = float(jnp.sum(k[0, t] * k[0, j] * jnp.exp(g_sum[0, t] - g_sum[0, j])))
        assert float(a[0, t, j]) == pytest.approx(want, rel=1e-4, abs=1e-12)


def test_bfloat16_policy_runs_the_large_products_in_bfloat16():
    x = _inputs(11, 128, "spread")
    with precision.policy("float32"):
        want = _CHUNKED(*x)
    with precision.policy("bfloat16"):
        # (a fresh function a trace: the policy is no part of jax's cache key)
        rule = jax.jit(lambda *a: dr.gated_delta_rule(*a))
        got = rule(*x)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(dr.gated_delta_rule(*a))),
                                 argnums=(0, 1, 2, 3, 4)))(*x)
        text = rule.lower(*x).as_text()
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(jnp.max(jnp.abs(want)))
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in grads)
    assert "bf16" in text and "while" in text


# -- the kernel pair (`ops.pallas_delta_rule`), under the Pallas interpreter --

def _kernel_inputs(case, many=1, n=256, d=128):
    """Rows of whole chunks at the kernel's widths: [many, n, d]."""
    q, k, v, g, beta = _inputs(17, n, {"at_the_bound": "at_the_bound"}.get(case, "spread"),
                               lead=(many,), dk=d, dv=d)
    if case == "zero_gates":
        g = jnp.zeros_like(g)
    if case == "beta_near_0":
        beta = jnp.full_like(beta, 1e-4)
    if case == "beta_near_1":
        beta = jnp.full_like(beta, 1.0 - 1e-4)
    if case == "alike_keys":  # what the no-powers solve exists for
        k = k[:, :1, :] + 1e-3 * k
        k, g = k / jnp.linalg.norm(k, axis=-1, keepdims=True), -1e-3 * jnp.ones_like(g)
        beta = jnp.full_like(beta, 0.97)
    return q, k, v, g, beta


def _oracle(q, k, v, g, beta):
    """`_chunk_operands` on the rows cut into chunks, chunks first."""
    cut = lambda x: x.reshape((x.shape[0], x.shape[1] // dr.CHUNK, dr.CHUNK) + x.shape[2:])
    return tuple(jnp.moveaxis(x, 1, 0) for x in dr._chunk_operands(*map(cut, (q, k, v, g, beta))))


_KERNEL_CASES = ["spread", "at_the_bound", "zero_gates", "beta_near_0",
                 "beta_near_1", "alike_keys"]
_NAMES = "w_k w_v k_end d_end q_dec b_low".split()


@functools.cache
def _pair_and_oracle(mode):
    """(x, cotangents) -> the oracle's and the kernel pair's operands and
    their pullbacks of the cotangents: jitted once a mode, so that the six
    cases of a mode share one compile (traced under the mode's policy)."""
    dt = jnp.dtype(mode)

    @jax.jit
    def both(x, cot):
        want, pull_want = jax.vjp(_oracle, *x)
        got, pull_got = jax.vjp(lambda *a: pk.chunk_operands(*a, dt, True), *x)
        return want, got, pull_want(cot), pull_got(cot)

    return both


@pytest.mark.parametrize("case", _KERNEL_CASES)
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_kernel_pair_equals_chunk_operands_and_their_autodiff(mode, case):
    dt = jnp.dtype(mode)
    q, k, v, g, beta = _kernel_inputs(case)
    x = (q.astype(dt), k.astype(dt), v.astype(dt), g, beta)
    with precision.policy(mode):
        cot = tuple(jax.random.normal(jax.random.PRNGKey(i), w.shape).astype(w.dtype)
                    for i, w in enumerate(jax.eval_shape(_oracle, *x)))
        want, got, g_want, g_got = _pair_and_oracle(mode)(x, cot)
    f32 = lambda t: np.asarray(t, np.float32)
    for name, a, b in zip(_NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        # the running sums (to 320) add in another order: exp carries 3e-5;
        # bfloat16: one rounding apart at the most
        tol = 5e-5 if mode == "float32" else 1e-2
        assert np.max(np.abs(f32(a) - f32(b))) <= tol * max(np.max(np.abs(f32(b))), 1e-30), name
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(f32(a))), name
        err = np.linalg.norm(f32(a) - f32(b)) / (np.linalg.norm(f32(b)) + 1e-30)
        # at the bound the decay's gradient is a difference of near-equal terms
        tight = 2e-3 if (name, case) == ("g", "at_the_bound") else 5e-5
        assert err < (tight if mode == "float32" else 25 * 2e-3), (name, err)


_KERNEL_RULE = jax.jit(lambda *a: dr.gated_delta_rule(*a, interpret=True))
_KERNEL_RULE_GRAD = jax.jit(jax.grad(
    _LOSS(lambda *a: dr.gated_delta_rule(*a, interpret=True)), argnums=_ALL))


@pytest.mark.parametrize("gates", ["spread", "at_the_bound", "near_zero"])
@pytest.mark.parametrize("n", [256, 200, 50])
def test_kernel_path_equals_the_recurrence_forward_and_gradient(n, gates):
    """Heads of 128 under the interpreter take the kernels (a length that is
    no multiple of their tile of 128 is padded as the `jnp` form pads)."""
    x = _inputs(n, n, gates, lead=(1, 2), dk=128, dv=128)
    with precision.policy("float32"):
        want, got = _REC(*x), _KERNEL_RULE(*x)
        g_want, g_got = _REC_GRAD(*x), _KERNEL_RULE_GRAD(*x)
    assert got.shape == want.shape == x[2].shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        assert err < (2e-3 if (name, gates) == ("g", "at_the_bound") else 5e-5), (name, err)


def test_which_form_runs_is_decided_by_backend_and_shape_alone():
    """The kernels where a Pallas call may run (here: the interpreter) and
    the heads fill the lanes; the `jnp` form for narrow heads, for a short
    row's smaller chunk, and on this backend without the interpreter
    (`tests/test_delta_scan_kernel.py` holds the same for the walk's pair)."""
    calls = lambda interpret, **kw: str(jax.make_jaxpr(
        lambda *a: dr.gated_delta_rule(*a, interpret=interpret))(
            *_inputs(1, kw.pop("n", 128), "spread", lead=(1,), **kw))).count("pallas_call")
    assert calls(True, dk=128, dv=128) == 2        # the chunk stage's and the walk's
    assert calls(False, dk=128, dv=128) == 0       # the CPU: no Pallas call may run
    assert calls(True, dk=16, dv=8) == 0           # narrow heads
    assert calls(True, dk=128, dv=64) == 0
    assert calls(True, n=20, dk=128, dv=128) == 0  # a chunk of 32 holds the row
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(dr.gated_delta_rule(*a, interpret=True)),
                                   argnums=_ALL))(*_inputs(1, 128, "spread", lead=(1,), dk=128, dv=128))
    text = str(grad)
    # the backward of the chunk stage is the kernel's own, not autodiff
    assert "delta_chunk_fwd" in text and "delta_chunk_bwd" in text
