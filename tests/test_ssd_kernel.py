"""`ops.pallas_ssd`'s kernel pair under the Pallas interpreter (CPU): the
chunked Mamba-2 scan with the state's walk inside the kernel, held to the
`jnp` form of `ops.ssd` and its autodiff (forward and the gradients of x, dt,
A, B and C, under both precision policies), to the recurrence a position at a
time, and to what the scan is: causal, its state carried from chunk to chunk.
Which form runs is `ops.ssd`'s to decide, from backend and shape alone."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import ssd_without_its_state
from sparknet_tpu import precision
from sparknet_tpu.ops import pallas_ssd as pk
from sparknet_tpu.ops import ssd as ssd_ops

_ALL = (0, 1, 2, 3, 4)
_NAMES = "x dt a b c".split()


def _operands(seed, n, rows=1, heads=4, hd=64, groups=2, state=128, dtype=jnp.float32):
    """The scan's operands at the kernels' widths: heads of 64 two to a lane
    tile (or of 128), a state of 128."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (rows, n, heads, hd)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (rows, n, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (rows, n, groups, state)).astype(dtype),
            jax.random.normal(ks[4], (rows, n, groups, state)).astype(dtype))


def _loss(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


_KERNELS = lambda *a: ssd_ops.ssd(*a, interpret=True)
_JNP = lambda *a: ssd_ops.ssd(*a)
_REC = lambda *a: ssd_ops.ssd_recurrent(*a)[0]

#: one chunk of one group; several chunks of two groups; a length that needs
#: padding; a row of two; heads of a whole lane row each (one a group)
_CASES = {"one_chunk": dict(n=128, heads=2, groups=1),
          "three_chunks": dict(n=384, heads=4, groups=2),
          "padded": dict(n=200, heads=4, groups=2),
          "two_rows": dict(n=256, rows=2, heads=2, groups=1),
          "heads_of_128": dict(n=256, heads=2, groups=2, hd=128)}


@functools.cache
def _both(mode):
    """operands -> (jnp form, kernel path, their gradients): traced under
    the mode's policy, one compile a shape."""
    grads = lambda fn: jax.grad(_loss(fn), argnums=_ALL)
    return jax.jit(lambda *a: (_JNP(*a), _KERNELS(*a), grads(_JNP)(*a),
                               grads(_KERNELS)(*a)))


def _pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("case", list(_CASES))
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_kernel_pair_equals_the_jnp_form_and_its_autodiff(mode, case):
    args = _operands(11, dtype=jnp.dtype(mode), **_CASES[case])
    with precision.policy(mode):
        assert _pallas_calls(_KERNELS, *args) == 1 and _pallas_calls(_JNP, *args) == 0
        want, got, g_want, g_got = _both(mode)(*args)
    f32 = lambda t: np.asarray(t, np.float32)
    assert got.shape == want.shape == args[0].shape and got.dtype == want.dtype == jnp.float32
    # float32: the products' sums in another order; bfloat16: the same casts
    # in both forms, one rounding apart at the most
    tol = 1e-5 if mode == "float32" else 1e-2
    assert np.max(np.abs(f32(got) - f32(want))) <= tol * np.max(np.abs(f32(want)))
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(f32(a))), name
        err = np.linalg.norm(f32(a) - f32(b)) / (np.linalg.norm(f32(b)) + 1e-30)
        assert err < (5e-5 if mode == "float32" else 25 * 2e-3), (name, err)


@pytest.mark.parametrize("n", [128, 200, 384])
def test_kernel_path_equals_the_recurrence_forward_and_gradient(n):
    """One chunk, a padded length, three chunks, against the definition."""
    args = _operands(n, n)
    with precision.policy("float32"):
        want, got = jax.jit(_REC)(*args), jax.jit(_KERNELS)(*args)
        g_want = jax.jit(jax.grad(_loss(_REC), argnums=_ALL))(*args)
        g_got = jax.jit(jax.grad(_loss(_KERNELS), argnums=_ALL))(*args)
    assert got.shape == want.shape == args[0].shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    for name, a, b in zip(_NAMES, g_got, g_want):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        assert err < 5e-5, (name, err)


def test_the_kernel_path_carries_its_state_from_chunk_to_chunk_and_is_causal():
    args = _operands(7, 384)
    got = _KERNELS(*args)
    dropped = ssd_without_its_state(
        lambda *a: ssd_ops.ssd(*a, interpret=True))(*args, chunk=128)
    # the control differs from the kernel path as it does from the jnp form:
    # the first chunk has nothing to carry; every later one does
    assert np.allclose(dropped[:, :128], got[:, :128], atol=1e-5)
    assert float(jnp.max(jnp.abs(dropped[:, 128:] - got[:, 128:]))) > 0.1
    assert np.allclose(dropped, ssd_without_its_state(ssd_ops.ssd)(*args, chunk=128),
                       atol=1e-4)
    x = args[0].at[:, 300:].add(1.0)
    moved = _KERNELS(x, *args[1:])
    assert np.allclose(moved[:, :300], got[:, :300], atol=1e-6)
    assert not np.allclose(moved[:, 300], got[:, 300], atol=1e-3)


def test_a_strong_decay_leaves_nothing_outside_float32_on_the_kernel_path():
    """dt A = -50 a position: exp(-6400) across a chunk. Every exponent is a
    difference taken before the exp and masked above the diagonal before it,
    so nothing overflows in the result or in any gradient, and both are what
    the recurrence gives: each position all but alone."""
    x, dt, a, b, c = _operands(9, 256)
    dt, a = jnp.full_like(dt, 3.125), jnp.full_like(a, -16.0)
    with precision.policy("float32"):
        got, want = _KERNELS(x, dt, a, b, c), _REC(x, dt, a, b, c)
        grads = jax.grad(_loss(_KERNELS), argnums=_ALL)(x, dt, a, b, c)
        g_want = jax.grad(_loss(_REC), argnums=_ALL)(x, dt, a, b, c)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    for name, g, w in zip(_NAMES, grads, g_want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.linalg.norm(g - w)) <= 5e-5 * float(jnp.linalg.norm(w)) + 1e-12, name


def test_which_form_runs_is_decided_by_backend_and_shape_alone():
    """The kernels where a Pallas call may run (here: the interpreter), the
    chunks are whole chunks of 128, the state fills the lanes and a group's
    heads fill whole lane tiles; the `jnp` form for every other shape, and on
    this backend without the interpreter."""
    calls = lambda interpret, chunk=ssd_ops.CHUNK, **kw: _pallas_calls(
        lambda *a: ssd_ops.ssd(*a, chunk, interpret=interpret),
        *_operands(1, kw.pop("n", 128), **kw))
    assert calls(True) == 1
    assert calls(False) == 0                      # the CPU: no Pallas call may run
    assert calls(True, heads=4, groups=4) == 0    # one head of 64 is half a lane tile
    assert calls(True, hd=8) == 0                 # sixteen heads a tile, two a group
    assert calls(True, hd=96, heads=4, groups=1) == 0   # no whole tiles of lanes
    assert calls(True, state=64) == 0             # half a lane row of state
    assert calls(True, n=64) == 0                 # a short row: one chunk of 64
    assert calls(True, chunk=64) == 0             # not the published chunk
    assert calls(True, hd=128, heads=2, groups=2) == 1
    assert calls(True, hd=32, heads=8, groups=2) == 1    # four heads a tile
    grad = str(jax.make_jaxpr(jax.grad(_loss(_KERNELS), argnums=_ALL))(*_operands(1, 256)))
    # the backward is the kernel's own, not autodiff of the forward's
    assert "ssd_chunk_fwd" in grad and "ssd_chunk_bwd" in grad
    assert grad.count("pallas_call") == 2 and "while" not in grad and "scan" not in grad


def test_the_forward_alone_writes_no_state_and_the_rule_keeps_one_a_chunk():
    """Outside a gradient the forward kernel writes y alone; under one it
    writes the float32 state every chunk started from beside it: the
    residuals are the inputs and those states, [rows, chunks, groups, a
    group's heads x P, N]."""
    args = _operands(5, 384)
    dt = jnp.swapaxes(args[1].reshape(1, 3, 128, 4), 2, 3)
    run = jnp.cumsum(dt * args[2][:, None], axis=-1)
    rows = tuple(t.reshape(1, 3, 2, 2, 128) for t in (dt, run))
    xs = (args[0].reshape(1, 384, 256), args[3].reshape(1, 384, 256),
          args[4].reshape(1, 384, 256), *rows,
          *(jnp.swapaxes(t, -1, -2) for t in rows))
    alone = jax.eval_shape(lambda *a: pk._forward(a, (), 64, 128, jnp.float32, True, False), *xs)
    kept = jax.eval_shape(lambda *a: pk._forward(a, (), 64, 128, jnp.float32, True, True), *xs)
    assert [o.shape for o in alone] == [(1, 384, 256)]
    assert [o.shape for o in kept] == [(1, 384, 256), (1, 3, 2, 128, 128)]
    y, states = pk._forward(xs, (), 64, 128, jnp.float32, True, True)
    want, last = ssd_ops.ssd_recurrent(*(t[:, :256] for t in args[:2]), args[2],
                                       *(t[:, :256] for t in args[3:]))
    # the third chunk starts from the state the first 256 positions leave
    assert np.allclose(states[0, 2].reshape(4, 64, 128), last[0], atol=1e-4)
    assert not np.any(np.asarray(states[0, 0]))
    assert np.allclose(y[:, :256].reshape(1, 256, 4, 64), want, atol=1e-4)


def test_the_layer_hands_the_interpreter_to_its_scan_and_to_nothing_else():
    """`seq_layers.mamba2` at the kernels' widths (two heads of 64, state
    128): under `ApplyCtx.interpret` its scan is the kernel pair, without it
    (this backend) the `jnp` form, and the layer's result and every
    parameter's gradient are the same either way."""
    from sparknet_tpu.model import seq_layers as sl
    from sparknet_tpu.model.layers import ApplyCtx
    from sparknet_tpu.model.spec import LayerSpec, Mamba2Param
    p = Mamba2Param(num_heads=2, head_dim=64, n_groups=1, state_size=128)
    params = sl.init_mamba2(jax.random.PRNGKey(0), LayerSpec(name="m", type="Mamba2", mamba2=p),
                            ((1, 256, 32),))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))
    layer = lambda interpret: lambda params, x: sl.mamba2(
        p, params, x, ApplyCtx(train=True, interpret=interpret))
    assert _pallas_calls(layer(True), params, x) == 1
    assert _pallas_calls(layer(False), params, x) == 0
    loss = lambda fn: jax.jit(jax.value_and_grad(
        lambda params, x: jnp.sum(jnp.sin(30.0 * fn(params, x))), argnums=(0, 1)))
    (got, g_got), (want, g_want) = loss(layer(True))(params, x), loss(layer(False))(params, x)
    assert abs(float(got - want)) < 1e-4 * abs(float(want)) + 1e-4
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-9
