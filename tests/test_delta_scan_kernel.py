"""`ops.pallas_delta_scan`'s kernel pair under the Pallas interpreter (CPU):
the gated delta rule's walk over chunks with the state inside the kernel
(the forward kernel; the backward kernel behind the backward pass's own walk
for the state every segment started from), held to the `lax.scan`
form of `ops.delta_rule` and its autodiff (the result
and the gradients of q, k, v, g and beta through `gated_delta_rule`, under
both precision policies; the walk alone and the six operands' cotangents on
operands of its own), to the recurrence a position at a time, and to what the
walk is: causal, its state carried from chunk to chunk and made again once a
segment for the backward. Which form runs is `ops.delta_rule`'s to decide,
from backend and shape alone."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision
from sparknet_tpu.ops import delta_rule as dr
from sparknet_tpu.ops import pallas_delta_rule as pk
from sparknet_tpu.ops import pallas_delta_scan as ps
from test_delta_rule import _inputs

_ALL = (0, 1, 2, 3, 4)
_NAMES = "q k v g beta".split()
_OPERANDS = "w_k w_v k_end d_end q_dec b_low".split()

_KERNELS = lambda *a: dr.gated_delta_rule(*a, interpret=True)
_SCAN = lambda *a: dr.gated_delta_rule(*a)      # this backend: `jnp`, a `lax.scan`
_REC = lambda *a: dr.delta_rule_recurrent(*a)[0]


def _loss(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


def _rule_inputs(seed, n, lead=(1,), gates="spread", dtype=jnp.float32):
    """q, k, v, g, beta at the kernels' widths (heads of 128): q, k, v in the
    policy's dtype as the layer hands them over, g and beta float32."""
    q, k, v, g, beta = _inputs(seed, n, gates, lead=lead, dk=128, dv=128)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


#: a tile of two chunks, the second padding; one segment of eight chunks;
#: three segments and a fourth of padding (a row past a program's eight tiles
#: is padded to whole programs); a length that is no whole tile; two rows of
#: two heads (a program of four heads)
_CASES = {"one_chunk": dict(n=64),
          "one_segment": dict(n=512),
          "three_segments": dict(n=1536),
          "padded": dict(n=200),
          "two_rows": dict(n=256, lead=(2, 2))}


@functools.cache
def _both(mode):
    """inputs -> (scan form, kernel path, their gradients): traced under the
    mode's policy, one compile a shape."""
    grads = lambda fn: jax.grad(_loss(fn), argnums=_ALL)
    return jax.jit(lambda *a: (_SCAN(*a), _KERNELS(*a), grads(_SCAN)(*a),
                               grads(_KERNELS)(*a)))


def _pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("case", list(_CASES))
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_kernel_pair_equals_the_scan_form_and_its_autodiff(mode, case):
    args = _rule_inputs(11, dtype=jnp.dtype(mode), **_CASES[case])
    with precision.policy(mode):
        # the chunk stage's kernel and the walk's; neither on this backend
        assert _pallas_calls(_KERNELS, *args) == 2 and _pallas_calls(_SCAN, *args) == 0
        want, got, g_want, g_got = _both(mode)(*args)
    f32 = lambda t: np.asarray(t, np.float32)
    assert got.shape == want.shape == args[2].shape and got.dtype == want.dtype == jnp.float32
    # float32: the products' sums in another order; bfloat16: the same casts
    # in both forms, one rounding apart at the most
    tol = 1e-5 if mode == "float32" else 1e-2
    assert np.max(np.abs(f32(got) - f32(want))) <= tol * np.max(np.abs(f32(want)))
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(f32(a))), name
        err = np.linalg.norm(f32(a) - f32(b)) / (np.linalg.norm(f32(b)) + 1e-30)
        assert err < (5e-5 if mode == "float32" else 25 * 2e-3), (name, err)


def _chunk_operands(seed, nc, many, dtype):
    """Six operands of the walk's own, chunks first, sized as a chunk's are:
    keys of unit norm, decays in (exp -5, 1], tril(B) lower triangular."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    wide = lambda key: jax.random.normal(key, (nc, many, dr.CHUNK, 128))
    b_low = jnp.tril(0.2 * jax.random.normal(ks[5], (nc, many, dr.CHUNK, dr.CHUNK)))
    d_end = jnp.exp(dr.MIN_LOG_DECAY * jax.random.uniform(ks[3], (nc, many, 128)))
    return (0.3 * unit(wide(ks[0])).astype(dtype), wide(ks[1]),
            unit(wide(ks[2])).astype(dtype), d_end,
            unit(wide(ks[4])).astype(dtype), b_low.astype(dtype))


def _walk_in_jnp(*ops):
    """The `lax.scan` form's own walk, the chunks as one segment: o [many,
    chunks x C, dv]."""
    o = dr._walk(tuple(x[None] for x in ops))
    return o.reshape(o.shape[0], -1, o.shape[-1])


#: (chunks, chunks a segment, rows x heads): one chunk; segments of one chunk;
#: three whole segments of three heads (a program of one head); six chunks in
#: one segment of two heads
@pytest.mark.parametrize("nc,seg,many", [(1, 1, 1), (3, 1, 2), (24, 8, 3), (6, 6, 2)])
@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_the_walk_alone_equals_the_scan_and_its_six_cotangents(mode, nc, seg, many):
    dt = jnp.dtype(mode)
    ops = _chunk_operands(nc, nc, many, dt)
    grads = lambda fn: jax.grad(_loss(fn), argnums=tuple(range(6)))
    kernels = lambda *a: ps.scan_chunks(*a, seg, dt, True)
    with precision.policy(mode):
        want, got = jax.jit(_walk_in_jnp)(*ops), jax.jit(kernels)(*ops)
        g_want, g_got = jax.jit(grads(_walk_in_jnp))(*ops), jax.jit(grads(kernels))(*ops)
    f32 = lambda t: np.asarray(t, np.float32)
    assert got.shape == want.shape == (many, nc * dr.CHUNK, 128)
    tol = 1e-5 if mode == "float32" else 1e-2
    assert np.max(np.abs(f32(got) - f32(want))) <= tol * np.max(np.abs(f32(want)))
    for name, a, b in zip(_OPERANDS, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        err = np.linalg.norm(f32(a) - f32(b)) / (np.linalg.norm(f32(b)) + 1e-30)
        # bfloat16: autodiff rounds each cotangent of a cast to bfloat16
        # before it sums them, the kernel sums in float32 and rounds once
        assert err < (5e-5 if mode == "float32" else 25 * 2e-3), (name, err)


@pytest.mark.parametrize("n", [128, 200, 640])
def test_kernel_path_equals_the_recurrence_forward_and_gradient(n):
    """One tile, a padded length, ten chunks in segments of five, against the
    definition."""
    args = _rule_inputs(n, n, lead=(1, 2))
    with precision.policy("float32"):
        want, got = jax.jit(_REC)(*args), jax.jit(_KERNELS)(*args)
        g_want = jax.jit(jax.grad(_loss(_REC), argnums=_ALL))(*args)
        g_got = jax.jit(jax.grad(_loss(_KERNELS), argnums=_ALL))(*args)
    assert got.shape == want.shape == args[2].shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    for name, a, b in zip(_NAMES, g_got, g_want):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        assert err < 5e-5, (name, err)


def test_the_kernel_path_carries_its_state_from_chunk_to_chunk_and_is_causal():
    ops = _chunk_operands(7, 4, 2, jnp.float32)
    walk = jax.jit(lambda *a: ps.scan_chunks(*a, a[0].shape[0], jnp.float32, True))
    rule = jax.jit(_KERNELS)
    args = _rule_inputs(7, 256)
    with precision.policy("float32"):
        got = ps.scan_chunks(*ops, 2, jnp.float32, True)
        # the control: every chunk walked alone, from a zero state
        dropped = jnp.concatenate([walk(*(t[i:i + 1] for t in ops)) for i in range(4)], axis=1)
        o = rule(*args)
        moved = rule(*(t.at[:, 150:].add(0.3) for t in args[:3]), *args[3:])
    # the first chunk has nothing to carry; every later one does
    scale = float(jnp.max(jnp.abs(got)))
    assert np.allclose(dropped[:, :dr.CHUNK], got[:, :dr.CHUNK], atol=1e-6 * scale)
    assert float(jnp.max(jnp.abs(dropped[:, dr.CHUNK:] - got[:, dr.CHUNK:]))) > 0.1 * scale
    assert np.allclose(moved[:, :150], o[:, :150], atol=1e-6)
    assert not np.allclose(moved[:, 150], o[:, 150], atol=1e-3)


def test_decays_at_the_bound_leave_result_and_gradients_finite():
    """g = `MIN_LOG_DECAY` at every position and channel: a chunk hands on
    exp(-320) of the state it was given (flushed to 0 in float32), and the
    result and every gradient are what the recurrence gives."""
    args = _rule_inputs(9, 640, gates="at_the_bound")
    with precision.policy("float32"):
        got, want = jax.jit(_KERNELS)(*args), jax.jit(_REC)(*args)
        grads = jax.jit(jax.grad(_loss(_KERNELS), argnums=_ALL))(*args)
        g_want = jax.jit(jax.grad(_loss(_REC), argnums=_ALL))(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    for name, a, b in zip(_NAMES, grads, g_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        # the decay's own gradient is a difference of near-equal terms there
        assert err < (2e-3 if name == "g" else 5e-5), (name, err)


#: what `_can_pallas` takes and refuses: (the two kernel pairs' calls under
#: the interpreter, the inputs' shape)
_SHAPES = {"heads_of_128": (2, dict(dk=128, dv=128)),
           "heads_of_256": (2, dict(dk=256, dv=256)),
           "a_row_of_one_tile": (2, dict(n=100, dk=128, dv=128)),
           "narrow_heads": (0, dict(dk=16, dv=8)),
           "narrow_values": (0, dict(dk=128, dv=64)),
           "narrow_keys": (0, dict(dk=64, dv=128)),
           "a_row_a_chunk_of_32_holds": (0, dict(n=20, dk=128, dv=128))}


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_which_form_runs_is_decided_by_backend_and_shape_alone(shape):
    """Both kernel pairs where a Pallas call may run (here: the interpreter)
    and the shape is theirs -- one gate for both --, the `jnp` form with its
    `lax.scan` for every other shape, and on this backend without the
    interpreter."""
    calls, kw = _SHAPES[shape]
    kw = dict(kw)
    args = _inputs(1, kw.pop("n", 128), "spread", lead=(1,), **kw)
    made = lambda interpret: jax.make_jaxpr(
        lambda *a: dr.gated_delta_rule(*a, interpret=interpret))(*args)
    # (a kernel's own loops are inside its call: the program's are the outer
    # equations)
    loops = lambda jaxpr: {e.primitive.name for e in jaxpr.eqns} & {"scan", "while"}
    here = made(True)
    assert str(here).count("pallas_call") == calls
    assert bool(loops(here)) == (calls == 0)
    off = made(False)                              # the CPU: no Pallas call may run
    assert str(off).count("pallas_call") == 0 and loops(off) == {"scan"}
    if calls:
        grad = jax.make_jaxpr(jax.grad(_loss(_KERNELS), argnums=_ALL))(*args)
        # the backward is the kernels' own, not autodiff of the walk: the one
        # loop left is the backward pass's own walk for the segments' states
        assert all(name in str(grad) for name in ("delta_chunk_fwd", "delta_chunk_bwd",
                                                  "delta_scan_fwd", "delta_scan_bwd"))
        assert str(grad).count("pallas_call") == 4 and loops(grad) == {"scan"}


def test_the_rule_keeps_the_operands_alone_and_the_backward_makes_a_state_a_segment():
    """The forward kernel writes o and nothing else, under a gradient too:
    the `custom_vjp` keeps the six operands, and the backward pass makes the
    float32 state every SEGMENT started from (`segment_states`, the
    recurrence's own) before its kernel walks the segments in reverse."""
    q, k, v, g, beta = _rule_inputs(5, 1024, lead=(2,))
    seg = 4
    with precision.policy("float32"):
        flat = pk.chunk_operands(q, k, v, g, beta, jnp.float32, True)
        alone = jax.eval_shape(lambda *a: ps._forward(a, seg, jnp.float32, True),
                               *ps._column(flat))
        assert alone.shape == (2, 1024, 128) and alone.dtype == jnp.float32
        o, kept = ps._scan_chunks_fwd(*flat, seg, jnp.float32, True)
        states = dr.segment_states(*flat[:4], seg)
    assert [x.shape for x in kept] == [x.shape for x in flat]
    assert states.shape == (4, 2, 128, 128) and states.dtype == jnp.float32
    assert not np.any(np.asarray(states[0]))
    for s in (1, 2, 3):  # segment s starts from what the positions before it leave
        upto = s * seg * dr.CHUNK
        want, last = dr.delta_rule_recurrent(q[:, :upto], k[:, :upto], v[:, :upto],
                                             g[:, :upto], beta[:, :upto])
        assert np.allclose(states[s], last, atol=1e-5)
        assert np.allclose(o[:, :upto], want, atol=1e-5)


def test_the_layer_hands_the_interpreter_to_both_kernel_pairs_of_its_rule():
    """`seq_layers.kda` at the kernels' widths (two heads of 128): under
    `ApplyCtx.interpret` its rule is the chunk stage's kernel and the walk's
    under the scope `delta`, without it (this backend) the `jnp` form with
    its `lax.scan`, and the layer's result and its input's gradient are the
    same either way."""
    from sparknet_tpu.model import seq_layers as sl
    from sparknet_tpu.model.layers import ApplyCtx
    from sparknet_tpu.model.spec import KDAttentionParam, LayerSpec
    p = KDAttentionParam(num_heads=2, head_dim=128, taps=4, lower_bound=-5.0, eps=1e-6)
    params = sl.init_kdattention(jax.random.PRNGKey(0),
                                 LayerSpec(name="k", type="KDAttention", kda=p),
                                 ((1, 256, 32),))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))
    layer = lambda interpret: lambda params, x: sl.kda(
        p, params, x, ApplyCtx(train=True, interpret=interpret))
    text = str(jax.make_jaxpr(layer(True))(params, x))
    assert "delta_chunk_fwd" in text and "delta_scan_fwd" in text
    off = str(jax.make_jaxpr(layer(False))(params, x))
    assert "delta_scan_fwd" not in off and "delta_chunk_fwd" not in off
    loss = lambda fn: jax.jit(jax.value_and_grad(
        lambda params, x: jnp.sum(jnp.sin(30.0 * fn(params, x))), argnums=1))
    (got, g_got), (want, g_want) = loss(layer(True))(params, x), loss(layer(False))(params, x)
    assert abs(float(got - want)) < 1e-4 * abs(float(want)) + 1e-4
    assert float(jnp.linalg.norm(g_got - g_want)) <= 1e-4 * float(jnp.linalg.norm(g_want)) + 1e-9
