"""Solver tests: lr policies vs closed form, Caffe SGD update rule vs a
hand-written numpy oracle (the reference's update lived in native Caffe —
`libs/CaffeSolver.scala:11-18` — and was never unit-tested)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu import CompiledNet, net_from_prototxt
from sparknet_tpu.solver import SgdSolver, SolverConfig, learning_rate
from tiny_nets import CIFARISH


def lr_at(cfg, it):
    return float(learning_rate(cfg, jnp.asarray(it)))


def approx(x):
    return pytest.approx(x, rel=1e-4)


def test_lr_policies():
    assert lr_at(SolverConfig(base_lr=0.001, lr_policy="fixed"), 999) == approx(0.001)
    step = SolverConfig(base_lr=0.01, lr_policy="step", gamma=0.1, stepsize=100000)
    assert lr_at(step, 0) == approx(0.01)
    assert lr_at(step, 99999) == approx(0.01)
    assert lr_at(step, 100000) == approx(0.001)
    assert lr_at(step, 250000) == approx(0.0001)
    inv = SolverConfig(base_lr=0.01, lr_policy="inv", gamma=0.0001, power=0.75)
    assert lr_at(inv, 0) == approx(0.01)
    assert lr_at(inv, 10000) == approx(0.01 * (1 + 0.0001 * 10000) ** -0.75)
    ms = SolverConfig(base_lr=0.1, lr_policy="multistep", gamma=0.5,
                      stepvalue=(10, 20))
    assert lr_at(ms, 5) == approx(0.1)
    assert lr_at(ms, 10) == approx(0.05)
    assert lr_at(ms, 25) == approx(0.025)
    poly = SolverConfig(base_lr=0.1, lr_policy="poly", power=2.0, max_iter=100)
    assert lr_at(poly, 50) == pytest.approx(0.1 * 0.25)


def test_caffe_sgd_update_rule():
    """V <- m*V + lr*lr_mult*(g + wd*decay_mult*w); W <- W - V, elementwise."""
    net = CompiledNet.compile(net_from_prototxt(CIFARISH))
    cfg = SolverConfig(base_lr=0.05, momentum=0.9, weight_decay=0.004,
                       lr_policy="fixed")
    solver = SgdSolver(net, cfg)
    params = net.init_params(jax.random.PRNGKey(0))
    state = solver.init_state(params)
    g = jax.tree.map(lambda w: jnp.ones_like(w) * 0.5, params)

    # two manual steps to exercise momentum accumulation
    w0 = np.asarray(params["conv1"]["w"])
    b0 = np.asarray(params["conv1"]["b"])
    p1, s1 = solver.update(params, state, g)
    p2, s2 = solver.update(p1, s1, g)

    # conv1 weight: lr_mult=1; bias: lr_mult=2 (from the prototxt params)
    v1 = 0.05 * (0.5 + 0.004 * w0)
    w1 = w0 - v1
    v2 = 0.9 * v1 + 0.05 * (0.5 + 0.004 * w1)
    w2 = w1 - v2
    np.testing.assert_allclose(np.asarray(p2["conv1"]["w"]), w2, rtol=1e-5)

    bv1 = 0.05 * 2 * (0.5 + 0.004 * b0)
    b1 = b0 - bv1
    bv2 = 0.9 * bv1 + 0.05 * 2 * (0.5 + 0.004 * b1)
    b2 = b1 - bv2
    np.testing.assert_allclose(np.asarray(p2["conv1"]["b"]), b2, rtol=1e-5)
    assert int(s2.it) == 2


def test_training_reduces_loss():
    net = CompiledNet.compile(net_from_prototxt(CIFARISH))
    solver = SgdSolver(net, SolverConfig(base_lr=0.01, momentum=0.9,
                                         lr_policy="fixed"))
    params = net.init_params(jax.random.PRNGKey(0))
    state = solver.init_state(params)
    batch = net.example_batch()  # fixed batch -> loss must drop
    losses = []
    for i in range(30):
        params, state, loss = solver.step(params, state, batch,
                                          jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::10]
    assert np.isfinite(losses).all()


def test_iter_size_accumulation_matches_full_batch(rng):
    """Caffe iter_size semantics: k accumulation micro-batches + one update
    == one update on the concatenated batch (loss is a batch mean, so
    grad-mean over micro-batches equals the full-batch grad)."""
    from sparknet_tpu.apps.adult_app import adult_net
    data = rng.standard_normal((8, 16)).astype(np.float32)
    label = rng.integers(0, 2, (8, 1)).astype(np.int32)

    full = CompiledNet.compile(adult_net(batch=8, n_features=16))
    p0 = full.init_params(jax.random.PRNGKey(0))
    s_full = SgdSolver(full, SolverConfig(base_lr=0.1, momentum=0.9,
                                          weight_decay=0.01, iter_size=1))
    st = s_full.init_state(p0)
    pf, stf, loss_f = s_full.step(p0, st, {"C0": data, "label": label})

    half = CompiledNet.compile(adult_net(batch=4, n_features=16))
    p1 = half.init_params(jax.random.PRNGKey(0))
    s_acc = SgdSolver(half, SolverConfig(base_lr=0.1, momentum=0.9,
                                         weight_decay=0.01, iter_size=2))
    st2 = s_acc.init_state(p1)
    pa, sta, loss_a = s_acc.step(p1, st2, {"C0": data, "label": label})

    assert float(loss_a) == pytest.approx(float(loss_f), rel=1e-5)
    assert int(sta.it) == int(stf.it) == 1  # ONE iteration per k micro-batches
    for lname in pf:
        for pname in pf[lname]:
            np.testing.assert_allclose(
                np.asarray(pa[lname][pname]), np.asarray(pf[lname][pname]),
                rtol=1e-5, atol=1e-6, err_msg=f"{lname}/{pname}")


def test_iter_size_indivisible_batch_rejected(rng):
    from sparknet_tpu.apps.adult_app import adult_net
    net = CompiledNet.compile(adult_net(batch=3, n_features=16))
    p = net.init_params(jax.random.PRNGKey(0))
    s = SgdSolver(net, SolverConfig(iter_size=2))
    with pytest.raises(ValueError, match="iter_size"):
        s.step(p, s.init_state(p),
               {"C0": np.zeros((7, 16), np.float32),
                "label": np.zeros((7, 1), np.int32)})


def test_iter_size_rejected_in_distributed_trainer():
    from sparknet_tpu.parallel import ParallelTrainer, make_mesh
    from sparknet_tpu.apps.adult_app import adult_net
    net = CompiledNet.compile(adult_net(batch=4, n_features=16))
    with pytest.raises(ValueError, match="iter_size"):
        ParallelTrainer(net, SolverConfig(iter_size=2), make_mesh(2))


def test_bf16_velocity_opt_in():
    """velocity_dtype='bfloat16' (SolverConfig): the stored momentum
    history is bf16 but each step applies the UNROUNDED f32 velocity, so a
    short trajectory stays close to the exact rule; the default remains
    float32 (Caffe-exact, PARITY.md)."""
    net = CompiledNet.compile(net_from_prototxt(CIFARISH))
    base = dict(base_lr=0.05, momentum=0.9, weight_decay=0.004,
                lr_policy="fixed")
    exact = SgdSolver(net, SolverConfig(**base))
    fast = SgdSolver(net, SolverConfig(velocity_dtype="bfloat16", **base))
    params = net.init_params(jax.random.PRNGKey(0))
    se, sf = exact.init_state(params), fast.init_state(params)
    assert se.momentum["conv1"]["w"].dtype == jnp.float32
    assert sf.momentum["conv1"]["w"].dtype == jnp.bfloat16
    g = jax.tree.map(lambda w: jnp.ones_like(w) * 0.5, params)
    pe, pf = params, params
    for _ in range(3):
        pe, se = exact.update(pe, se, g)
        pf, sf = fast.update(pf, sf, g)
    # params stay f32 and close to the exact trajectory (bf16 has ~3
    # decimal digits; 3 steps of history rounding)
    assert pf["conv1"]["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(pf["conv1"]["w"]),
                               np.asarray(pe["conv1"]["w"]),
                               rtol=2e-2, atol=2e-3)
    with pytest.raises(ValueError, match="velocity_dtype"):
        SgdSolver(net, SolverConfig(velocity_dtype="float16", **base))


def test_bf16_velocity_flows_through_trainer(tmp_path):
    """ParallelTrainer must honor SolverConfig.velocity_dtype when it
    builds the distributed state (it used to zeros_like the params,
    silently pinning f32), and a round must run on the bf16 state."""
    import jax
    from sparknet_tpu.parallel import ParallelTrainer, make_mesh

    from sparknet_tpu.zoo import cifar10_quick
    net = CompiledNet.compile(cifar10_quick(batch=2))
    cfg = SolverConfig(base_lr=0.01, momentum=0.9,
                       velocity_dtype="bfloat16")
    tr = ParallelTrainer(net, cfg, make_mesh(2), tau=2)
    state = tr.init_state(jax.random.PRNGKey(0))
    assert state.momentum["conv1"]["w"].dtype == jnp.bfloat16
    assert state.params["conv1"]["w"].dtype == jnp.float32
    r = np.random.default_rng(0)
    batches = {"data": r.standard_normal((2, 4, 32, 32, 3))
               .astype(np.float32),
               "label": r.integers(0, 10, (2, 4, 1)).astype(np.int32)}
    state, loss = tr.train_round(state, batches, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    assert state.momentum["conv1"]["w"].dtype == jnp.bfloat16
