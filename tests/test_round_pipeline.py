"""Round-pipeline overlap & fuse (r6): bit-exactness pins for the three
MFU levers — double-buffered H2D pre-placement, batch-buffer donation, and
the Pallas LRN wiring in the layer path — plus the jit-cache-churn
gauge check. The levers may only move WHERE work happens (prefetch thread
vs dispatch, donated vs fresh buffers, kernel vs XLA lowering), never WHAT
is computed: pre-placement and donation pin bitwise, the kernel pins to
parity tolerances under the bf16 policy. Each arm of a switch (donation,
the fused boundary) is ALSO held to the serial reference of a round
(tests/round_oracle.py), so an arm can leave the tree with its own cases.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import round_oracle
from sparknet_tpu import CompiledNet, net_from_prototxt, precision
from sparknet_tpu.parallel import ParallelTrainer, make_mesh
from sparknet_tpu.solver import SolverConfig

N_DEV = 4
TAU = 3
LOCAL_B = 8

TINY_MLP = """
name: "tiny_mlp"
input: "data"
input_shape { dim: 8 dim: 6 }
input: "label"
input_shape { dim: 8 dim: 1 }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "gaussian" std: 0.3 } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "gaussian" std: 0.3 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label"
        top: "loss" }
"""

# conv -> LRN -> MAX pool -> ip -> loss at Pallas-gate-friendly shapes:
# batch 128 (the LRN N-minor kernel's lane alignment), pool 3x3/2 pad 0
# (the CaffeNet pool geometry), C=16 (the bf16 sublane tile)
CONV_LRN_POOL = """
name: "conv_lrn_pool"
input: "data"
input_shape { dim: 128 dim: 3 dim: 9 dim: 9 }
input: "label"
input_shape { dim: 128 dim: 1 }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 16 kernel_size: 3
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
        inner_product_param { num_output: 4
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip1" bottom: "label"
        top: "loss" }
"""


@pytest.fixture(scope="module")
def net():
    return CompiledNet.compile(net_from_prototxt(TINY_MLP))


@pytest.fixture(scope="module")
def solver_cfg():
    return SolverConfig(base_lr=0.05, momentum=0.9, weight_decay=0.001,
                        lr_policy="fixed")


def make_round_batches(seed, tau=TAU):
    r = np.random.default_rng(seed)
    data = r.standard_normal((tau, N_DEV * LOCAL_B, 6)).astype(np.float32)
    label = (data.sum(-1, keepdims=True) > 0).astype(np.int32)
    return {"data": data, "label": label}


def params_np(state):
    return jax.tree.map(np.asarray, state.params)


def assert_trees_bitwise(a, b, msg=""):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree_util.tree_leaves_with_path(b)
    assert len(fa) == len(fb)
    for (ka, xa), (_, xb) in zip(fa, fb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), (msg, ka)


# -- pin (a): pre-placed device batches == host batches ----------------------


def test_preplaced_batches_bitwise_equal_host_batches(net, solver_cfg,
                                                      trainer_cls):
    """place_batches on the 'prefetch side' then train_round must produce
    the SAME post-round params as handing train_round the host arrays —
    pre-placement is the same cast + put_device_axis, just earlier."""
    mesh = make_mesh(N_DEV)
    t_host = trainer_cls(net, solver_cfg, mesh, tau=TAU)
    t_pre = trainer_cls(net, solver_cfg, mesh, tau=TAU)
    s_host = t_host.init_state(jax.random.PRNGKey(3))
    s_pre = t_pre.init_state(jax.random.PRNGKey(3))
    for rnd in range(3):
        rng = jax.random.PRNGKey(50 + rnd)
        s_host, l_host = t_host.train_round(s_host, make_round_batches(rnd),
                                            rng)
        placed = t_pre.place_batches(make_round_batches(rnd))
        assert all(isinstance(v, jax.Array) for v in placed.values())
        s_pre, l_pre = t_pre.train_round(s_pre, placed, rng)
        assert float(l_host) == float(l_pre)
    assert_trees_bitwise(params_np(s_host), params_np(s_pre), "preplaced")


def test_preplaced_batches_thread_cast_matches_main_thread(net, solver_cfg,
                                                           trainer_cls):
    """The prefetch thread passes compute_dt explicitly (the precision
    policy is thread-local): placement on a worker thread under the bf16
    policy must equal main-thread placement bit for bit."""
    from concurrent.futures import ThreadPoolExecutor

    mesh = make_mesh(N_DEV)
    t = trainer_cls(net, solver_cfg, mesh, tau=TAU)
    with precision.policy("bfloat16"):
        dt = precision.compute_dtype()
        main = t.place_batches(make_round_batches(0), dt)
        with ThreadPoolExecutor(1) as exe:
            # the worker thread sees the DEFAULT (f32) policy; compute_dt
            # must carry the main thread's choice across
            threaded = exe.submit(
                t.place_batches, make_round_batches(0), dt).result()
    for k in main:
        assert main[k].dtype == threaded[k].dtype
        assert np.array_equal(np.asarray(main[k]), np.asarray(threaded[k]))
    assert main["data"].dtype == jnp.bfloat16


# -- pin (b): donated-batch rotation never aliases a live buffer -------------


@pytest.mark.parametrize("donate", [False, True],
                         ids=["fresh", "donated"])
def test_donating_trainer_bitwise_equals_non_donating(net, solver_cfg,
                                                      trainer_cls, donate):
    """Hammer τ rounds through a donate_batches trainer fed freshly placed
    batches each round (the train loop's two-slot rotation) and through
    the legacy non-donating trainer. The arm named by `donate` is held,
    round by round, to the serial reference of a round; and while both
    arms exist every round's loss and the final params must match
    BITWISE — donation may recycle buffers, never values."""
    mesh = make_mesh(N_DEV)
    t_ref = trainer_cls(net, solver_cfg, mesh, tau=TAU)
    t_don = trainer_cls(net, solver_cfg, mesh, tau=TAU,
                        donate_batches=True)
    assert t_don.donate_batches and not t_ref.donate_batches
    arm = t_don if donate else t_ref
    s_ref = t_ref.init_state(jax.random.PRNGKey(9))
    s_don = t_don.init_state(jax.random.PRNGKey(9))
    placed_prev = None
    for rnd in range(8):
        rng = jax.random.PRNGKey(70 + rnd)
        start = round_oracle.split_state(arm, s_don if donate else s_ref)
        s_ref, l_ref = t_ref.train_round(s_ref, make_round_batches(rnd), rng)
        # two-slot rotation: place round R+1's buffers while round R's
        # (donated) are still owned by the executable, as the loop does
        placed = t_don.place_batches(make_round_batches(rnd))
        if placed_prev is not None:
            # the previous round's donated buffers are dead; the fresh
            # placement must not have resurrected them
            for k in placed:
                assert placed[k] is not placed_prev[k]
        s_don, l_don = t_don.train_round(s_don, placed, rng)
        placed_prev = placed
        s_arm, l_arm = (s_don, l_don) if donate else (s_ref, l_ref)
        round_oracle.assert_round_matches(arm, start, s_arm, l_arm,
                                          make_round_batches(rnd), rng)
        assert float(l_ref) == float(l_don), rnd
    assert_trees_bitwise(params_np(s_ref), params_np(s_don), "donate")


def test_donated_batches_are_consumed(net, solver_cfg, trainer_cls):
    """The donation contract: train_round CONSUMES the batch buffers — a
    caller re-feeding the same placed dict must fail loudly (deleted
    arrays), not silently compute on recycled memory."""
    mesh = make_mesh(N_DEV)
    t = trainer_cls(net, solver_cfg, mesh, tau=TAU, donate_batches=True)
    s = t.init_state(jax.random.PRNGKey(0))
    placed = t.place_batches(make_round_batches(0))
    s, loss = t.train_round(s, placed, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    if not any(getattr(v, "is_deleted", lambda: False)()
               for v in placed.values()):
        # XLA:CPU declines batch donation ("donated buffers were not
        # usable") and leaves the arrays alive — the consumed contract is
        # only observable where donation really happens (TPU)
        pytest.skip("backend did not honor batch donation")
    with pytest.raises(Exception):  # RuntimeError: Array has been deleted
        _ = [np.asarray(v) for v in placed.values()]
        t.train_round(s, placed, jax.random.PRNGKey(2))


# -- satellite: jit-cache churn gauge ----------------------------------------


def test_overlapped_round_holds_steady_jit_cache(net, solver_cfg,
                                                 trainer_cls):
    """The overlapped/donating round must hold a STEADY executable cache:
    pre-placement and donation may not introduce shape/layout churn. The
    vanilla trainer's cache plateaus after round 1 (the round-0 entry is
    keyed on the freshly device_put state, round 1 on the round's own
    donated output — same ONE executable, two fast-path keys on this
    jax); the levered trainer must plateau at the SAME count and never
    grow past it."""
    mesh = make_mesh(N_DEV)
    t_ref = trainer_cls(net, solver_cfg, mesh, tau=TAU)
    t_lev = trainer_cls(net, solver_cfg, mesh, tau=TAU,
                        donate_batches=True)
    s_ref = t_ref.init_state(jax.random.PRNGKey(0))
    s_lev = t_lev.init_state(jax.random.PRNGKey(0))
    for rnd in range(2):  # reach steady state (round-0 key + output key)
        rng = jax.random.PRNGKey(rnd)
        s_ref, _ = t_ref.train_round(s_ref, make_round_batches(rnd), rng)
        s_lev, _ = t_lev.train_round(
            s_lev, t_lev.place_batches(make_round_batches(rnd)), rng)
    steady_ref = t_ref.compiled_variants()
    steady_lev = t_lev.compiled_variants()
    assert steady_lev == steady_ref  # no churn introduced by the levers
    for rnd in range(2, 8):
        rng = jax.random.PRNGKey(rnd)
        s_ref, _ = t_ref.train_round(s_ref, make_round_batches(rnd), rng)
        s_lev, _ = t_lev.train_round(
            s_lev, t_lev.place_batches(make_round_batches(rnd)), rng)
        assert t_lev.compiled_variants() == steady_lev, rnd
        assert t_ref.compiled_variants() == steady_ref, rnd


def test_preplaced_wrong_dtype_fails_loudly(net, solver_cfg, trainer_cls):
    """The dtype half of the placement contract is ENFORCED, not just
    documented: a float32 jax.Array fed under the bf16 policy (a caller
    that placed without the compute-dtype cast — cast_host_inputs skips
    device arrays) must fail at first sight, not silently train an f32
    second executable."""
    t = trainer_cls(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    bad = {k: jnp.asarray(v) for k, v in make_round_batches(0).items()}
    with precision.policy("bfloat16"):
        with pytest.raises(AssertionError, match="compute dtype"):
            t.place_batches(bad)


def test_preplaced_wrong_sharding_fails_loudly(net, solver_cfg,
                                               trainer_cls):
    """The SHARDING half of the placement contract: a jax.Array placed
    without the P(None, data) spec (e.g. a plain single-device
    device_put) must fail at first sight — passing it through would make
    jit reshard it inside every dispatch, a real per-round copy hidden
    behind the passthrough's t_h2d_ms ~ 0."""
    t = trainer_cls(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    bad = {k: jax.device_put(jnp.asarray(v), jax.devices()[0])
           for k, v in make_round_batches(0).items()}
    with pytest.raises(AssertionError, match="sharding"):
        t.place_batches(bad)


def test_batch_invariants_still_enforced_on_first_call(net, solver_cfg,
                                                       trainer_cls):
    """Hoisting the shape checks to first sight must not lose them: a
    wrong tau or an indivisible batch still fails loudly."""
    t = trainer_cls(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    good = make_round_batches(0)
    with pytest.raises(AssertionError, match="tau"):
        t.place_batches({k: v[:1] for k, v in good.items()})
    with pytest.raises(AssertionError, match="divisible"):
        t.place_batches({k: v[:, :N_DEV * LOCAL_B - 1]
                         for k, v in good.items()})


def test_pallas_lrn_inside_sharded_round(solver_cfg):
    """The kernel must trace inside the shard_map'd ROUND, not just in a
    bare loss_fn: pallas_call has no shard_map replication rule, so the
    trainer switches replication checking off where ops/ may route a layer
    to a kernel (the net-level parity tests below bypass shard_map and
    cannot catch a trace-time crash here). Compared with the same round
    on the fused form a CPU runs."""
    net = CompiledNet.compile(net_from_prototxt(CONV_LRN_POOL))
    r = np.random.default_rng(11)
    batches = {
        "data": r.standard_normal((2, 32, 9, 9, 3)).astype(np.float32),
        "label": r.integers(0, 4, (2, 32, 1)).astype(np.int32)}
    t_pal = ParallelTrainer(net, solver_cfg, make_mesh(N_DEV), tau=2,
                            interpret=True)
    t_xla = ParallelTrainer(net, solver_cfg, make_mesh(N_DEV), tau=2)
    rng = jax.random.PRNGKey(1)
    _, l_pal = t_pal.train_round(
        t_pal.init_state(jax.random.PRNGKey(0)), dict(batches), rng)
    _, l_xla = t_xla.train_round(
        t_xla.init_state(jax.random.PRNGKey(0)), dict(batches), rng)
    assert np.isfinite(float(l_pal))
    assert float(l_pal) == pytest.approx(float(l_xla), rel=1e-3)


# -- pin (c): net-level Pallas-vs-XLA parity under the bf16 policy -----------


def _loss_and_grads(net, interpret, batch, params):
    loss_fn = net.loss_fn("loss", interpret=interpret)
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, jax.random.PRNGKey(0)),
        has_aux=True)(params)
    return float(loss), jax.tree.map(np.asarray, grads)


def _parity_net_and_batch():
    net = CompiledNet.compile(net_from_prototxt(CONV_LRN_POOL))
    r = np.random.default_rng(4)
    batch = {
        "data": jnp.asarray(
            r.standard_normal((128, 9, 9, 3)).astype(np.float32)),
        "label": jnp.asarray(r.integers(0, 4, (128, 1)).astype(np.int32))}
    params = net.init_params(jax.random.PRNGKey(2))
    return net, batch, params


def test_net_level_pallas_lrn_parity_bf16():
    """The LAYER-PATH wiring pin (kernel-level parity lives in
    tests/test_pallas_lrn.py): the same net through interpret=True (the
    kernel path, as on the chip) vs interpret=False (the fused form a CPU
    runs, itself held to the reduce_window oracle at op level by
    tests/test_ops.py), loss + all grads, under the bf16 precision policy
    the TPU headline runs."""
    net, batch, params = _parity_net_and_batch()
    with precision.policy("bfloat16"):
        l_pal, g_pal = _loss_and_grads(net, True, batch, params)
        l_xla, g_xla = _loss_and_grads(net, False, batch, params)
    # both paths quantize the LRN output to bf16 once; differences are
    # accumulation-order ulps inside the f32 normalizer
    assert l_pal == pytest.approx(l_xla, rel=2e-2)
    for (kp, gp), (_, gx) in zip(
            jax.tree_util.tree_leaves_with_path(g_pal),
            jax.tree_util.tree_leaves_with_path(g_xla)):
        np.testing.assert_allclose(
            np.asarray(gp, np.float32), np.asarray(gx, np.float32),
            rtol=5e-2, atol=5e-3, err_msg=str(kp))


def test_ops_knobs_thread_through_trainer(net, solver_cfg):
    """`interpret` (RunConfig.ops_interpret) reaches the compiled round AND
    survives an elastic resize (resized() carries donate_batches +
    interpret)."""
    t = ParallelTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                        donate_batches=True, interpret=True)
    assert t.interpret is True
    s = t.init_state(jax.random.PRNGKey(0))
    s, loss = t.train_round(s, make_round_batches(0), jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    t2 = t.resized(2)
    assert t2.interpret is True and t2.donate_batches


@pytest.mark.parametrize("op", ["pool", "lrn"])
def test_run_config_rejects_the_removed_kernel_knobs(op):
    """A stale config file is input from outside: which kernel an op runs
    is no option any more (ops/ decides), and a file that still sets
    `<op>_impl` fails at parse with the unknown-key error, not silently."""
    from sparknet_tpu.utils.config import RunConfig
    with pytest.raises(ValueError, match="unknown config keys"):
        RunConfig.from_dict({f"{op}_impl": "xla"})


# -- loop-level wiring: the knobs through train() ----------------------------


def _run_tiny_train(tmp_path, tag, **overrides):
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet

    r = np.random.default_rng(0)
    ds = ArrayDataset({
        "data": r.standard_normal((256, 1, 28, 28)).astype(np.float32),
        "label": r.integers(0, 10, (256, 1)).astype(np.int32)})
    root = os.path.join(str(tmp_path), tag)
    os.makedirs(root)
    cfg = RunConfig(model="lenet", n_devices=2, local_batch=8, tau=2,
                    max_rounds=4, eval_every=0, workdir=root,
                    **overrides)
    jsonl = os.path.join(root, "m.jsonl")
    log = Logger(os.path.join(root, "l.txt"), echo=False, jsonl_path=jsonl)
    try:
        train(cfg, lenet(batch=8), ds, None, logger=log)
    finally:
        log.close()
    return [json.loads(l) for l in open(jsonl) if "loss" in l]


def test_train_loop_levers_do_not_change_the_trajectory(tmp_path):
    """train() with every r6 lever ON (the defaults: h2d prefetch on the
    round-prep thread, donated batches) must reproduce the lever-less
    loop's losses BITWISE, and the breakdown rows must show the prefetch
    h2d residual at ~0."""
    on = _run_tiny_train(tmp_path, "on")  # defaults: levers on
    off = _run_tiny_train(tmp_path, "off", h2d_prefetch=False,
                          donate_batches=False)
    assert [rec["step"] for rec in on] == [rec["step"] for rec in off]
    for a, b in zip(on, off):
        assert a["loss"] == b["loss"], (a, b)
    # the placement happened on the prefetch thread: the dispatch-side h2d
    # phase sees only the passthrough (pre-placed contract), not the copy
    assert all("t_h2d_ms" in rec for rec in on)
    on_h2d = [rec["t_h2d_ms"] for rec in on[1:]]   # round 0 places inline
    assert max(on_h2d) < 50.0, on_h2d  # passthrough, not a batch copy


# -- r8: fused τ-boundary + async collect ------------------------------------


def _assert_arms_bitwise(ref, s_ref, l_ref, fused, s_fus, l_fus, what):
    """The arm-against-arm pin, while both arms exist: losses, params,
    momentum AND the health scalars of one round, bit for bit."""
    assert float(l_ref) == float(l_fus), what
    assert_trees_bitwise(s_ref, s_fus, what)
    for k in ("grad_norm", "nonfinite", "nonfinite_by_worker"):
        assert np.array_equal(np.asarray(ref.last_health[k]),
                              np.asarray(fused.last_health[k])), (what, k)


# τ=2 is the scan of ONE step before the peeled one: the smallest round in
# which reading rows by index and slicing the stack could differ
@pytest.mark.parametrize("fused_arm", [False, True],
                         ids=["unfused", "fused"])
@pytest.mark.parametrize("tau", [TAU, 2])
def test_fused_boundary_bitwise_multi_round(net, solver_cfg, trainer_cls,
                                            tau, fused_arm):
    """The r8 fused τ-boundary (final scan step peeled so the boundary
    pmean — and the ZeRO re-shard under the named trainer — traces in the
    same region as the last optimizer update) must be a pure
    RESTRUCTURING: the same ops on the same values in the same order.
    The arm named by `fused_arm` is held, round by round over a
    multi-round trajectory, to the serial reference of a round (weights,
    momentum, clock, loss, health scalars); and while both arms exist they
    are pinned bitwise against each other — under BOTH trainer impls (the
    conftest trainer_cls matrix)."""
    mesh = make_mesh(N_DEV)
    ref = trainer_cls(net, solver_cfg, mesh, tau=tau)
    fused = trainer_cls(net, solver_cfg, mesh, tau=tau,
                        fused_boundary=True)
    assert ref.fused_boundary is False and fused.fused_boundary is True
    arm = fused if fused_arm else ref
    s_ref = ref.init_state(jax.random.PRNGKey(0))
    s_fus = fused.init_state(jax.random.PRNGKey(0))
    for rnd in range(4):
        batches = make_round_batches(rnd, tau)
        key = jax.random.PRNGKey(rnd)
        start = round_oracle.split_state(arm, s_fus if fused_arm else s_ref)
        s_ref, l_ref = ref.train_round(s_ref, batches, key)
        s_fus, l_fus = fused.train_round(s_fus, batches, key)
        s_arm, l_arm = (s_fus, l_fus) if fused_arm else (s_ref, l_ref)
        round_oracle.assert_round_matches(arm, start, s_arm, l_arm, batches,
                                          key)
        _assert_arms_bitwise(ref, s_ref, l_ref, fused, s_fus, l_fus, rnd)


@pytest.mark.parametrize("fused_arm", [False, True],
                         ids=["unfused", "fused"])
@pytest.mark.parametrize("kw,tau,tbw", [
    ({}, 1, None),
    ({"elastic_tau": True}, TAU, [1, TAU, 2, TAU]),
    ({"elastic_tau": True}, 2, [1, 2, 2, 1]),
], ids=["tau1", "elastic-tau3", "elastic-tau2"])
def test_fused_boundary_tau1_and_elastic_masked(net, solver_cfg,
                                                trainer_cls, kw, tau, tbw,
                                                fused_arm):
    """Edge geometry: τ=1 compiles the fused round scan-free, and an
    elastic_tau-masked round (per-worker budgets, the peeled final step
    masked off for short-budget workers). The arm named by `fused_arm` is
    held to the serial reference in which a worker with a budget simply
    stops there; the two arms, fed the same tau vector, still pin
    bitwise."""
    mesh = make_mesh(N_DEV)
    ref = trainer_cls(net, solver_cfg, mesh, tau=tau, **kw)
    fused = trainer_cls(net, solver_cfg, mesh, tau=tau,
                        fused_boundary=True, **kw)
    arm = fused if fused_arm else ref
    s_ref = ref.init_state(jax.random.PRNGKey(1))
    s_fus = fused.init_state(jax.random.PRNGKey(1))
    r = np.random.default_rng(5)
    batches = {
        "data": r.standard_normal(
            (tau, N_DEV * LOCAL_B, 6)).astype(np.float32)}
    batches["label"] = (batches["data"].sum(-1, keepdims=True)
                        > 0).astype(np.int32)
    extra = {"tau_by_worker": tbw} if tbw is not None else {}
    start = round_oracle.split_state(arm, s_fus if fused_arm else s_ref)
    s_ref, l_ref = ref.train_round(s_ref, batches,
                                   jax.random.PRNGKey(2), **extra)
    s_fus, l_fus = fused.train_round(s_fus, batches,
                                     jax.random.PRNGKey(2), **extra)
    s_arm, l_arm = (s_fus, l_fus) if fused_arm else (s_ref, l_ref)
    round_oracle.assert_round_matches(arm, start, s_arm, l_arm, batches,
                                      jax.random.PRNGKey(2),
                                      tau_by_worker=tbw)
    _assert_arms_bitwise(ref, s_ref, l_ref, fused, s_fus, l_fus, (tau, tbw))


def test_fused_boundary_resize_carries_knob(net, solver_cfg, trainer_cls):
    t = trainer_cls(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                    fused_boundary=True)
    assert t.resized(2).fused_boundary is True


def test_async_collect_loop_bitwise_and_t_collect_zero(tmp_path):
    """The r8 loop levers through the REAL train(). Async collect only
    moves WHERE the deferred fetch blocks (the collector thread, not the
    round loop), so collect on/off must reproduce the same losses
    BITWISE — and with it on, the breakdown's t_collect_ms (the round
    loop's blocking share) must read ~0 with the off-thread fetch
    attributed as t_collect_bg_ms. The fused boundary changes the traced
    program shape (peeled final step), which on conv nets shifts XLA's
    fusion tiling at the last ulp — same caveat the elastic_tau masking
    documents — so fused on/off pins at ulp tolerance here; the BITWISE
    fused pin is the TINY_MLP trainer matrix above."""
    on = _run_tiny_train(tmp_path, "r8_on")  # defaults: fused + async
    sync = _run_tiny_train(tmp_path, "r8_sync", collect_async=False)
    unfused = _run_tiny_train(tmp_path, "r8_unf", fused_boundary=False,
                              collect_async=False)
    assert [rec["step"] for rec in on] == [rec["step"] for rec in sync]
    for a, b in zip(on, sync):
        assert a["loss"] == b["loss"], (a, b)  # collect: bitwise
    for a, b in zip(on, unfused):  # fused: same math, ulp-level conv
        assert abs(a["loss"] - b["loss"]) <= 1e-5 * abs(b["loss"]), (a, b)
    on_rows = [rec for rec in on if "t_collect_ms" in rec]
    assert on_rows, "breakdown rows missing under async collect"
    assert all(rec["t_collect_ms"] == 0.0 for rec in on_rows), on_rows
    assert all("t_collect_bg_ms" in rec for rec in on_rows)
    sync_rows = [rec for rec in sync if "t_collect_ms" in rec]
    assert all("t_collect_bg_ms" not in rec for rec in sync_rows)
