"""ShardedTrainer parity suite (r7): the NamedSharding-founded trainer
against the shard_map replica-layout trainer.

The two trainers share their round MATH verbatim
(`ParallelTrainer._round_math` runs inside both shard_maps), so on the
f32 TINY_MLP pin the parity is BITWISE — losses, post-round params,
momentum rows, and health scalars. On cifar10_quick through the real
train() loop the trajectory is pinned bitwise too under the default f32
policy and allclose under bf16 (conv reassociation may differ there).
Cross-layout checkpoint resume is pinned exact in all four directions —
the layouts are storage formats of the same logical state, and a resume
must never show which one wrote the snapshot.

state_sharding="momentum"/"full" (ZeRO-1) change SEMANTICS by contract
(momentum is cross-worker averaged once per round), so those modes pin
the per-device at-rest byte reduction and trajectory sanity, not
bitwise equality.
"""
import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu import CompiledNet, net_from_prototxt
from sparknet_tpu.parallel import ParallelTrainer, ShardedTrainer, make_mesh
from sparknet_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from sparknet_tpu.solver import SolverConfig
from sparknet_tpu.utils import checkpoint as ckpt

from tiny_nets import TINY_MLP

N_DEV = 8
TAU = 3
LOCAL_B = 8


@pytest.fixture(scope="module")
def net():
    return CompiledNet.compile(net_from_prototxt(TINY_MLP))


@pytest.fixture(scope="module")
def solver_cfg():
    return SolverConfig(base_lr=0.05, momentum=0.9, weight_decay=0.001,
                        lr_policy="fixed")


def make_round_batches(seed, n_dev=N_DEV):
    r = np.random.default_rng(seed)
    data = r.standard_normal((TAU, n_dev * LOCAL_B, 6)).astype(np.float32)
    label = (data.sum(-1, keepdims=True) > 0).astype(np.int32) + \
        (data[..., :1] > 0.5).astype(np.int32)
    return {"data": data, "label": label}


def assert_trees_bitwise(a, b, msg=""):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree_util.tree_leaves_with_path(b)
    assert len(fa) == len(fb), (msg, len(fa), len(fb))
    for (ka, xa), (_, xb) in zip(fa, fb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), (msg, ka)


from sparknet_tpu.parallel.mesh import per_device_state_bytes  # noqa: E402
# (the ONE at-rest byte ledger — shared with bench.py --sharding so the
# BENCH_r07 acceptance number and this tier-1 pin measure the same thing)


# -- the bitwise pin ---------------------------------------------------------


def test_round_parity_bitwise_tiny_mlp(net, solver_cfg):
    """Multi-round f32 pin: same seeds, same batches -> the NamedSharding
    round must equal the shard_map round BITWISE in losses, params,
    momentum worker rows, and every health scalar."""
    a = ParallelTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    b = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    sa = a.init_state(jax.random.PRNGKey(0))
    sb = b.init_state(jax.random.PRNGKey(0))
    for rnd in range(4):
        rng = jax.random.PRNGKey(100 + rnd)
        sa, la = a.train_round(sa, make_round_batches(rnd), rng)
        sb, lb = b.train_round(sb, make_round_batches(rnd), rng)
        assert float(la) == float(lb), rnd
        for k in a.last_health:
            assert np.array_equal(np.asarray(a.last_health[k]),
                                  np.asarray(b.last_health[k])), (rnd, k)
    assert_trees_bitwise(a.averaged_params(sa), b.averaged_params(sb),
                         "params")
    # replicated-mode momentum: [n_data] worker rows in both layouts
    assert_trees_bitwise(sa.momentum, sb.momentum, "momentum")
    # eval agrees exactly too
    batch = {k: v[0] for k, v in make_round_batches(99).items()}
    assert a.evaluate(sa, batch) == b.evaluate(sb, batch)


def test_round_parity_bitwise_tp2(net, solver_cfg):
    """DPxTP hybrid pin: on a (4, 2) mesh the ShardedTrainer holds FULL
    logical weights column-sharded by spec where the replica trainer
    holds pre-split stacked shards — the round must still match bitwise,
    and averaged_params must materialize identical full weights."""
    def mk():
        return make_mesh(N_DEV, axis_names=(DATA_AXIS, MODEL_AXIS),
                         shape=(4, 2))
    a = ParallelTrainer(net, solver_cfg, mk(), tau=TAU)
    b = ShardedTrainer(net, solver_cfg, mk(), tau=TAU)
    sa = a.init_state(jax.random.PRNGKey(1))
    sb = b.init_state(jax.random.PRNGKey(1))
    for rnd in range(2):
        rng = jax.random.PRNGKey(7 + rnd)
        sa, la = a.train_round(sa, make_round_batches(rnd), rng)
        sb, lb = b.train_round(sb, make_round_batches(rnd), rng)
        assert float(la) == float(lb), rnd
    assert_trees_bitwise(a.averaged_params(sa), b.averaged_params(sb),
                         "tp2 params")
    # the logical TP layout is the serve-side contract: full weights by
    # spec, no reassembly step
    for lname, lp in sb.params.items():
        for pname, leaf in lp.items():
            assert leaf.shape == np.asarray(
                b.averaged_params(sb)[lname][pname]).shape


def test_elastic_tau_masked_round_parity(net, solver_cfg):
    """The elastic_tau traced-budget input works identically in both
    layouts (same masked scan, same [n_data] vector plumbing)."""
    a = ParallelTrainer(net, solver_cfg, make_mesh(4), tau=TAU,
                        elastic_tau=True)
    b = ShardedTrainer(net, solver_cfg, make_mesh(4), tau=TAU,
                       elastic_tau=True)
    sa = a.init_state(jax.random.PRNGKey(2))
    sb = b.init_state(jax.random.PRNGKey(2))
    budgets = (3, 1, 2, 3)
    rng = jax.random.PRNGKey(11)
    batches = make_round_batches(0, n_dev=4)
    sa, la = a.train_round(sa, dict(batches), rng, tau_by_worker=budgets)
    sb, lb = b.train_round(sb, dict(batches), rng, tau_by_worker=budgets)
    assert float(la) == float(lb)
    assert_trees_bitwise(a.averaged_params(sa), b.averaged_params(sb),
                         "elastic_tau")


# -- ZeRO-1 state sharding ---------------------------------------------------


def test_momentum_sharding_cuts_per_device_bytes(net, solver_cfg):
    """state_sharding='momentum' must cut the at-rest per-device momentum
    bytes by >= (n_data-1)/n_data of the shardable momentum bytes (leaves
    with a dim divisible by n_data; indivisible leaves legitimately stay
    whole) while leaving params replicated."""
    rep = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    zm = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                        state_sharding="momentum")
    s_rep = rep.init_state(jax.random.PRNGKey(0))
    s_zm = zm.init_state(jax.random.PRNGKey(0))
    b_rep = per_device_state_bytes(s_rep)
    b_zm = per_device_state_bytes(s_zm)
    assert b_zm["params"] == b_rep["params"]
    # shardable bytes: logical momentum leaves with any dim % n_data == 0
    shardable = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(zm.init_state(
            jax.random.PRNGKey(0)).momentum)
        if any(s % N_DEV == 0 and s > 0 for s in x.shape))
    want_cut = shardable * (N_DEV - 1) // N_DEV
    assert b_rep["momentum"] - b_zm["momentum"] >= want_cut, (
        b_rep, b_zm, shardable)


def test_full_sharding_cuts_param_bytes_too(net, solver_cfg):
    rep = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    zf = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                        state_sharding="full")
    b_rep = per_device_state_bytes(rep.init_state(jax.random.PRNGKey(0)))
    b_zf = per_device_state_bytes(zf.init_state(jax.random.PRNGKey(0)))
    assert b_zf["params"] < b_rep["params"]
    assert b_zf["momentum"] < b_rep["momentum"]


@pytest.mark.parametrize("mode", ["momentum", "full"])
def test_zero1_modes_train_and_stay_finite(net, solver_cfg, mode):
    """The ZeRO modes are a semantic opt-in (momentum cross-worker
    averaged once per round) — pin that they train: loss descends on the
    same easy task, params stay finite, and the jit cache holds one
    executable (the re-shard constraint must not fork variants)."""
    t = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                       state_sharding=mode)
    state = t.init_state(jax.random.PRNGKey(0))
    losses = []
    for rnd in range(6):
        state, loss = t.train_round(state, make_round_batches(rnd % 3),
                                    jax.random.PRNGKey(200 + rnd))
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(state.params))
    assert t.compiled_variants() in (0, 1, 2)  # exe + fast-path key


def test_zero1_requires_named_and_tp1(net, solver_cfg):
    with pytest.raises(NotImplementedError):
        ShardedTrainer(net, solver_cfg,
                       make_mesh(N_DEV, axis_names=(DATA_AXIS, MODEL_AXIS),
                                 shape=(4, 2)),
                       tau=TAU, state_sharding="momentum")
    with pytest.raises(ValueError):
        ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                       state_sharding="typo")
    from sparknet_tpu.apps.train_loop import resolve_trainer_impl
    from sparknet_tpu.utils.config import RunConfig
    with pytest.raises(ValueError):
        resolve_trainer_impl(RunConfig(trainer_impl="shard_map",
                                       state_sharding="momentum"))


def test_resolve_trainer_impl_env_and_knob(monkeypatch):
    from sparknet_tpu.apps.train_loop import resolve_trainer_impl
    from sparknet_tpu.utils.config import RunConfig
    monkeypatch.delenv("SPARKNET_TRAINER_IMPL", raising=False)
    assert resolve_trainer_impl(RunConfig()) == "shard_map"
    monkeypatch.setenv("SPARKNET_TRAINER_IMPL", "named")
    assert resolve_trainer_impl(RunConfig()) == "named"
    # an explicit knob beats the env (the env is the CI matrix lever)
    assert resolve_trainer_impl(
        RunConfig(trainer_impl="shard_map")) == "shard_map"
    with pytest.raises(ValueError):
        resolve_trainer_impl(RunConfig(trainer_impl="nope"))


# -- elastic resize as re-placement -----------------------------------------


def test_resized_carries_class_and_sharding(net, solver_cfg):
    t = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU,
                       state_sharding="momentum")
    t2 = t.resized(4)
    assert type(t2) is ShardedTrainer
    assert t2.state_sharding == "momentum"
    assert t2.n_devices == 4


def test_adapt_live_replacement_matches_checkpoint_roundtrip(net,
                                                             solver_cfg):
    """The elastic fast path: adopting the live logical state onto a
    smaller mesh must equal writing + re-reading a checkpoint (the slow
    path both trainers share) — same params bitwise, same policy-mapped
    momentum."""
    t8 = ShardedTrainer(net, solver_cfg, make_mesh(N_DEV), tau=TAU)
    s8 = t8.init_state(jax.random.PRNGKey(3))
    for rnd in range(2):
        s8, _ = t8.train_round(s8, make_round_batches(rnd),
                               jax.random.PRNGKey(rnd))
    t4 = t8.resized(4)
    live = t4.adapt_live(s8, momentum_policy="norm_rescale")
    from sparknet_tpu.parallel.mesh import fetch_global
    flat = ckpt._flatten(fetch_global(s8))
    via_ckpt = t4.adapt_state(flat, momentum_policy="norm_rescale",
                              old_layout="logical")
    assert_trees_bitwise(live.params, via_ckpt.params, "live params")
    assert_trees_bitwise(live.momentum, via_ckpt.momentum, "live momentum")
    # and the resized trainer actually trains from it
    live2, loss = t4.train_round(live, make_round_batches(9, n_dev=4),
                                 jax.random.PRNGKey(9))
    assert np.isfinite(float(loss))


# -- cross-layout checkpoint resume (the four directions) --------------------


def _loop_cfg(tmp_path, sub, impl, max_rounds, ckdir=None,
              state_sharding="replicated", checkpoint_sharded="auto"):
    from sparknet_tpu.utils.config import RunConfig
    wd = tmp_path / sub
    wd.mkdir(exist_ok=True)
    return RunConfig(
        solver=SolverConfig(base_lr=0.01, momentum=0.9, weight_decay=0.004,
                            lr_policy="fixed"),
        tau=2, local_batch=4, eval_every=0, max_rounds=max_rounds,
        workdir=str(wd), seed=0, trainer_impl=impl,
        state_sharding=state_sharding,
        checkpoint_sharded=checkpoint_sharded,
        checkpoint_dir=str(ckdir or wd / "ck"), checkpoint_every=2,
        checkpoint_async=False)


def _run_loop(tmp_path, sub, impl, max_rounds, ckdir=None,
              state_sharding="replicated", checkpoint_sharded="auto"):
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data import cifar
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import cifar10_quick
    d = str(tmp_path / "cifar")
    if not os.path.isdir(d):
        cifar.write_synthetic(d, n_per_file=40)
    loader = cifar.CifarLoader(d)
    cfg = _loop_cfg(tmp_path, sub, impl, max_rounds, ckdir=ckdir,
                    state_sharding=state_sharding,
                    checkpoint_sharded=checkpoint_sharded)
    jsonl = os.path.join(cfg.workdir, "m.jsonl")
    train(cfg, cifar10_quick(batch=cfg.local_batch),
          ArrayDataset(loader.train_batch_dict()),
          logger=Logger(os.path.join(cfg.workdir, "log.txt"), echo=False,
                        jsonl_path=jsonl))
    losses = [json.loads(l)["loss"] for l in open(jsonl) if '"loss"' in l]
    return losses, cfg


def test_cifar10_quick_loop_trajectory_parity(tmp_path):
    """ISSUE 8 acceptance pin: the NamedSharding trainer reproduces the
    shard_map trainer's cifar10_quick loss trajectory through the REAL
    train() loop. Under the default f32 policy the rounds are the same
    XLA math on the same placement — pinned bitwise, which subsumes the
    allclose-under-bf16 requirement."""
    ref, _ = _run_loop(tmp_path, "ref", "shard_map", 4)
    named, _ = _run_loop(tmp_path, "named", "named", 4)
    assert len(ref) == 4
    assert named == ref


def test_cross_layout_resume_all_directions_exact(tmp_path):
    """A checkpoint is a storage format, not a commitment: each layout
    resumes the other's snapshot and continues the uninterrupted
    trajectory EXACTLY (same-topology momentum rows map 1:1; params are
    logical in both directions)."""
    ref, _ = _run_loop(tmp_path, "ref", "shard_map", 4)
    _, c_named = _run_loop(tmp_path, "seed_named", "named", 2)
    _, c_rep = _run_loop(tmp_path, "seed_rep", "shard_map", 2)
    for i, (src, impl) in enumerate(
            ((c_named, "shard_map"), (c_rep, "named"),
             (c_named, "named"), (c_rep, "shard_map"))):
        ck2 = tmp_path / f"copy{i}"
        shutil.copytree(src.checkpoint_dir, ck2)
        cont, _ = _run_loop(tmp_path, f"cont{i}", impl, 4, ckdir=ck2)
        assert cont == ref[2:], (i, impl, cont, ref)


def test_named_checkpoint_meta_stamps_layout(tmp_path):
    _, cfg = _run_loop(tmp_path, "stamp", "named", 2)
    metas = sorted((tmp_path / "stamp" / "ck").glob("step-*/meta.json"))
    assert metas
    extra = json.load(open(metas[-1]))["extra"]
    assert extra["layout"] == "logical"
    assert extra["state_sharding"] == "replicated"


def test_zero1_loop_checkpoint_roundtrip(tmp_path):
    """state_sharding='momentum' through the loop: checkpoints save the
    gathered logical momentum and a resume continues without error (the
    semantics pin is test_zero1_modes_train_and_stay_finite; here the
    storage path is under test)."""
    _, c1 = _run_loop(tmp_path, "zm", "named", 2,
                      state_sharding="momentum")
    cont, _ = _run_loop(tmp_path, "zm2", "named", 4,
                        ckdir=c1.checkpoint_dir,
                        state_sharding="momentum")
    assert len(cont) == 2 and all(np.isfinite(l) for l in cont)


# -- r8: sharded checkpoint layout, crossed with state layouts + stores ------

_FMT_REF: list = []


@pytest.mark.parametrize("kind", ["local", "gs", "s3"])
def test_cross_layout_and_format_restore_matrix(tmp_path, kind,
                                                monkeypatch):
    """The r8 storage matrix: checkpoint FORMAT (sharded <-> monolithic)
    x state LAYOUT (replica <-> logical) x STORE (local / gs:// / s3://).
    A seed run saves under one (format, layout); a continuation under the
    OTHER format and layout resumes from the same store and must
    reproduce the uninterrupted reference trajectory exactly — the
    format, like the layout, is a storage decision no resume may be able
    to observe."""
    import contextlib
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from fake_stores import bucket_store

    with contextlib.ExitStack() as stack:
        if kind == "local":
            root = None
        else:
            root, _ = stack.enter_context(bucket_store(kind))
        # the uninterrupted reference trajectory is deterministic and
        # store-independent — computed once, reused across the 3 params
        if not _FMT_REF:
            _FMT_REF.extend(_run_loop(tmp_path, "fmt_ref",
                                      "shard_map", 4)[0])
        ref = list(_FMT_REF)
        cells = (("named", "on", "shard_map", "off"),
                 ("shard_map", "off", "named", "on"))
        for i, (impl_a, fmt_a, impl_b, fmt_b) in enumerate(cells):
            ckdir = (f"{root}/fmt{i}" if root
                     else str(tmp_path / f"fmt{i}"))
            _, cfg_a = _run_loop(tmp_path, f"fmt_seed{i}", impl_a, 2,
                                 ckdir=ckdir, checkpoint_sharded=fmt_a)
            meta = ckpt._load_meta(ckpt._join(ckdir, "step-2"))
            assert ("shards" in meta) == (fmt_a == "on"), meta.keys()
            cont, _ = _run_loop(tmp_path, f"fmt_cont{i}", impl_b, 4,
                                ckdir=ckdir, checkpoint_sharded=fmt_b)
            assert cont == ref[2:], (i, kind, cont, ref)
