"""Collective-traffic pin for the distributed round.

BASELINE.md's >=90%-scaling claim rests on the round moving EXACTLY one
copy of the net's parameters per τ-round (weight pmean; momentum stays
worker-local — reference `libs/CaffeNet.scala:123-137` only ships net
blobs). PERF.md §ici-scaling-model turns that byte count into predicted
efficiency at 8/16/32 chips; this test pins the byte count itself by
inspecting the compiled round's optimized HLO, so an accidental extra
all-gather / per-step sync / momentum-on-the-wire regression fails CI
instead of silently halving the predicted scaling.

Pinned properties (on the 8-virtual-device CPU mesh, caffenet shapes):
  1. bytes all-reduced per round ≈ one per-replica copy of the params
     (+ the scalar loss pmean) — NOT ×τ, NOT params+momentum;
  2. τ-invariance: compiling at τ=2 and τ=4 moves identical bytes
     (averaging is per-round, never per-step);
  3. op-count sanity: the number of collective ops stays bounded by the
     param-leaf count + loss (XLA's combiner may merge below that).
"""
import re

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from sparknet_tpu import CompiledNet
from sparknet_tpu.parallel import ParallelTrainer, make_mesh
from sparknet_tpu.parallel.mesh import DATA_AXIS, place_global_state
from sparknet_tpu.solver import SolverConfig
from sparknet_tpu.zoo import caffenet

N_DEV = 8
LOCAL_B = 4
CROP = 67
N_CLASSES = 16

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}

# result shapes of an HLO op line: `f32[1,96,3,11,11]{4,3,2,1,0}` tokens
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _collective_lines(hlo: str):
    """(op_kind, result_bytes) for every collective in the optimized HLO.

    `-start` variants are the async halves of the same op — counting
    `-done` too would double; we take only starts + synchronous forms."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= (.+?) (all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)"
                      r"(-start)?\(", line)
        if not m:
            continue
        result, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(result):
            if dt not in _DTYPE_BYTES:
                continue  # layout annotation like {4,3,2,1,0}
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out.append((kind, nbytes))
    return out


def _build(tau: int):
    net = CompiledNet.compile(
        caffenet(batch=LOCAL_B, crop=CROP, n_classes=N_CLASSES))
    mesh = make_mesh(N_DEV)
    trainer = ParallelTrainer(
        net, SolverConfig(base_lr=0.01, momentum=0.9, weight_decay=5e-4,
                          lr_policy="fixed"), mesh, tau=tau)
    state = trainer.init_state(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batches = {
        "data": r.standard_normal(
            (tau, N_DEV * LOCAL_B, CROP, CROP, 3)).astype(np.float32),
        "label": r.integers(0, N_CLASSES,
                            (tau, N_DEV * LOCAL_B, 1)).astype(np.int32)}
    sharded = trainer._shard_batches(batches)
    rngs = place_global_state(
        jax.random.split(jax.random.PRNGKey(1), N_DEV),
        trainer.mesh, P(DATA_AXIS))
    return trainer, state, sharded, rngs


def _round_collectives(tau: int):
    trainer, state, sharded, rngs = _build(tau)
    import jax.numpy as jnp
    hlo = trainer._round.lower(state, sharded, rngs,
                               jnp.asarray(1.0, jnp.float32)
                               ).compile().as_text()
    per_replica_param_bytes = sum(
        int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
        for lp in jax.tree.leaves(
            state.params, is_leaf=lambda x: hasattr(x, "shape"))
        for leaf in [lp])
    n_leaves = len(jax.tree.leaves(state.params))
    return _collective_lines(hlo), per_replica_param_bytes, n_leaves


@pytest.fixture(scope="module")
def tau2():
    return _round_collectives(2)


def test_round_moves_one_param_copy(tau2):
    colls, param_bytes, n_leaves = tau2
    assert colls, "no collectives found in the compiled round HLO"
    kinds = {k for k, _ in colls}
    # DP round: weight average + loss average are pmean -> all-reduce.
    # Anything else on the wire is a regression.
    assert kinds == {"all-reduce"}, f"unexpected collectives: {kinds}"
    total = sum(b for _, b in colls)
    # one param copy + three f32 scalars: the loss and the two health
    # signals (grad_norm, nonfinite count — reduced over τ BEFORE the
    # psum, so they stay scalars; combiner padding tolerance 1%)
    assert param_bytes <= total <= int(param_bytes * 1.01) + 256, (
        f"round all-reduces {total} bytes; params are {param_bytes} — "
        f"{'momentum or batch data is on the wire' if total > param_bytes * 1.5 else 'short of one param copy'}")
    assert len(colls) <= n_leaves + 3, (
        f"{len(colls)} collective ops for {n_leaves} param leaves "
        f"(+ loss + 2 health scalars)")


def test_round_collective_bytes_tau_invariant(tau2):
    colls2, param_bytes, _ = tau2
    colls4, _, _ = _round_collectives(4)
    assert sum(b for _, b in colls2) == sum(b for _, b in colls4), (
        "collective bytes grew with tau — averaging has become per-step")


#: the float32 parameters of the full CaffeNet (crop 227, 1,000 classes):
#: what the boundary average moves a round, the "243.9 MB" every scaling
#: number in the perf records is worked out from
FULL_CAFFENET_PARAM_BYTES = 243_860_896


def test_perf_md_documents_the_measured_bytes(tau2):
    """The per-round volume the ICI model is written for, pinned to the
    program alone: the compiled round all-reduces ONE copy of its parameters
    (to the byte, beside the loss and health scalars), and the full-size net's
    parameters -- counted from the zoo's spec, no weight drawn -- are the
    pinned 243,860,896 bytes. A document that quotes another number is
    wrong; no document is read here."""
    colls, param_bytes, _ = tau2
    assert 0 < sum(b for _, b in colls) - param_bytes <= 256
    net = CompiledNet.compile(caffenet(batch=4, crop=227, n_classes=1000))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    full_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in jax.tree.leaves(shapes))
    assert full_bytes == FULL_CAFFENET_PARAM_BYTES
    assert f"{full_bytes / 1e6:.1f} MB" == "243.9 MB"


def _tp_round_collectives(tau: int = 2, dp: int = 4, tp: int = 2):
    """Compile the DP×TP hybrid round on TINY_MLP shapes and parse its
    collectives. ip1 (num_output 16) and ip2 (4) are both divisible by
    tp=2, so both are column-sharded; conv-free, so every all-gather in
    the program is the TP feature gather."""
    from tiny_nets import TINY_MLP
    from sparknet_tpu import net_from_prototxt

    net = CompiledNet.compile(net_from_prototxt(TINY_MLP))
    mesh = make_mesh(dp * tp, axis_names=("data", "model"),
                     shape=(dp, tp))
    trainer = ParallelTrainer(
        net, SolverConfig(base_lr=0.01, momentum=0.9, lr_policy="fixed"),
        mesh, tau=tau)
    r = np.random.default_rng(0)
    b = 4
    batches = {
        "data": r.standard_normal((tau, dp * b, 6)).astype(np.float32),
        "label": r.integers(0, 4, (tau, dp * b, 1)).astype(np.int32)}
    sharded = trainer._shard_batches(batches)
    rngs = place_global_state(
        jax.random.split(jax.random.PRNGKey(1), dp),
        trainer.mesh, P(DATA_AXIS))
    import jax.numpy as jnp
    hlo = trainer._round.lower(
        trainer.init_state(jax.random.PRNGKey(0)), sharded,
        rngs, jnp.asarray(1.0, jnp.float32)).compile().as_text()
    params = net.init_params(jax.random.PRNGKey(0))
    per_replica_param_bytes = sum(
        l.nbytes for l in jax.tree.leaves(params))
    return _collective_lines(hlo), per_replica_param_bytes


@pytest.fixture(scope="module")
def tp_tau2():
    return _tp_round_collectives(tau=2)


def test_tp_round_collective_kinds_and_weight_bytes(tp_tau2):
    """The DP×TP hybrid round's wire traffic, pinned: the weight-average
    all-reduce stays ONE param copy per round — but a LOGICAL copy, i.e.
    column-sharded layers contribute 1/tp each per model rank (shard
    identity is preserved across the data-axis pmean; a full-size
    all-reduce here would mean shards were being summed together — the
    r3 bug class this guards). TP additionally puts all-gathers on the
    wire (the Megatron feature gather + its transpose), which the DP-only
    test asserts are ABSENT; their per-activation bytes scale with
    batch×features, pinned loosely here (presence + τ-scaling) since
    XLA may fuse them."""
    tp = 2
    colls, full_param_bytes = tp_tau2
    kinds = {k for k, _ in colls}
    assert "all-reduce" in kinds, kinds
    assert "all-gather" in kinds, (
        f"TP round emitted no all-gather — column sharding is not "
        f"actually sharded? kinds={kinds}")
    ar_bytes = sum(b for k, b in colls if k == "all-reduce")
    # sharded-layer params (here: ALL layers are TP-shardable InnerProducts)
    # cross the wire as 1/tp each; only small HEALTH/LOSS riders come
    # along — three f32 scalars (loss, grad_norm, nonfinite), each
    # psum'd over data AND vma-cleared over the model axis (2 legs), plus
    # the [n_data + 1] attribution-plus-authority vector on the same two
    # legs: 6×4 + 2×4×(n_data+1) bytes, computed exactly so the slack
    # stays tight — at these ~360-byte shapes a single layer's
    # shards-summed regression is ~130 bytes and a blanket slack would
    # mask exactly the bug class this pins.
    n_data = 4  # dp in _tp_round_collectives
    riders = 6 * 4 + 2 * 4 * (n_data + 1)
    logical = full_param_bytes / tp
    assert logical <= ar_bytes <= logical + riders + 8, (
        f"weight-average all-reduce moved {ar_bytes} bytes; expected "
        f"~{int(logical)} (one LOGICAL copy: full {full_param_bytes} / "
        f"tp {tp}) + {riders} rider bytes")


def test_tp_round_allgather_bytes_tau_scale(tp_tau2):
    """The TP feature gathers happen INSIDE every local step, so their
    bytes scale ~linearly with τ (unlike the weight all-reduce, pinned
    τ-invariant above) — τ=4 must carry ~2x the all-gather bytes of τ=2,
    and the all-reduce must not grow."""
    c2, _ = tp_tau2
    c4, _ = _tp_round_collectives(tau=4)
    ag2 = sum(b for k, b in c2 if k == "all-gather")
    ag4 = sum(b for k, b in c4 if k == "all-gather")
    assert ag2 > 0 and 1.8 * ag2 <= ag4 <= 2.2 * ag2, (ag2, ag4)
    ar2 = sum(b for k, b in c2 if k == "all-reduce")
    ar4 = sum(b for k, b in c4 if k == "all-reduce")
    assert ar2 == ar4, (ar2, ar4)
