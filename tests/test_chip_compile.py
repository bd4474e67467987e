"""Compiles for a DESCRIBED TPU v5e — nothing attached, nothing executed.

The interpret-mode kernel tests cannot see what the chip's compiler refuses:
the f32 N-minor LRN backward at norm2's shape passed every one of them and
was then refused for 17.68 MB of scoped VMEM against a 16 MiB limit. libtpu's
compiler is installed wherever jax[tpu] is, and compiles for a topology that
is only described (`on-chip-measurement` guide §2.3), so the main path's
kernels at CaffeNet's real shapes are compiled here on every tier-1 run, a
second or two each, at no chip time.

The whole-program cases (the τ-averaging round on one chip and on four under
both trainer implementations, the eval program, the serve forward at each
bucket) take ~25 s each and are the rehearsal a builder runs before a chip
call: `pytest tests/test_chip_compile.py -m slow`.

Code that asks `jax.default_backend()` sees the CPU here and would take its
CPU branch (portable LRN, checked shard_map, unrolled τ scan), so the
whole-program cases steer it from the test; the kernel cases call the kernels
directly.
"""
import collections
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from sparknet_tpu import precision
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.ops.pallas_lrn import lrn_pallas
from sparknet_tpu.parallel import ParallelTrainer, ShardedTrainer, make_mesh
from sparknet_tpu.parallel.mesh import DATA_AXIS, place_global_state
from sparknet_tpu.parallel.trainer import TrainState
from sparknet_tpu.solver import SolverConfig
from sparknet_tpu.zoo import caffenet

BATCH, CROP, CLASSES, TAU = 256, 227, 1000, 5
NORM1 = (BATCH, 27, 27, 96)     # pool1 -> norm1
NORM2 = (BATCH, 13, 13, 256)    # pool2 -> norm2


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e:2x2 host. The persistent compile
    cache is off around these compiles: an executable built for a described
    device is written to it but cannot be read back without a chip, and the
    next run would warn on every entry."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this environment
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def _lrn_sum(x):
    return lrn_pallas(x).astype(jnp.float32).sum()


KERNEL_CASES = [
    # the N-minor kernel (batch a multiple of 128 lanes): the training path
    ("lrn-fwd-norm1-bf16", lrn_pallas, NORM1, jnp.bfloat16),
    ("lrn-fwd-norm1-f32", lrn_pallas, NORM1, jnp.float32),
    ("lrn-fwd-norm2-bf16", lrn_pallas, NORM2, jnp.bfloat16),
    ("lrn-fwd-norm2-f32", lrn_pallas, NORM2, jnp.float32),
    ("lrn-grad-norm1-bf16", jax.grad(_lrn_sum), NORM1, jnp.bfloat16),
    ("lrn-grad-norm1-f32", jax.grad(_lrn_sum), NORM1, jnp.float32),
    ("lrn-grad-norm2-bf16", jax.grad(_lrn_sum), NORM2, jnp.bfloat16),
    # the case the compiler refused before _nmin_vmem_limit stated the need
    ("lrn-grad-norm2-f32", jax.grad(_lrn_sum), NORM2, jnp.float32),
    # the rows kernel: what a serve bucket of 8 runs at norm1 and norm2
    ("lrn-rows-norm1-b8", lrn_pallas, (8,) + NORM1[1:], jnp.float32),
    ("lrn-rows-norm2-b8", lrn_pallas, (8,) + NORM2[1:], jnp.float32),
]


@pytest.mark.parametrize("fn,shape,dtype",
                         [c[1:] for c in KERNEL_CASES],
                         ids=[c[0] for c in KERNEL_CASES])
def test_kernel_compiles_for_v5e(v5e, fn, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype,
                             sharding=SingleDeviceSharding(v5e[0]))
    # the kernel, not a portable lowering
    assert "tpu_custom_call" in _compiled_text(fn, x)


def _delta_operands(dtype, grad: bool):
    """The chunk stage's forward kernel, or (the cotangents ones) its
    backward kernel alone."""
    from sparknet_tpu.ops.pallas_delta_rule import chunk_operands
    total = lambda *a: sum(jnp.sum(o.astype(jnp.float32))
                           for o in chunk_operands(*a, jnp.dtype(dtype), False))
    return jax.grad(total, argnums=(0, 1, 2, 3, 4)) if grad else total


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_delta_rule_kernels_compile_for_v5e(v5e, grad, dtype):
    """`ops.pallas_delta_rule`'s pair at the linear-attention cell's shape (a
    row's 32 heads, 8,192 positions, 128 a head: 4,096 chunks, a program
    eight tiles of two): Mosaic takes every op of both bodies, and their
    blocks and temporaries fit the scoped VMEM the call states (float32
    blocks are twice the size: the backward asked 16.16 MB of the default
    16 MiB before `_specs` stated the need)."""
    one = SingleDeviceSharding(v5e[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    x = s((32, 8192, 128), dtype)
    text = _compiled_text(_delta_operands(dtype, grad), x, x, x,
                          s((32, 8192, 128), jnp.float32), s((32, 8192), jnp.float32))
    assert text.count("tpu_custom_call") == 1
    assert ("delta_chunk_bwd" if grad else "delta_chunk_fwd") in text


def _delta_scan(dtype, grad: bool):
    """The walk over chunks: its forward kernel, or its backward kernel from
    the segments' states and the result's cotangent."""
    from sparknet_tpu.ops import pallas_delta_scan as ps
    from sparknet_tpu.ops.delta_rule import SEGMENT
    kw = dict(seg=SEGMENT, dtype=jnp.dtype(dtype), interpret=False)
    if grad:
        return lambda ops, states, d_o: ps._backward(ops, states, d_o, **kw)
    return lambda ops, states, d_o: ps._forward(ops, **kw)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_delta_scan_kernels_compile_for_v5e(v5e, grad, dtype):
    """`ops.pallas_delta_scan`'s pair at the linear-attention cell's shape (a
    row's 32 heads and 128 chunks, 128 a head: a grid of 8 blocks of heads x
    16 segments, a program eight chunks of four heads with their float32
    states [4, 128, 128] in a scratch): Mosaic takes every op of both bodies
    -- the products with a transposed left operand and those that contract
    over a chunk's 64 positions among them --, and the blocks, the scratches
    and the bodies' temporaries fit the scoped VMEM the calls state (the
    default 16 MiB forward; the backward's blocks, in and out, pass it)."""
    one = SingleDeviceSharding(v5e[0])
    s = lambda dt, *shape: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    ops = tuple(s(dt, 128, 32, *tail) for dt, tail in (
        (dtype, (64, 128)), (jnp.float32, (64, 128)), (dtype, (64, 128)),
        (jnp.float32, (1, 128)), (dtype, (64, 128)), (dtype, (64, 64))))
    text = _compiled_text(_delta_scan(dtype, grad), ops,
                          s(jnp.float32, 16, 32, 128, 128), s(jnp.float32, 32, 8192, 128))
    assert text.count("tpu_custom_call") == 1
    assert ("delta_scan_bwd" if grad else "delta_scan_fwd") in text


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_kda_shape_kernels_compile_for_v5e(v5e, grad, dtype):
    """`ops.pallas_kda_shape`'s pair at the linear-attention cell's shape (a
    row's 32 heads, 8,192 positions, 128 a head: a grid of 32 x 16 tiles of
    512 positions with their halo blocks): Mosaic takes every op of both
    bodies (the sublane rotations of 136 and 144 rows, the selects on the
    program's index) and their blocks fit the scoped VMEM the calls state."""
    from sparknet_tpu.ops.pallas_kda_shape import param_rows, shape_kernels
    one = SingleDeviceSharding(v5e[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    x = s((1, 32, 8192, 128), dtype)
    total = lambda *a: sum(jnp.sum(o.astype(jnp.float32))
                           for o in shape_kernels(*a, 4, -5.0, False))
    fn = jax.grad(total, argnums=(0, 1, 2, 3, 4)) if grad else total
    text = _compiled_text(fn, x, x, x, x, s((32, param_rows(4), 128), jnp.float32))
    assert text.count("tpu_custom_call") == 1
    assert ("kda_shape_bwd" if grad else "kda_shape_fwd") in text


#: the scan kernels' shapes: (rows, positions, groups, heads a group, chunk,
#: document runs): the state-space cell's, and the packed cell's (one group
#: of 64 heads worked in eight blocks of eight, chunks of 256, rows that
#: hold several documents)
SSD_KERNEL_SHAPES = {"nemotron": (2, 8192, 2, 16, 128, False),
                     "granite-packed": (1, 16384, 1, 64, 256, True)}


@pytest.mark.parametrize("shape", sorted(SSD_KERNEL_SHAPES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_ssd_kernels_compile_for_v5e(v5e, grad, dtype, shape):
    """`ops.pallas_ssd`'s pair at the state-space cell's shape (2 rows of
    8,192 positions, 32 heads of 64 in 2 groups, state 128: a grid of 2 x 2
    x 64 chunks, sixteen heads -- eight lane tiles -- a program, the float32
    state [8, 128, 128] in a scratch) and at the packed cell's (1 row of
    16,384, 64 heads in ONE group, chunks of 256: a grid of 1 x 8 x 64, the
    group's heads in eight blocks of eight, [256, 256] squares, the
    document runs as a row and a column of int32): Mosaic takes every op of
    both bodies (the lane and sublane selects, the [Q, 1] columns spread
    over lanes, the reversed walk's index map, a block's `g // 8` map onto
    its group's B and C), and blocks, scratch and temporaries fit the 16 MiB
    of scoped VMEM a call has unasked, float32 operands too."""
    from sparknet_tpu.ops import ssd as ssd_ops
    from sparknet_tpu.ops.pallas_ssd import ssd_chunks
    r, n, groups, per, q, docs = SSD_KERNEL_SHAPES[shape]
    at_once = ssd_ops.program_heads(q, per, 64, 128)
    assert at_once == 16 * 128 // q
    programs = groups * per // at_once
    one = SingleDeviceSharding(v5e[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    cuts = (s((r, n // q, 1, 1, q), jnp.int32),
            s((r, n // q, 1, q, 1), jnp.int32)) if docs else ()
    total = lambda *a: jnp.sum(ssd_chunks(*a[:7], tuple(a[7:]), 64, 128,
                                          jnp.dtype(dtype), False))
    fn = jax.grad(total, argnums=tuple(range(7))) if grad else total
    rows = s((r, n // q, programs, at_once, q), jnp.float32)
    cols = s((r, n // q, programs, q, at_once), jnp.float32)
    text = _compiled_text(fn, s((r, n, groups * per * 64), dtype),
                          s((r, n, groups * 128), dtype),
                          s((r, n, groups * 128), dtype), rows, rows, cols, cols,
                          *cuts)
    # the gradient is the forward with its chunk states, then the backward
    assert text.count("tpu_custom_call") == (2 if grad else 1)
    assert "ssd_chunk_fwd" in text and ("ssd_chunk_bwd" in text) == grad


def test_bf16_row_block_is_the_profiled_one():
    """PERF.md's LRN profile is of the bf16 kernel at these blocks; the f32
    repair states a VMEM need and must never move them."""
    from sparknet_tpu.ops.pallas_lrn import (_DEFAULT_SCOPED_VMEM,
                                             _nmin_vmem_limit, _row_block)
    assert _row_block(27 * 27) == 27 and _row_block(13 * 13) == 13
    # bf16 stays inside the compiler's default allowance (the kernel is
    # called exactly as before); f32 at norm2 states more than it
    assert _nmin_vmem_limit(13, 256, 2, 3) == _DEFAULT_SCOPED_VMEM
    assert _nmin_vmem_limit(27, 96, 2, 3) == _DEFAULT_SCOPED_VMEM
    assert _nmin_vmem_limit(13, 256, 4, 3) > 17.68e6


# -- whole programs (rehearsal before a chip call; ~25 s each) ---------------

@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the `jax.default_backend()` askers (ops/lrn's `pallas_backend`,
    which the trainer's may_pallas asks too; seq_layers; mesh.scan_unroll)
    down their TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_ssd_block_compiles_for_v5e_and_walks_its_chunks_in_the_kernels(v5e, as_tpu):
    """One Mamba-2 mixer at the state-space cell's shape (2 rows, 8,192
    positions, hidden 4,096, 32 held heads of 64 in 2 groups, state 128) in a
    recomputation block, forward and backward under the bfloat16 policy, for
    a v5e (~10 s): the scan is `ops.pallas_ssd`'s kernels (forward in the
    block, forward again with its chunk states for the backward, backward:
    three calls under `ssd`, `kernel_calls` of the report) and NO device loop
    -- the walk over the 64 chunks is the kernels' grid --, no chunk's [128,
    128] squares and no chunk's own contribution to the state exist outside
    them (61 and 6 such float32 arrays in the `jnp` form's text), and the
    block's temporaries are 1.50 GB (2.10 with the scan in `jnp`)."""
    from sparknet_tpu.model import seq_layers as sl
    from sparknet_tpu.model.spec import LayerSpec, Mamba2Param
    from sparknet_tpu.obs import device as obs_device
    one = SingleDeviceSharding(v5e[0])
    p = Mamba2Param(num_heads=128, head_dim=64, n_groups=8, state_size=128, taps=4,
                    chunk_size=128, heads_held=(0, 32), groups_held=(0, 2))
    layer = LayerSpec(name="m", type="Mamba2", mamba2=p)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda k: sl.init_mamba2(k, layer, ((2, 8192, 4096),)),
                       jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16, sharding=one)

    def loss(params, x):
        with jax.named_scope("tau_step"), jax.named_scope("Mamba2/l0_mamba"):
            y = jax.checkpoint(lambda params, x: sl.mamba2(p, params, x, _seq_ctx()))(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    try:
        precision.set_policy("bfloat16")
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    finally:
        precision.set_policy("float32")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.8e9, f"the block's temporaries are {temp / 1e9:.2f} GB"
    text = compiled.as_text()
    ops = obs_device.parse_hlo_ops(text)
    got = obs_device.ssm(ops, sl.SSD_SCOPES)
    assert got["layers"] == 1 and got["kernel_calls"] == 3, got
    assert (got["loops"], got["trips"], got["carried_bytes"]) == (0, 0, 0), got
    kernels = sorted(name.split(".")[0] for name, op in ops.items() if op.get("pallas"))
    assert kernels == ["%ssd_chunk_bwd", "%ssd_chunk_fwd", "%ssd_chunk_fwd"], kernels
    assert "f32[2,64,32,128,128]" not in text and "f32[2,64,32,64,128]" not in text
    # the chunk states the backward reads: one float32 [.., 2048, 128] a chunk
    assert "f32[2,64,2,1024,128]" in text


def _caffenet(batch=BATCH):
    return CompiledNet.compile(caffenet(batch=batch, crop=CROP,
                                        n_classes=CLASSES))


def _trainer(cls, devices, mesh=None, tau=TAU, **kw):
    mesh = mesh or Mesh(np.array(devices), (DATA_AXIS,))
    return cls(_caffenet(), SolverConfig(
        base_lr=0.01, momentum=0.9, weight_decay=5e-4, lr_policy="step",
        gamma=0.1, stepsize=100000), mesh, tau=tau, donate_batches=True,
        fused_boundary=True, **kw)


def _state_avals(trainer):
    """The trainer's TrainState as ShapeDtypeStructs on its own mesh — no
    array can be put on a described device."""
    n, mesh = trainer.n_devices, trainer.mesh
    logical = jax.eval_shape(trainer.net.init_params, jax.random.PRNGKey(0))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    if trainer.state_layout == "replica":
        tp_layers = trainer._tp_sharded_layers()

        def row(lname, pname, l):
            shape = list(l.shape)
            if lname in tp_layers:  # this device's column shard
                shape[1 if pname == "w" else 0] //= trainer.tp
            return sds((n, *shape), l.dtype, trainer._dev_spec)

        rows = {ln: {pn: row(ln, pn, l) for pn, l in lp.items()}
                for ln, lp in logical.items()}
        return TrainState(params=rows, momentum=rows,
                          it=sds((n,), jnp.int32, trainer._dev_spec))
    store = trainer._store_shardings()
    return TrainState(
        params=jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            logical, store.params),
        momentum=jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype,
                                              sharding=s),
            logical, store.momentum),
        it=jax.ShapeDtypeStruct((), jnp.int32, sharding=store.it))


def _round_avals(trainer, compute_dt):
    n, mesh = trainer.n_data, trainer.mesh
    batch = NamedSharding(mesh, P(None, DATA_AXIS))
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n))
    return (_state_avals(trainer),
            {"data": jax.ShapeDtypeStruct(
                (trainer.tau, n * BATCH, CROP, CROP, 3), compute_dt,
                sharding=batch),
             "label": jax.ShapeDtypeStruct((trainer.tau, n * BATCH, 1),
                                           jnp.int32, sharding=batch)},
            jax.ShapeDtypeStruct(key.shape, key.dtype,
                                 sharding=NamedSharding(mesh, P(DATA_AXIS))),
            jax.ShapeDtypeStruct((), jnp.float32,
                                 sharding=NamedSharding(mesh, P())))


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bfloat16", "float32"])
@pytest.mark.parametrize("n_chips", [1, 4])
@pytest.mark.parametrize("cls", [ParallelTrainer, ShardedTrainer],
                         ids=["shard_map", "named"])
def test_caffenet_round_compiles_for_v5e(v5e, as_tpu, cls, n_chips, policy):
    """The round `train()` runs under the ImageNet app's recipe: batch 256 a
    chip, τ=5, health on, donated batches, fused boundary."""
    precision.set_policy(policy)
    trainer = _trainer(cls, v5e[:n_chips])
    compiled = trainer._round.lower(
        *_round_avals(trainer, precision.compute_dtype())).compile()
    text = compiled.as_text()
    # norm1 + norm2, forward + backward, in the scanned body and the peeled
    # final step
    assert text.count("tpu_custom_call") >= 4
    assert ("all-reduce" in text) == (n_chips > 1)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"


@pytest.mark.slow
def test_caffenet_tau50_round_reads_its_rows_in_place(v5e, as_tpu):
    """The benchmark's round (`caffenet-tau50`: bf16, batch 256, τ=50,
    donated, fused boundary, health off) holds no copy of its stack: the
    peeled last step used to slice the other 49 steps' rows out of it
    (`slice` of bf16[49,256,227,227,3], 3.96 GB of 4.79 GB of temporaries)."""
    precision.set_policy("bfloat16")
    tau = 50
    trainer = _trainer(ParallelTrainer, v5e[:1], tau=tau,
                       compute_health=False)
    compiled = trainer._round.lower(
        *_round_avals(trainer, jnp.bfloat16)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9, f"round temporaries {temp / 1e9:.2f} GB"
    assert f"bf16[{tau - 1},{BATCH},{CROP},{CROP},3]" not in compiled.as_text()


@pytest.mark.parametrize("cls", [ParallelTrainer, ShardedTrainer],
                         ids=["shard_map", "named"])
@pytest.mark.parametrize("fused", [True, False], ids=["peeled", "scanned"])
def test_round_never_slices_its_stack_along_tau(as_tpu, cls, fused):
    """Tier-1 size of the pin above, from the lowered text of a rolled
    (TPU-branch) round on the CPU backend: the only reads of the [τ, ...]
    stack are one step's rows at a time, so no op anywhere produces a
    [τ-1, ...] array of the batch's trailing shape."""
    from tiny_nets import TINY_MLP
    from sparknet_tpu import net_from_prototxt

    tau, n, local_b = 4, 2, 8
    trainer = cls(CompiledNet.compile(net_from_prototxt(TINY_MLP)),
                  SolverConfig(base_lr=0.01, momentum=0.9,
                               lr_policy="fixed"),
                  make_mesh(n), tau=tau, fused_boundary=fused)
    batches = trainer._shard_batches({
        "data": np.zeros((tau, n * local_b, 6), np.float32),
        "label": np.zeros((tau, n * local_b, 1), np.int32)})
    rngs = place_global_state(jax.random.split(jax.random.PRNGKey(0), n),
                              trainer.mesh, P(DATA_AXIS))
    text = trainer._round.lower(trainer.init_state(jax.random.PRNGKey(1)),
                                batches, rngs, jnp.float32(1.0)).as_text()
    assert "stablehlo.while" in text  # the scan is rolled, as on the chip
    # one step's rows are read ([1, b, 6] before the squeeze) ...
    assert re.search(rf"tensor<1x{local_b}x6xf32>", text)
    # ... and never all-but-one step's
    assert not re.search(rf"tensor<{tau - 1}x\d+x(6xf32|1xi32)>", text), (
        "the round slices its stack along tau")


@pytest.mark.slow
def test_caffenet_data2_model2_round_compiles_for_v5e(v5e, as_tpu):
    """The DPxTP round of `chip_smoke.py --four-chips`: fc layers
    column-sharded over the model axis of a (data=2, model=2) mesh."""
    from sparknet_tpu.parallel.mesh import MODEL_AXIS
    precision.set_policy("bfloat16")
    trainer = _trainer(ParallelTrainer, None, mesh=Mesh(
        np.array(v5e).reshape(2, 2), (DATA_AXIS, MODEL_AXIS)))
    assert trainer.tp == 2
    text = trainer._round.lower(
        *_round_avals(trainer, jnp.bfloat16)).compile().as_text()
    assert text.count("tpu_custom_call") >= 4
    assert "all-gather" in text and "all-reduce" in text


@pytest.mark.slow
def test_caffenet_eval_compiles_for_v5e(v5e, as_tpu):
    precision.set_policy("bfloat16")
    trainer = _trainer(ParallelTrainer, v5e[:1])
    sh = NamedSharding(trainer.mesh, P(DATA_AXIS))
    batch = {"data": jax.ShapeDtypeStruct((BATCH, CROP, CROP, 3),
                                          jnp.bfloat16, sharding=sh),
             "label": jax.ShapeDtypeStruct((BATCH, 1), jnp.int32,
                                           sharding=sh)}
    text = trainer._eval.lower(_state_avals(trainer).params,
                               batch).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # norm1, norm2 forward


@pytest.mark.slow
@pytest.mark.parametrize("bucket", [1, 8])
def test_caffenet_serve_forward_compiles_for_v5e(v5e, as_tpu, bucket):
    """The forward `InferenceServer` runs per bucket (JaxNet._fwd_test)."""
    from sparknet_tpu.net_api import JaxNet
    one = SingleDeviceSharding(v5e[0])
    net = JaxNet(caffenet(batch=bucket, crop=CROP, n_classes=CLASSES))
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(net.net.init_params, jax.random.PRNGKey(0)))
    batch = {"data": jax.ShapeDtypeStruct((bucket, CROP, CROP, 3),
                                          jnp.float32, sharding=one),
             "label": jax.ShapeDtypeStruct((bucket, 1), jnp.int32,
                                           sharding=one)}
    text = net._fwd_test.lower(params, batch, None).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # the rows kernel, twice


# -- the sequence layers' kernels, and the sequence model's round ------------

def _seq_ctx():
    from sparknet_tpu.model.layers import ApplyCtx
    return ApplyCtx(train=True)


def test_attention_core_backward_keeps_nothing_of_size_positions_squared(v5e, as_tpu):
    """The latent-attention core at the benchmark's shape (2 rows, 20 heads,
    8,192 positions, 256 + 256 a head, heads first as `mla` hands them over),
    forward and backward, for a v5e: jax's splash-attention kernels, and no
    [.., 8192, 8192] tensor anywhere (one layer's float32 scores would be
    10.7 GB)."""
    from sparknet_tpu.model import seq_layers as sl
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((2, 20, 8192, 256), jnp.bfloat16, sharding=one)
    text = _compiled_text(jax.grad(
        lambda q, k, v: sl.attention_core(q, k, v, _seq_ctx()).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") >= 2 and "splash_mha" in text
    assert "8192,8192" not in text
    # the operands are the kernel's as they come: none is laid out again
    assert not re.search(r"\[2,20,8192,256\]\S* (transpose|copy)\(", text)


def test_grouped_core_at_head_width_64_runs_as_a_kernel(v5e, as_tpu):
    """The grouped-query core at the hybrid cell's shape (2 rows, 32 query
    heads over 8 key/value heads, 8,192 positions, 64 a head), forward and
    backward, for a v5e: the same kernels, the grouping done inside them (no
    key or value is written out once a query head), and no [.., 8192, 8192]
    tensor (one layer's float32 scores would be 17 GB)."""
    from sparknet_tpu.model import seq_layers as sl
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16, sharding=one)
    text = _compiled_text(jax.grad(
        lambda q, k, v: sl.attention_core(q, k, v, _seq_ctx()).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 2 and "splash_mha" in text
    assert "8192,8192" not in text
    # the kernels take the 8 key/value heads as they are
    assert re.search(r"custom-call\([^)]*\), custom_call_target=\"tpu_custom_call\"",
                     text) and "bf16[2,8,8192,64]" in text


def test_delta_rule_block_compiles_for_v5e_and_fits(v5e, as_tpu):
    """One Kimi Delta Attention layer at the linear-attention cell's shape (2
    rows, 8,192 positions, hidden 2,560, 32 heads of 128) in a recomputation
    block, forward and backward under the bfloat16 policy, for a v5e (~20 s):
    the chunk stage runs as `ops.pallas_delta_rule`'s kernels and the walk
    over chunks as `ops.pallas_delta_scan`'s (forward in the block, forward
    again under the row's checkpoint, backward: six calls under `delta`,
    `kernel_calls` of the report); of the seven device loops the `lax.scan`
    was (forward, made again by the row, and backward with the segment's
    chunks made again) TWO are left, the backward pass's own walk of two
    products a chunk for the state every segment started from (16 segments x
    8 chunks: `ops.delta_rule.segment_states`; a loop nest in the rows'
    backward body is what keeps the compiler's assignment of the whole
    round where the parent's was: PERF.md section 6, PR 50); its temporaries
    stay under the 2.14 GB they were with the scan a loop (1.98; 1.88 with
    the states the forward kernel's second output; 2.43 with the chunk stage
    in `jnp`, 4.4 with both rows at once, 10.2 before the operands were made
    a segment at a time: PERF.md section 6, PR 33 and 37), and nothing
    under `delta` is a gather or a scatter (an index with two integers a
    slice apart is one, and a TPU runs it as a loop)."""
    from sparknet_tpu.model import seq_layers as sl
    from sparknet_tpu.model.spec import KDAttentionParam, LayerSpec
    from sparknet_tpu.obs import device as obs_device
    one = SingleDeviceSharding(v5e[0])
    p = KDAttentionParam(num_heads=32, head_dim=128, taps=4, lower_bound=-5.0, eps=1e-6)
    layer = LayerSpec(name="k", type="KDAttention", kda=p)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda k: sl.init_kdattention(k, layer, ((2, 8192, 2560),)),
                       jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((2, 8192, 2560), jnp.bfloat16, sharding=one)

    def loss(params, x):
        with jax.named_scope("tau_step"), jax.named_scope("KDAttention/l0_kda"):
            y = jax.checkpoint(lambda params, x: sl.kda(p, params, x, _seq_ctx()))(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    try:
        precision.set_policy("bfloat16")
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    finally:
        precision.set_policy("float32")
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"the block's temporaries: {temp / 1e9:.3f} GB")
    assert temp < 2.15e9, f"the block's temporaries are {temp / 1e9:.2f} GB"
    text = compiled.as_text()
    ops = obs_device.parse_hlo_ops(text)
    got = obs_device.delta_rule(ops, sl.DELTA_SCOPES)
    print(got)
    under_delta = [op for op in ops.values() if op["layer_type"] == "KDAttention"
                   and "delta" in op["scope"].split("/")]
    # forward in the block, forward again by the row, backward: a row loop
    # each, the chunk stage's kernel and the walk's
    assert got["kernel_calls"] == sum(op.get("pallas", False) for op in under_delta) == 6, got
    assert "delta_chunk_fwd" in text and "delta_chunk_bwd" in text
    assert "delta_scan_fwd" in text and "delta_scan_bwd" in text
    # the stage before the rule likewise, under a scope of its own: what
    # shapes q, k, v and the decay is `ops.pallas_kda_shape`'s pair
    assert got["shape_kernel_calls"] == 3, got
    assert "kda_shape_fwd" in text and "kda_shape_bwd" in text
    # a row's walk over its 16 segments of 8 chunks is the kernels' grid
    # forward, made again and backward; the one scan of scans left is the
    # backward pass's walk for the segments' states
    assert (got["loops"], got["trips"]) == (2, 16 + 8), got
    assert got["carried_bytes"] >= 32 * 128 * 128 * 4  # a row's float32 states
    # the chunk stage's elementwise passes and the scan's stacks are gone
    # from the program: what is left under `delta` beside the kernels and
    # that walk moves a few arrays
    assert got["instructions"] < 60, got
    assert under_delta and not any(op.get("indexed") for op in under_delta)


def test_attention_block_lays_out_nothing_between_projection_and_core(v5e, as_tpu):
    """One attention block at GLM-4.7-Flash's widths and the benchmark's
    shape (norm, latent attention, residual sum: a recomputation block that
    keeps the core's names), forward + backward, for a v5e, ~15 s: q, k and v
    leave their projections heads first, so no gather or scatter touches an
    activation (twelve and four did, from the rotary turn's strided slices),
    v goes from its matmul into the forward kernel with nothing between, and
    the block accesses under 19 GB (26.3 before the layout moved into the
    weights, 15.7 after)."""
    from model_cases import attention_block
    from sparknet_tpu.model.spec import MLAttentionParam
    from sparknet_tpu.obs.device import attention_moves, parse_hlo_ops
    net, params, x, loss = attention_block(MLAttentionParam(
        num_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6, eps=1e-5),
        positions=8192, d=2048)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda l, dtype=None: jax.ShapeDtypeStruct(
        l.shape, dtype or l.dtype, sharding=one)
    precision.set_policy("bfloat16")
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree.map(on_chip, params), on_chip(x, jnp.bfloat16)).compile()
    finally:
        precision.set_policy("float32")
    text = compiled.as_text()
    ops = parse_hlo_ops(text)
    moves = attention_moves(ops, *net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves
    assert 0 < moves["bytes"] < 10e9, moves  # 17.5 GB with the strided slices
    fwd = re.search(r"(%splash_mha_fwd_residuals[\w.]*) = .*? custom-call\(([^)]*)\)",
                    text)
    assert "transpose(" not in ops[fwd.group(1)]["scope"]  # the forward's own
    straight = [o for o in re.findall(r"%[\w.\-]+", fwd.group(2))
                if ops[o]["matmul"]]
    assert len(straight) == 1, f"v passes through something: {fwd.group(2)}"
    accessed = compiled.cost_analysis()["bytes accessed"]
    assert accessed < 19e9, f"the block accesses {accessed / 1e9:.2f} GB"


@pytest.mark.parametrize("rows,width", [(16384, 1536), (32768, 1792)])
def test_grouped_expert_products_compile_for_v5e(v5e, as_tpu, rows, width):
    """The experts' grouped matmul at the benchmark's shapes (a 16,384-row
    buffer, 8 held experts of 2048 x 1536; a 32,768-row one, 8 of 2048 x
    1,792, which the 512-wide tiles overhang), forward and both gradients: megablox's
    kernels, so only the rows routed to an expert meet it."""
    from sparknet_tpu.model import seq_layers as sl
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((8, 2048, width), jnp.float32, sharding=one)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)
    precision.set_policy("bfloat16")
    try:
        text = _compiled_text(jax.grad(
            lambda x, w, s: sl._grouped_dot(x, w, s, _seq_ctx()).astype(
                jnp.float32).sum(), argnums=(0, 1)), x, w, sizes)
    finally:
        precision.set_policy("float32")
    # the gradient of a sum needs the two backward products alone
    assert text.count("tpu_custom_call") >= 2
    assert "gmm" in text and "tgmm" in text


#: the two cells' expert layers (`zoo.lfm2_moe`, `zoo.glm4_moe_lite` of the
#: benchmark's configurations): 16,384 tokens a step, top 4, width 2,048
_EXPERT_LAYERS = {
    "lfm2": dict(n_routed_experts=32, intermediate_size=1792,
                 n_shared_experts=0, norm_topk_eps=1e-6),
    "glm": dict(n_routed_experts=64, intermediate_size=1536,
                n_shared_experts=1, routed_scaling_factor=1.8)}


def _made_under(text, ops, scopes) -> set:
    """The dimensions of every array a device op under one of `scopes`
    makes (its result, or the first of a tuple's)."""
    made = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+)\[([\d,]*)\]", line)
        if m and m.group(1) in ops and any(
                s in ops[m.group(1)]["scope"].split("/") for s in scopes):
            made.add(tuple(int(n) for n in m.group(3).split(",") if n))
    return made


def _no_scalar_by_slot(ops, scopes, slots: int) -> None:
    """No device op under the routing `scopes` fetches or places `slots`
    (tokens x k) single elements with one gather or scatter."""
    by_slot = [(name, op["scalars"]) for name, op in ops.items()
               if slots in op.get("scalars", ())
               and any(s in op["scope"].split("/") for s in scopes)]
    assert not by_slot, by_slot


def _routing_walks_rows(text, ops, trainer, slot_side_gathers: int,
                        buffer_rows: int, buffer_sums: int = 0, k: int = 4):
    """A compiled round's routing (its layers choose `k` experts a token):
    `routing_moves` of a step body gathers
    3 x the buffer's rows an expert layer and `slot_side_gathers` x tokens x
    k in all, scatter-adds `buffer_sums` x the buffer's rows an expert layer
    (the weighted sums by token that walk the buffer: none where the k
    gathers run), no op under the routing scopes makes an array of tokens
    x k rows of the model's width, and none fetches or places tokens x k
    single scalars (at most 4 x the buffer's rows an expert layer)."""
    from sparknet_tpu.obs.device import routing_moves
    scopes, d = trainer.net.routing_scopes()
    tokens = 2 * 8192
    moves = routing_moves(ops, scopes, d)
    layers = len(trainer.net.counter_blobs())
    assert moves["rows_gathered"] <= (slot_side_gathers * tokens * k
                                      + 3 * layers * buffer_rows), moves
    assert moves["rows_scattered"] == buffer_sums * layers * buffer_rows, moves
    made = _made_under(text, ops, scopes)
    assert not made & {(tokens * k, d), (tokens, k, d)}, made
    assert moves["slot_scalar_moves"] > 0, moves
    assert moves["slot_scalars_moved"] <= 4 * layers * buffer_rows, moves
    _no_scalar_by_slot(ops, scopes, tokens * k)


def _slot_side_pair(sl):
    """The form routing had before its data passes walked the buffer's rows,
    as (rows_of_tokens, sum_by_token) over the same plan: gathers of every
    slot's row (`jnp.take`, which masks out-of-bounds afterwards), reshaped
    [tokens, k, d] and summed over k, and `dw` from a second slot-side gather
    of `y`. Kept here so that `routing_moves` is shown to tell the two apart."""
    def slot_rows(y, plan):
        ys = jnp.where(plan["slot_ok"].reshape(-1, 1),
                       jnp.take(y, plan["slot_row"].reshape(-1), axis=0), 0)
        return ys.astype(jnp.float32).reshape(*plan["slot_ok"].shape, -1)

    @jax.custom_vjp
    def gather_rows(xf, plan):
        return jnp.take(xf, plan["tok"], axis=0)

    def gather_rows_bwd(plan, g):
        return jnp.sum(slot_rows(g, plan), axis=1).astype(g.dtype), None

    gather_rows.defvjp(lambda xf, plan: (gather_rows(xf, plan), plan),
                       gather_rows_bwd)

    @jax.custom_vjp
    def combine(y, w, plan):
        return jnp.sum(slot_rows(y, plan) * w[:, :, None], axis=1).astype(y.dtype)

    def combine_bwd(res, g):
        y, w, plan = res
        w_row = jnp.where(plan["row_ok"],
                          jnp.take(w.reshape(-1), plan["row_slot"]), 0.0)
        dy = (jnp.take(g, plan["tok"], axis=0).astype(jnp.float32)
              * w_row[:, None]).astype(y.dtype)
        dw = jnp.sum(slot_rows(y, plan) * g.astype(jnp.float32)[:, None, :],
                     axis=-1)
        return dy, dw, None

    combine.defvjp(lambda y, w, plan: (combine(y, w, plan), (y, w, plan)),
                   combine_bwd)
    return gather_rows, combine


#: cell -> (top k, the routed width, the buffer's rows) of its expert layer
#: at a step's 16,384 tokens
_ROUTED = {"lfm2": (4, 2048, 32768), "glm": (4, 2048, 16384),
           "ling": (8, 2560, 4096), "nemotron": (22, 1024, 22528)}


@pytest.mark.parametrize("cell,form", [("lfm2", "rows"), ("glm", "rows"),
                                       ("lfm2", "slots"), ("ling", "rows"),
                                       ("nemotron", "rows")])
def test_routing_walks_the_buffers_rows_not_the_steps_slots(
        v5e, as_tpu, monkeypatch, cell, form):
    """The lone expert layer at the four sequence cells' shapes (T = 16,384
    tokens; k = 4, d = 2,048 and a buffer of R = 32,768 rows for
    LFM2-8B-A1B's, 16,384 for GLM-4.7-Flash's; k = 8, d = 2,560, R = 4,096
    for Ling-3.0-flash's; k = 22 in a latent of 1,024, R = 22,528 for
    Nemotron-3-Super's, from the benchmark's own files), forward + backward
    in a recomputation block as the net builds it, for a v5e, ~20 s each: it
    compiles (the grouped products as kernels), and under `router` /
    `dispatch` / `combine` no op makes an array of T x k rows of width d, as
    one [T k, d] or as [T, k, d]. Where the buffer is no short one beside the
    slots (`sum_walks_buffer`: LFM2, GLM) the combine and the dispatch's
    backward fetch T rows k times and add them in one pass, `dw` comes from
    the rows the backward fetches anyway, so `routing_moves` counts 2 T k +
    3 R rows gathered and none scattered. Where it is (Ling: 1 row for 32
    slots; Nemotron: 1 for 16) each of those passes is one scatter-add of the
    buffer's R rows: 3 R rows gathered, and 2 R scattered, 3 R where a
    latent's `latent_up` has the combine made again in the backward. The same
    query on the form the layer had (`_slot_side_pair`) reads 3 T k + 3 R and
    finds those arrays: the counter tells the three apart. And no per-slot
    SCALAR travels by an index over the T k slots (`slot_scalars_moved`: 2 R
    where the k gathers run -- a row's weight fetched in the backward pass,
    its `dw` placed -- and 3 R where the sums walk the buffer, which fetch
    the rows' weights forward too (4 R before PR 52, when the dispatch's
    backward fetched a weight of 1 a landed row by the plan's slot side,
    which that form now reads nowhere: `_plan`'s second sort is made in
    neither pass); 4 T k + R
    and 4 T k + 3 R before the router selected its chosen scores from the
    experts' columns, `seq_layers.chosen_scores`), and that select's [T, k,
    experts] is no array any op under `router` writes."""
    from sparknet_tpu.model import seq_layers as sl
    from sparknet_tpu.model.spec import MoEParam
    from sparknet_tpu.obs.device import parse_hlo_ops, routing_moves
    tokens, (k, width, want_rows) = 16384, _ROUTED[cell]
    if cell in _EXPERT_LAYERS:
        d, p = width, MoEParam(experts_held=(0, 8), num_experts_per_tok=k,
                               capacity_factor=2.0, **_EXPERT_LAYERS[cell])
    else:
        from model_cases import benchmark_expert_layers
        (p, *_), _, d = benchmark_expert_layers({
            "ling": "ling3-flash-ep64-tau4",
            "nemotron": "nemotron3-super-tp4-ep64-tau4"}[cell])
    assert (p.num_experts_per_tok, p.latent_size or d) == (k, width)
    rows, slots = sl.moe_capacity(p, tokens), tokens * k
    assert rows == want_rows
    if form == "slots":
        gather_rows, combine = _slot_side_pair(sl)
        monkeypatch.setattr(sl, "rows_of_tokens", gather_rows)
        monkeypatch.setattr(sl, "sum_by_token", combine)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda l, dtype=None: jax.ShapeDtypeStruct(
        l.shape, dtype or l.dtype, sharding=one)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: sl.init_moe_params(jax.random.PRNGKey(0), p, d)))
    x = on_chip(jax.ShapeDtypeStruct((2, tokens // 2, d), jnp.bfloat16))

    def loss(params, x):
        with jax.named_scope("MoE/lone"):  # as `CompiledNet.apply` opens it
            out = jax.checkpoint(
                lambda pp, xx: sl.moe(p, pp, xx, _seq_ctx())[0],
                policy=jax.checkpoint_policies.save_only_these_names(
                    *sl.KEPT_NAMES["MoE"]))(params, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    precision.set_policy("bfloat16")
    try:
        text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), params, x)
    finally:
        precision.set_policy("float32")
    assert "gmm" in text and "tgmm" in text
    ops = parse_hlo_ops(text)
    moves = routing_moves(ops, sl.ROUTING_SCOPES, width)
    made = _made_under(text, ops, sl.ROUTING_SCOPES)
    by_slot = {(slots, width), (tokens, k, width)}
    if form == "slots":
        assert made & by_slot
        assert moves["rows_gathered"] == 3 * slots + 3 * rows, moves
    else:
        assert not made & by_slot, made & by_slot
        assert f"[{tokens},{k},{width}]" not in text
        # the combine, the dispatch's backward and, under a latent, the
        # combine made again for `latent_up`'s weight gradient
        sums = 3 if p.latent_size else 2
        walks = sl.sum_walks_buffer(rows, tokens, k)
        assert walks == (cell in ("ling", "nemotron"))
        assert moves["row_gathers"] == 3 + (0 if walks else sums * k), moves
        assert moves["rows_gathered"] == 3 * rows + (
            0 if walks else sums * slots), moves
        slabs = -(-width // sl.SCATTER_COLUMNS)  # a scatter-add a slab
        assert moves["row_scatters"] == (sums * slabs if walks else 0), moves
        assert moves["rows_scattered"] == (sums * rows if walks else 0), moves
        # no per-slot scalar travels by an index over the step's slots: a
        # row's weight is fetched (in the backward pass; forward and
        # backward where the sums walk the buffer -- the dispatch's backward
        # weighs every landed row 1 and fetches nothing, since PR 52) and
        # its `dw` placed, R single elements a move, none of T x k
        moved = 3 if walks else 2
        assert moves["slot_scalar_moves"] == moved, moves
        assert moves["slot_scalars_moved"] == moved * rows, moves
        _no_scalar_by_slot(ops, sl.ROUTING_SCOPES, slots)
        # ... and the router's select over the experts' columns stays
        # inside its fusions
        assert not any(math.prod(dims) == slots * p.n_routed_experts
                       for dims in _made_under(text, ops, ("router",)))
    assert moves["instructions"] > 0 and moves["bytes"] > 0
    # the block keeps the routing (`moe_route`): what it makes again under
    # `router` is no product and no sort, under `dispatch` no sort
    again = re.findall(r'op_name="[^"]*rematted_computation/(?:router|dispatch)'
                       r'/(?:dot_general|top_k|sort|argsort)"', text)
    assert not again, again[:2]
    assert re.search(r'op_name="[^"]*rematted_computation/dispatch/', text)


def _sequence_round(v5e, config: str):
    """(compiled, trainer, the round's jaxpr) of a sequence configuration's
    benchmark round (`benchmark/configs/<config>.json`: the published
    widths, 2 x 8,192 tokens a step, tau=4, bf16, donated, fused boundary,
    health off) for one described chip."""
    import json
    from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
    from sparknet_tpu.utils.config import RunConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmark", "configs", config + ".json")
    with open(path) as f:
        c = json.load(f)
    cfg = RunConfig.from_dict({
        "model": path, "tau": c["tau"], "local_batch": c["local_batch"],
        "precision": c["precision"], "solver": c["solver"], "n_devices": 1,
        **c["run_config"]})
    mesh = Mesh(np.array(v5e[:1]), (DATA_AXIS,))
    trainer = build_trainer(cfg, resolve_spec(cfg), mesh)
    try:
        batch = NamedSharding(mesh, P(None, DATA_AXIS))
        key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), 1))
        traced = trainer._round.trace(
            _state_avals(trainer),
            {name: jax.ShapeDtypeStruct(  # `tokens`; `doc_ids` beside them
                (c["tau"], c["local_batch"], c["seq_len"]), jnp.int32,
                sharding=batch) for name in trainer.net.input_shapes},
            jax.ShapeDtypeStruct(key.shape, key.dtype,
                                 sharding=NamedSharding(mesh, P(DATA_AXIS))),
            jax.ShapeDtypeStruct((), jnp.float32,
                                 sharding=NamedSharding(mesh, P())))
        compiled = traced.lower().compile()
    finally:
        precision.set_policy("float32")
    return compiled, trainer, traced.jaxpr.jaxpr


def _round_bytes(compiled) -> int:
    """State + temporaries of a compiled round, printed as read (`-rP`
    shows a passing test's)."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"round: {total / 1e9:.2f} GB, of which temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f}")
    return total


def _products_made_once(text: str, kept, name: str, kind: str, n: int) -> None:
    """The round's `n` products under the kept name `name`, all in layers
    of type `kind`, run once: `n` on a step body's forward path, none on a
    recomputed one, and no instruction of the compiled text, inside a fusion
    or out, comes from a `dot_general` under a recomputed `kind` scope.
    `mlp_pre` / `GatedMLP`: a dense SwiGLU's two input products a layer (the
    down projection was never made twice; 2 a layer and step body made again
    before PR 41). `ip_out` / `InnerProduct`: the heads' logits, the largest
    product of each model, and Nemotron's MTP projection (one a head and
    step body made again before PR 47)."""
    made = kept[name]
    assert made["step_bodies"] == 2, made  # the loop's, the peeled
    assert (made["forward"], made["backward"]) == (n, 0), made
    assert re.search(rf'op_name="[^"]*/{kind}/[^"]*dot_general"', text)
    again = re.findall(
        rf'op_name="[^"]*rematted_computation/{kind}/[^"]*dot_general"', text)
    assert not again, again[:2]


def _routing_made_once(ops, trainer, jaxpr) -> None:
    """The round's expert layers (one a counters' top) make their routing
    once a step (`moe_route`; PR 52): on a step body's forward path a score
    product a layer, and in the report's count with it the choice's `top_k`s
    (sorts here) and the compiler's `ConcatBitcast`s around them; on a
    recomputed path none of them -- no product, sort or custom call under a
    `router` scope is made again --, and what a step keeps of the choices
    and the plans at most 5 MB a layer (printed as read)."""
    from sparknet_tpu.obs.device import recompute_report
    made = recompute_report(ops, trainer.net.kept_makers(), jaxpr)["moe_route"]
    layers = len(trainer.net.counter_blobs())
    routers = [op for op in ops.values() if "router" in op["scope"].split("/")]
    products = collections.Counter(
        op["computation"] for op in routers
        if op["matmul"] and op["phase"] == "forward")
    print("moe_route", made, "layers", layers, "products", dict(products))
    assert made["maker"] == "router" and made["step_bodies"] == 2, made
    assert set(products.values()) == {layers} and len(products) == 2, products
    assert made["forward"] >= 2 * layers and made["backward"] == 0, made
    assert 0 < made["kept_bytes"] <= 5e6 * layers, made
    assert not [op for op in routers if op["recomputed"] and (
        op["matmul"] or op["opcode"] in ("sort", "topk", "custom-call"))]


@pytest.mark.slow
def test_glm_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The benchmark's sequence-model round (`glm47-flash-ep8-tau4`) for one
    described chip: ~2 min. 5.65 GB of state
    + 5.68 GB of temporaries = 11.33 GB since PR 52, whose four expert
    blocks and MTP module keep their routing (5.0 MB a step) and make no
    score product, `top_k` or sort again; 5.65 and 11.30 since PR 47, whose
    head blocks keep
    their logits (2 x 634 MB a step) and make no head's product twice: the
    compiler packs the round 0.4 GB TIGHTER than the 6.05 GB it took with
    both heads' products made again (the gradient is 2.83 GB of them; what the six
    attention cores keep for the backward 1.01 GB, and their statistics
    as the kernel writes them, padded to 128 lanes, 1.0 GB more; since PR 41
    the dense block's two SwiGLU input products, 0.67 GB kept, which put
    0.96 GB on the 5.1 GB the round took before; 6.9 GB
    while q, k and v were laid out again between projection and core). Each
    step body runs the cores' forward kernel on its forward path alone, makes
    no product of the dense SwiGLU twice, and no gather or scatter in its
    attention touches an activation; both heads' products run once a step
    body (`ip_out` 2 forward, 0 on a recomputed path)."""
    compiled, trainer, jaxpr = _sequence_round(v5e, "glm47-flash-ep8-tau4")
    total = _round_bytes(compiled)
    assert total < 12.0e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "gmm" in text and "8192,8192" not in text
    from sparknet_tpu.obs.device import (attention_moves, parse_hlo_ops,
                                         recompute_report)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert (kept["attn_core"]["forward"], kept["attn_core"]["backward"]) == (6, 0)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 2)
    _products_made_once(text, kept, "mlp_pre", "GatedMLP", 2)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves  # 96 before PR 30
    assert moves["bytes"] < 57e9, moves  # 94.9 GB a step body before, 42.8 now
    # four expert layers fetch tokens x k rows twice a step (the combine, the
    # dispatch's backward), the MTP module's a third time (its combine is
    # made again for the norm that follows it)
    _routing_walks_rows(text, ops, trainer, 4 * 2 + 3, 16384)
    _routing_made_once(ops, trainer, jaxpr)


@pytest.mark.slow
def test_lfm2_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The hybrid sequence model's round (`lfm2-8b-a1b-ep4-tau4`: seven gated
    short convolutions, two grouped-query attentions at head width 64, eight
    expert layers of width 1,792, a tied head) for one described chip: 7.37
    GB of state (921,256,448 parameters and their momentum) and the round's
    temporaries (4.57 GB: 11.94 together since PR 52, whose eight expert
    blocks keep their routing, 9.2 MB a step, and make no score product,
    `top_k` or sort again; 4.52 and 11.89 before it: PR 47's kept logits of the tied
    head, 537 MB a step, moved nothing: the head's block is the last of the
    forward pass) under the chip's 16 GB beside the benchmark's stacks. The two
    attention cores run as kernels with grouped heads (no [.., 8192, 8192]
    scores), once a step body on its forward path alone, and the head's
    product once."""
    compiled, trainer, jaxpr = _sequence_round(v5e, "lfm2-8b-a1b-ep4-tau4")
    total = _round_bytes(compiled)
    assert total < 14.5e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "gmm" in text and "8192,8192" not in text
    from sparknet_tpu.obs.device import (attention_moves, parse_hlo_ops,
                                         recompute_report)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert (kept["attn_core"]["forward"], kept["attn_core"]["backward"]) == (2, 0)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 1)
    _products_made_once(text, kept, "mlp_pre", "GatedMLP", 2)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves
    _routing_walks_rows(text, ops, trainer, 8 * 2, 32768)
    _routing_made_once(ops, trainer, jaxpr)


@pytest.mark.slow
def test_ling_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The linear-attention model's round (`ling3-flash-ep64-tau4`: six Kimi
    Delta Attention layers and one latent attention with direct queries,
    six expert layers behind a 512-wide group-limited router, an untied
    head) for one described chip (~4 min): 6.58 GB of state (822,036,416
    parameters and their momentum) + 6.67 GB of temporaries: 13.25 GB since
    PR 52, whose expert blocks keep their routing (6.5 MB a step for the six
    layers) and make no score product, `top_k` or select again (13.31 and
    6.73 before it; 13.30
    before PR 50, the
    same with PR 47's kept logits, 644 MB a step, whose product runs once;
    the gradient is 3.29 of the temporaries; 6.57 before PR 44 -- one packing
    of the compiler's that every form of that PR's expert layer left, with
    the lone layer's own temporaries 50 MB lower: PERF.md section 6 --;
    6.49 before PR 43, whose weighted
    sums by token add the buffer's 4,096 rows into a float32 [16384, 2560]
    array a slab of 512 columns at a time where eight gathers fetched 16,384
    rows each -- 6.73 with the rows added whole; 6.22 before PR 41, whose
    dense block keeps its SwiGLU's two input products; 6.23 before PR 39; 7.43 with every forward of the stage
    before the rule a kernel call, which is why the shaping kernels' forward
    rule makes v in plain `jnp`: `ops/pallas_kda_shape.py`, PERF.md section
    6).
    The one attention core runs as a kernel once a step body
    on its forward path alone; every delta rule is the chunk stage's kernels
    and the walk's (`ops.pallas_delta_scan`, PR 50: 13.31 GB with them; 14.19
    with nothing but kernel calls in the rows' backward body, which is why
    the backward pass walks the chunks once as a `lax.scan` for the state
    every segment started from), and no gather or scatter in any operator
    touches an activation."""
    compiled, trainer, jaxpr = _sequence_round(v5e, "ling3-flash-ep64-tau4")
    total = _round_bytes(compiled)
    assert total < 13.5e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "gmm" in text and "8192,8192" not in text
    from sparknet_tpu.obs.device import (attention_moves, delta_rule,
                                         parse_hlo_ops, recompute_report)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert (kept["attn_core"]["forward"], kept["attn_core"]["backward"]) == (1, 0)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 1)
    _products_made_once(text, kept, "mlp_pre", "GatedMLP", 2)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves
    scopes, kept_names = trainer.net.delta_scopes()
    rule = delta_rule(ops, scopes)
    # two step bodies x six layers x (forward, made again by the row,
    # backward) x (the chunk stage's kernel, the walk's); of the device loops
    # over segments and chunks (84 before PR 50) the backward pass's walk
    # for the segments' states is left, a scan of scans a layer and step body
    print(f"state + temporaries {total / 1e9:.3f} GB", rule)
    assert rule["kernel_calls"] == 2 * 6 * 3 * 2 and kept_names == ("kda_out",), rule
    assert rule["loops"] == 2 * 6 * 2 and rule["trips"] == 2 * 6 * (16 + 8), rule
    # and as many of the stage before the rule (`ops.pallas_kda_shape`)
    assert rule["shape_kernel_calls"] == 2 * 6 * 3, rule
    # six expert layers fetch three times the buffer's 4,096 rows and add
    # them by token twice (the combine, the dispatch's backward): no gather
    # of tokens x k rows is left (2 x 6 x 8 of 16,384 rows before PR 43)
    _routing_walks_rows(text, ops, trainer, 0, 4096, buffer_sums=2, k=8)
    _routing_made_once(ops, trainer, jaxpr)


@pytest.mark.slow
def test_evabyte_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The dense byte model's round (`evabyte-l4-tau4`: four layers of EVA
    attention and SwiGLU at width 4,096, one row of 16,384 bytes a step, a
    float32 residual stream, eight heads) for one described chip: 6.57 GB
    of state (821,366,784 parameters and their momentum) and the round's
    temporaries under 15 GB together (14.44 since PR 41: the four blocks keep
    their SwiGLUs' two input products, 2.89 GB a step, which put 1.29 GB on
    the 6.58 GB of temporaries the round took before; the same with PR 47's
    kept float32 logits of the eight heads, 168 MB a step). Every core is ONE
    kernel call forward a layer-step over 17,408 key columns (the row's keys
    and 1,024 chunk summaries) and one backward, on its forward path alone,
    and no SwiGLU makes a product twice."""
    compiled, trainer, _ = _sequence_round(v5e, "evabyte-l4-tau4")
    total = _round_bytes(compiled)
    assert total < 15e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "16384,17408" not in text
    from sparknet_tpu.obs.device import (attention_moves, parse_hlo_ops,
                                         recompute_report)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert (kept["attn_core"]["forward"], kept["attn_core"]["backward"]) == (4, 0)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 1)
    _products_made_once(text, kept, "mlp_pre", "GatedMLP", 8)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves


@pytest.mark.slow
def test_nemotron_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The state-space hybrid's round (`nemotron3-super-tp4-ep64-tau4`: five
    Mamba-2 mixers at 32 held heads, one grouped-query attention without a
    rotary turn at 8 held heads, five LatentMoE layers behind a 512-wide
    router that chooses 22, the MTP module's attention and expert layer, two
    heads) for one described chip: 5.74 GB of state (716,980,192 parameters
    and their momentum) + 5.85 GB of temporaries: 11.59 GB, under 13 together
    (5.90 and 11.64 before PR 52, whose expert blocks keep their routing --
    the chosen ids and raw scores, the plan's index arrays by buffer row: 18.5 MB a step
    for the six layers -- and make no score product, sort or select again;
    6.26 and 12.00 before PR 48, whose scans are kernels that keep their
    chunks' squares in VMEM; 6.22 and 11.95 before PR 47, whose head blocks keep their logits, 2 x 537
    MB a step, the main head's across the MTP module, and the block of the
    projection into that module its result, 134 MB, the next block's input
    either way: three products once a step body; 6.31 and 12.05 before PR
    43, when every weighted sum by token was 22 gathers of 16,384 latent
    rows).
    Both attention cores run as kernels once a step body on their forward
    path alone; every scan is `ops.pallas_ssd`'s kernel pair (thirty calls:
    two step bodies x five layers x forward, forward made again with its
    chunk states, backward) and no device loop -- the kernels' grid walks a
    row's 64 chunks with the state in VMEM, and no chunk's [128, 128] squares
    exist outside them; no gather or scatter in any mixer touches an
    activation; the expert layers move rows of the latent's width."""
    compiled, trainer, jaxpr = _sequence_round(v5e, "nemotron3-super-tp4-ep64-tau4")
    total = _round_bytes(compiled)
    assert total < 13e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    # (the MTP module's concatenation is [2, 8192, 8192]; no score square is)
    assert "splash_mha" in text and "gmm" in text and "8,8192,8192" not in text
    from sparknet_tpu.obs.device import (attention_moves, parse_hlo_ops,
                                         recompute_report, ssm)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert kept["attn_core"]["forward"] >= 2 and kept["attn_core"]["backward"] == 0
    _products_made_once(text, kept, "ip_out", "InnerProduct", 3)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves
    scans = ssm(ops, trainer.net.ssd_scopes())
    print("ssm:", scans)
    # two step bodies x five layers x (forward, made again with its chunk
    # states, backward): every scan is `ops.pallas_ssd`'s kernels ...
    assert scans["layers"] == 5 and scans["kernel_calls"] == 2 * 5 * 3, scans
    # ... whose grid walks the 64 chunks with the state in VMEM: no device
    # loop under `ssd`, nothing carried from trip to trip
    assert (scans["loops"], scans["trips"], scans["carried_bytes"]) == (0, 0, 0), scans
    assert "f32[2,64,32,128,128]" not in text and "f32[2,64,32,64,128]" not in text
    scopes, width = trainer.net.routing_scopes()
    assert width == 1024
    from sparknet_tpu.model.seq_layers import moe_capacity
    rows = moe_capacity(trainer.net.spec.layer_by_name("l1_moe").moe, 2 * 8192)
    assert rows % 512 == 0 and rows >= 2 * 5632
    # five expert layers and the MTP module's fetch three times the buffer's
    # rows and add them by token three times (the combine, the combine made
    # again for `latent_up`'s weight gradient, the dispatch's backward)
    _routing_walks_rows(text, ops, trainer, 0, rows, buffer_sums=3, k=22)
    _routing_made_once(ops, trainer, jaxpr)


@pytest.mark.slow
def test_granite_packed_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The packed state-space hybrid's round (`granite4-h-micro-pp4-tau4`:
    nine Mamba-2 mixers of 64 heads in one group at chunks of 256, one
    grouped-query attention without a rotary turn, a dense SwiGLU behind
    every mixer, a tied head over 12,544 rows, ONE row of 16,384 positions
    that holds several documents) for one described chip: 6.18 GB of state
    (772,160,448 parameters and their momentum) + 9.46 GB of temporaries =
    15.64 GB, under 15.8 together (ten layers' kept SwiGLU products are 5.4
    GB of them; at a quarter of the vocabulary, 25,088 rows, the compiler
    refuses the round: 5.94 GiB of state + 10.01 of temporaries against
    15.75). The attention core runs as a kernel once a step body on its
    forward path alone, under segment ids; every scan is `ops.pallas_ssd`'s
    kernel pair under document runs (two step bodies x nine layers x
    forward, forward made again with its chunk states, backward) and no
    device loop; no SwiGLU product and no logits are made twice."""
    compiled, trainer, _ = _sequence_round(v5e, "granite4-h-micro-pp4-tau4")
    total = _round_bytes(compiled)
    assert total < 15.8e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "16384,16384" not in text
    from sparknet_tpu.obs.device import parse_hlo_ops, recompute_report, ssm
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert kept["attn_core"]["forward"] >= 2 and kept["attn_core"]["backward"] == 0
    _products_made_once(text, kept, "mlp_pre", "GatedMLP", 20)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 1)
    scans = ssm(ops, trainer.net.ssd_scopes())
    print("ssm:", scans)
    assert scans["layers"] == 9 and scans["kernel_calls"] == 2 * 9 * 3, scans
    assert (scans["loops"], scans["trips"], scans["carried_bytes"]) == (0, 0, 0), scans
    assert trainer.net.ssd_kernel_shape(trainer.net.spec.layer_by_name("l0_mamba")) \
        == {"chunk": 256, "heads_per_program": 8, "programs_per_group": 8}
    assert set(trainer.counter_blobs) == {f"l{i}_mamba_counters"
                                          for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)}


@pytest.mark.slow
def test_smallthinker_round_compiles_for_v5e_and_fits(v5e, as_tpu):
    """The sliding-window model's round (`smallthinker-21b-ep4-tau4`: one
    global grouped-query attention without a rotary turn and three over a
    sliding window of 4,096, 28 query heads over 4, four expert layers of 16
    held ReGLU experts behind a router that reads the stream before the
    attention, an untied head over 37,984 rows; ONE row of 16,384 positions)
    for one described chip: 5.25 GB of state (656,529,920 parameters and
    their momentum) and the round's temporaries under the chip's 16 GB:
    7.97 GB of them, 13.23 together, since PR 52, whose expert blocks keep
    their routing (7.3 MB a step for the four layers) and make no score
    product, `top_k` or sort again -- 2.04 GB under the 10.01 and 15.26 the
    round took since PR 47 (7.48 and 12.73 before it; bound 14.5 then): the
    compiler's packing again, not live bytes. The head's block keeps its logits, 1.245 GB a step, and
    makes their product once; the loss around them holds three float32
    [16384, 37984] arrays of 2.49 GB, never more than two at once in either
    form (its cast for the labels' gather, the softmax's gradient, that
    gradient laid out again for the gather's scatter), and with the bf16
    logits alive from the product to the gradient the compiler's packing
    gives each a place of its own where it gave the three two: + 2.49 GB of
    address space, not of live bytes -- the lone head block compiles to 7.47
    GB of temporaries against the bare block's 4.98 at a peak of 4.98 live
    in both, and on the chip the cell's `memory_peak_bytes` FELL, 8.52 ->
    8.25 GB (PERF.md section 6, PR 47, which also says what removes the
    three arrays: the labels' logits picked by a masked sum). The
    four cores run as kernels at seven heads a group (no [.., 16384, 16384]
    scores), once a step body on its forward path alone, and the sliding
    cores' tables send them to 140 key blocks where the global core's sends
    it to 272."""
    compiled, trainer, jaxpr = _sequence_round(v5e, "smallthinker-21b-ep4-tau4")
    total = _round_bytes(compiled)
    assert total < 15.6e9, f"round needs {total / 1e9:.2f} GB of a 16 GB chip"
    text = compiled.as_text()
    assert "splash_mha" in text and "gmm" in text and "16384,16384" not in text
    from sparknet_tpu.obs.device import (attention_moves, parse_hlo_ops,
                                         recompute_report, window)
    ops = parse_hlo_ops(text)
    kept = recompute_report(ops, trainer.net.kept_makers())
    assert kept["attn_core"]["step_bodies"] == 2  # the loop's, the peeled
    assert (kept["attn_core"]["forward"], kept["attn_core"]["backward"]) == (4, 0)
    _products_made_once(text, kept, "ip_out", "InnerProduct", 1)
    moves = attention_moves(ops, *trainer.net.attention_scopes())
    assert moves["gathers_scatters"] == 0, moves
    part = window(ops, *trainer.net.window_scopes())
    assert (part["windowed_layers"], part["blocks_visited"],
            part["blocks_causal"]) == (3, 272 + 3 * 140, 4 * 272), part
    for name, layer in part["layers"].items():
        assert (layer["core_forward_calls"], layer["core_backward_calls"]) == (1, 1), name
        assert layer["blocks_visited"] == (272 if layer["window"] is None else 140)
    from sparknet_tpu.model.seq_layers import moe_capacity
    rows = moe_capacity(trainer.net.spec.layer_by_name("l0_moe").moe, 16384)
    assert rows == 61440
    # four expert layers: the k = 6 gathers' side (4 x 61,440 > 6 x 16,384)
    _routing_walks_rows(text, ops, trainer, 4 * 2, rows, k=6)
    _routing_made_once(ops, trainer, jaxpr)
