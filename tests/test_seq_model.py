"""GLM's whole model (`zoo.glm4_moe_lite`) and what it needed of the net, the
solver and the trainer -- against the benchmark's plain reference
(`benchmark/configs/glm47-flash-ep8-tau4.reference.py`, which imports nothing
of the program) at small widths on the CPU: the two-headed loss and its
gradients, one tau-round through `ParallelTrainer.train_round`, the solver's
multipliers, what a recomputation block keeps, and the compiled round's
account of itself (`obs.device`'s reports on made-up texts). The layers are
`test_seq_attention.py`, the expert layer `test_seq_experts.py`.
"""
from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (CTX, D, MLA_P, MOE_P, POS, ROWS, _ids, _params, _x,
                         attention_block, case, check_loss_and_every_gradient,
                         check_products_kept, check_round, check_routing_kept,
                         compiled, program_loss_and_grads, program_round,
                         tiny_round)
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import net as net_mod
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import ApplyCtx
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (InputSpec, LayerSpec, MLAttentionParam,
                                     NetSpec, ParamSpec, RMSNormParam)

GLM = case("glm4_moe_lite")
ref, TINY, LAYERS = GLM.ref, GLM.tiny, GLM.layers


# -- the whole model ---------------------------------------------------------

def _net():
    return compiled("glm4_moe_lite")


@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("float32", 3), ("bfloat16", 1)])
def test_two_headed_loss_and_gradients_match_the_reference(policy, seed):
    # bf16: an expert here sees some tens of tokens, and one slot that flips
    # its expert on a rounding moves its gradient
    f32 = policy == "float32"
    blobs, _, _ = check_loss_and_every_gradient(
        "glm4_moe_lite", policy, GLM.params(seed), _ids(seed + 70),
        loss_tol=2e-5 if f32 else 2e-3, grad_tol=2e-5 if f32 else 0.3)
    assert float(blobs["loss_next"] + blobs["loss_mtp"]) == pytest.approx(
        float(blobs["loss"]), rel=1e-6)


@pytest.mark.parametrize("other", ["no_blocks", "blocks_that_keep_inputs_only"])
def test_recomputation_blocks_change_no_number_and_sharing_sums_gradients(
        other, monkeypatch):
    """The net as built (blocks that keep what their layers name) against
    the same net with no recomputation at all, and against blocks under the
    bare `jax.checkpoint`: the same loss and gradients to the bit."""
    spec = GLM.spec()
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "l2", "head", "mtp",
                                              "mtp_head"}
    params, ids = GLM.params(7), _ids(77)
    # (a fresh function a trace: `_kept_names` is no part of jax's cache key)
    f = lambda net: jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn("loss")(p, {"tokens": ids}, None)[0]))(params)
    (l1, _), g1 = program_loss_and_grads("glm4_moe_lite", "float32")(params, ids)
    if other == "no_blocks":
        l2, g2 = f(CompiledNet.compile(spec.replace(layers=tuple(
            LayerSpec(**{**l.__dict__, "block": None}) for l in spec.layers))))
    else:
        monkeypatch.setattr(net_mod, "_kept_names", lambda layers: ())
        l2, g2 = f(_net())
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-7)
    # the MTP module's lookup and head run on the embedding's and the head's
    # own matrices: neither appears twice, and both gradients hold both uses
    assert "mtp_embed" not in params and "mtp_head" not in params
    assert _net().param_layers() == [n for n, _, _ in LAYERS]
    no_mtp = CompiledNet.compile(GLM.spec(num_nextn_predict_layers=0))
    g0 = jax.jit(jax.grad(
        lambda p: no_mtp.loss_fn("loss")(p, {"tokens": ids}, None)[0]))(
        {k: v for k, v in params.items() if k != "mtp"})
    assert not np.allclose(g0["lm_head"]["w"], g1["lm_head"]["w"], rtol=1e-3)
    with pytest.raises(ValueError, match="param_from"):
        CompiledNet.compile(spec.replace(layers=tuple(
            LayerSpec(**{**l.__dict__, "param_from": "nowhere"})
            if l.name == "mtp_head" else l for l in spec.layers)))


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tmp_path):
    from sparknet_tpu.apps.train_loop import resolve_spec

    case_ = tiny_round("glm4_moe_lite", tmp_path, tau=3, mtp_weight=0.3)
    cfg, params, ids = case_.cfg, case_.params, case_.ids
    assert resolve_spec(cfg, tokens=(ROWS, 16)).inputs[0].shape == (ROWS, 16)
    trainer = case_.make_trainer()
    # ids stay int32 on their way to the device
    placed = trainer.place_batches({"tokens": ids})
    assert placed["tokens"].dtype == jnp.int32
    assert np.array_equal(np.asarray(placed["tokens"]), ids)
    state, got = program_round("glm4_moe_lite", trainer, params, ids)
    check_round(got, case_.want, rel=2e-4)
    assert float(jnp.linalg.norm(state.params["l1_moe"]["router_bias"][0]
                                 - params["l1_moe"]["router_bias"])) == 0
    # the round's counters: sums over its three steps, on the device until read
    assert trainer.last_health is None
    values = trainer.counter_values()
    assert set(values) == {"l1_moe_counters", "l2_moe_counters", "mtp_counters"}
    for v in values.values():
        assert list(v) == list(sl.MOE_COUNTERS) and v["slots_dropped"] == 0
        assert 0 < v["slots_landed"] <= 3 * ROWS * POS * 2
        assert v["expert_tokens_max"] + v["expert_tokens_min"] == v["slots_landed"]
    # ... and scrapeable: sparknet_moe_<counter>{layer=...}
    from sparknet_tpu.obs import MetricsRegistry
    from sparknet_tpu.obs import device as obs_device
    registry = MetricsRegistry()
    obs_device.attach_round_counter_gauges(registry, trainer)
    text = registry.render_prometheus()
    assert 'sparknet_moe_slots_dropped{layer="l1_moe"} 0' in text
    assert registry.gauge("sparknet_moe_slots_landed", labels=("layer",)).value(
        layer="mtp") == values["mtp_counters"]["slots_landed"]
    # the round program's account of what its blocks keep: off the chip no
    # kernel runs (both counts 0), and a step keeps the four cores' outputs
    report = obs_device.program_report("train_round")
    assert report is trainer.program_report()
    kept = dict(report["recompute"])
    pre = kept.pop(sl.MLP_PRE)
    # ... both heads their logits (the second runs on the first's matrix)
    check_products_kept("glm4_moe_lite", report, tau=3)
    del kept[sl.IP_OUT]
    # ... and the two expert blocks and the MTP module's their routing
    check_routing_kept(report, 3, [
        case_.spec.layer_by_name("l1_moe").moe,
        case_.spec.layer_by_name("l2_moe").moe,
        case_.spec.layer_by_name("mtp").mtp.moe])
    del kept[sl.MOE_ROUTE]
    assert kept == {sl.ATTN_CORE: {
        "maker": "splash_mha_fwd", "step_bodies": 0, "forward": 0, "backward": 0,
        "kept_bytes": 4 * ROWS * POS * MLA_P.num_heads * MLA_P.v_head_dim * 4}}
    # ... and the one dense block its SwiGLU's two input products, neither
    # made again (the shared experts of the other blocks name nothing)
    assert (pre["maker"], pre["backward"]) == (sl.MLP_PRE, 0) and pre["forward"] >= 2
    assert pre["kept_bytes"] == 2 * ROWS * POS * TINY["intermediate_size"] * 4
    assert obs_device.program_part("recompute")["train_round"] == report["recompute"]
    with pytest.raises(ValueError, match="model_type"):
        case_.path.write_text(json.dumps(dict(TINY, model_type="other")))
        resolve_spec(cfg)


def test_a_net_without_counters_has_none_and_its_round_is_what_it_was():
    from sparknet_tpu.parallel import ParallelTrainer, make_mesh
    from sparknet_tpu.solver import SolverConfig

    net = CompiledNet.compile(zoo.lenet(batch=4))
    assert net.counter_blobs() == {} and net.kept_makers() == {}
    trainer = ParallelTrainer(net, SolverConfig(), make_mesh(1), tau=2,
                              compute_health=False)
    assert trainer._health_specs() == {} and trainer.counter_values() == {}
    assert _net().counter_blobs() == {
        b: sl.MOE_COUNTERS for b in ("l1_moe_counters", "l2_moe_counters", "mtp_counters")}


# -- the solver's multipliers ------------------------------------------------

def test_param_multipliers_by_the_layers_own_parameter_names():
    from sparknet_tpu.solver import _param_multipliers
    lr, decay = _param_multipliers(_net())
    assert lr["l1_moe"]["router_bias"] == 0 and decay["l1_moe"]["router_bias"] == 0
    assert lr["mtp"]["router_bias"] == 0 and lr["mtp"]["router"] == 1
    for layer, names in (("l0_attn", ("q_a_norm", "kv_a_norm")),
                         ("final_norm", ("scale",)), ("l1_attn_norm", ("scale",)),
                         ("mtp", ("enorm", "hnorm", "attn_norm", "mlp_norm", "norm",
                                  "q_a_norm", "kv_a_norm"))):
        for name in names:
            assert (lr[layer][name], decay[layer][name]) == (1.0, 0.0), (layer, name)
    for layer, name in (("l0_attn", "q_b"), ("l0_mlp", "down"), ("embed", "w"),
                        ("lm_head", "w"), ("l2_moe", "experts_up"), ("mtp", "eh_proj")):
        assert (lr[layer][name], decay[layer][name]) == (1.0, 1.0)
    assert set(lr["l1_moe"]) == set(ref.param_shapes(LAYERS)["l1_moe"])
    assert {n: ref.multipliers(n) for n in lr["mtp"]} == {
        n: (lr["mtp"][n], decay["mtp"][n]) for n in lr["mtp"]}
    # a spec's own ParamSpecs still go to "w" and "b", in that order
    spec = NetSpec(name="n", inputs=(InputSpec("x", (2, 4)),), layers=(
        LayerSpec(name="e", type="RMSNorm", bottoms=("x",), tops=("e",),
                  rmsnorm=RMSNormParam()),))
    assert _param_multipliers(CompiledNet.compile(spec))[1] == {"e": {"scale": 0.0}}


def test_caffenets_multipliers_are_unchanged():
    from sparknet_tpu.solver import _param_multipliers
    lr, decay = _param_multipliers(CompiledNet.compile(zoo.caffenet(batch=2)))
    layers = ["conv1", "conv2", "conv3", "conv4", "conv5", "fc6", "fc7", "fc8"]
    assert lr == {l: {"w": 1.0, "b": 2.0} for l in layers}
    assert decay == {l: {"w": 1.0, "b": 0.0} for l in layers}
    lr, decay = _param_multipliers(CompiledNet.compile(zoo.lenet(batch=2)))
    assert all(v == {"w": 1.0, "b": 2.0} for v in lr.values())
    assert all(v == {"w": 1.0, "b": 1.0} for v in decay.values())
    bare = zoo.lenet(batch=2)
    bare = bare.replace(layers=tuple(LayerSpec(**{**l.__dict__, "params": (
        ParamSpec(lr_mult=3.0),)}) if l.name == "fc2" else l for l in bare.layers))
    lr, _ = _param_multipliers(CompiledNet.compile(bare))
    assert lr["fc2"] == {"w": 3.0, "b": 1.0}


# -- what a recomputation block keeps ----------------------------------------

#: head sizes of whole lanes, positions a multiple of the kernel's tiles: the
#: smallest attention the kernel path takes
KERNEL_MLA_P = MLAttentionParam(num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                                qk_nope_head_dim=96, qk_rope_head_dim=32,
                                v_head_dim=128, rope_theta=1e6, eps=1e-5)
KERNEL_POS = max(sl.ATTN_BLOCKS)


@pytest.fixture
def kernel_path(monkeypatch):
    """The layers take their kernel path (as on the chip) under bf16: for
    tracing alone, nothing here can run a TPU kernel."""
    monkeypatch.setattr(sl, "use_kernels", lambda ctx: True)
    with precision.policy("bfloat16"):
        yield


def test_the_core_forward_kernel_is_traced_once_where_the_block_keeps_its_names(
        kernel_path, monkeypatch):
    """`jax.make_jaxpr` of an attention block's gradient, the kernel path
    forced: one forward splash kernel and one backward; under the bare
    `jax.checkpoint` (what a block was before it kept names) the forward
    kernel is there twice."""
    import re
    _, params, x, loss = attention_block(KERNEL_MLA_P, KERNEL_POS)
    kernels = lambda: re.findall(r"name=(splash_mha_\w+)",
                                 str(jax.make_jaxpr(jax.grad(loss))(params, x)))
    assert kernels() == ["splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"]
    monkeypatch.setattr(net_mod, "_kept_names", lambda layers: ())
    assert kernels() == ["splash_mha_fwd_residuals", "splash_mha_fwd_residuals",
                         "splash_mha_dkv_no_residuals"]


def _kept(loss, params, x):
    """What the backward pass keeps that is neither an argument nor a
    constant: [(shape, dtype)]."""
    from jax._src.ad_checkpoint import saved_residuals  # public: its printer
    return sorted((a.shape, str(a.dtype)) for a, why in
                  saved_residuals(loss, params, x)
                  if "from the argument" not in why and "constant" not in why)


def test_a_block_keeps_the_cores_output_and_nothing_else_on_the_exact_path():
    _, params, x, loss = attention_block()
    assert _kept(loss, params, x) == [
        ((ROWS, MLA_P.num_heads, POS, MLA_P.v_head_dim), "float32")]


def test_a_block_keeps_the_cores_output_and_statistics_on_the_kernel_path(
        kernel_path, monkeypatch):
    net, params, x, loss = attention_block(KERNEL_MLA_P, KERNEL_POS)
    heads = (ROWS, KERNEL_MLA_P.num_heads, KERNEL_POS)
    assert _kept(loss, params, x) == [
        (heads, "float32"), (heads + (KERNEL_MLA_P.v_head_dim,), "bfloat16")]
    assert net.kept_makers() == {sl.ATTN_CORE: "splash_mha_fwd"}
    # under the bare jax.checkpoint: the block's inputs alone
    monkeypatch.setattr(net_mod, "_kept_names", lambda layers: ())
    assert _kept(loss, params, x) == []


#: the dense feed-forward's width in the blocks below
MLP_WIDTH = 160


def _mlp_block():
    """(net, its loss and gradients) of one recomputation block as a
    decoder's dense feed-forward half is: norm, SwiGLU, residual sum. The
    gradient is taken under the round's step scope, which
    `obs.device.scope_of` reads a pass from."""
    from sparknet_tpu.model.spec import GatedMLPParam
    from sparknet_tpu.obs.device import STEP_SCOPE
    tag = dict(block="b")
    net = CompiledNet.compile(NetSpec(
        name="blk", inputs=(InputSpec("x", (ROWS, POS, D)),), layers=(
            LayerSpec(name="n", type="RMSNorm", bottoms=("x",), tops=("xn",),
                      rmsnorm=RMSNormParam(), **tag),
            LayerSpec(name="m", type="GatedMLP", bottoms=("xn",), tops=("y",),
                      gated_mlp=GatedMLPParam(intermediate_size=MLP_WIDTH), **tag),
            LayerSpec(name="r", type="Eltwise", bottoms=("x", "y"), tops=("z",),
                      **tag))))

    def loss(p, x):
        z = net.apply(p, {"x": x}, train=True)["z"].astype(jnp.float32)
        return jnp.sum(z * z)

    def grad(p, x):
        with jax.named_scope(STEP_SCOPE):
            return jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    return net, loss, grad


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_a_dense_block_makes_no_product_twice_and_computes_what_it_did(
        policy, monkeypatch):
    """The gradient of norm -> SwiGLU -> sum in one block holds the layer's
    three products forward and their six backward; under the bare
    `jax.checkpoint` (what the block was before `GatedMLP` named anything)
    `x W_gate` and `x W_up` are there a second time. Loss and gradients are
    the same bits either way."""
    net, loss, grad = _mlp_block()
    params = net.init_params(jax.random.PRNGKey(5))
    x = _x(11)
    # (a fresh function a trace: the policy is no part of jax's cache key)
    products = lambda: str(jax.make_jaxpr(lambda p, x: grad(p, x))(
        params, x)).count("dot_general")
    wide = lambda: [k for k in _kept(loss, params, x) if k[0][-1] == MLP_WIDTH]
    with precision.policy(policy):
        assert wide() == [((ROWS, POS, MLP_WIDTH), policy)] * 2
        assert products() == 9
        kept = jax.jit(lambda p, x: grad(p, x))(params, x)
        monkeypatch.setattr(net_mod, "_kept_names", lambda layers: ())
        assert wide() == []
        assert products() == 11
        bare = jax.jit(lambda p, x: grad(p, x))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(kept[0]) > 0 and all(
        np.any(np.asarray(g)) for g in jax.tree_util.tree_leaves(kept[1]))


@pytest.mark.parametrize("train", [True, False])
def test_a_dense_layer_pins_its_input_in_training_alone(train):
    """`apply_gatedmlp` sets its input behind an `optimization_barrier` where
    a backward pass will follow (so the norm before it is written once and
    read, not made again inside each weight-gradient product: PERF.md
    section 6, PR 41), and nowhere else; the result is `_swiglu`'s bits."""
    from sparknet_tpu.model.spec import GatedMLPParam
    layer = LayerSpec(name="m", type="GatedMLP", bottoms=("x",), tops=("y",),
                      gated_mlp=GatedMLPParam(intermediate_size=MLP_WIDTH))
    p, x = _params(3, "l0_mlp"), _x(4)
    apply = lambda p, x: sl.apply_gatedmlp(layer, p, (x,), ApplyCtx(train=train))[0]
    text = str(jax.make_jaxpr(apply)(p, x))
    assert ("optimization_barrier" in text) == train
    assert text.count("name=" + sl.MLP_PRE) == 2
    assert np.array_equal(np.asarray(jax.jit(apply)(p, x)), np.asarray(
        jax.jit(lambda p, x: sl._swiglu(x, p["gate"], p["up"], p["down"]))(p, x)))


def test_the_report_counts_a_dense_blocks_products_made_again(monkeypatch):
    """`recompute_report` of the compiled block: the two named products on
    the forward path and none on a recomputed one, with the bytes a step
    keeps; with the name struck from the block's policy, both made again
    (the backward pass proper runs four products under the same scope, which
    are no recomputation and do not count)."""
    from sparknet_tpu.obs import device as obs_device
    net, _, grad = _mlp_block()
    makers = net.kept_makers()
    assert makers == {sl.MLP_PRE: sl.MLP_PRE}
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((ROWS, POS, D), jnp.float32)

    def report():
        # (a fresh function a trace: the policy is no part of the cache key)
        traced = jax.jit(lambda p, x: grad(p, x)).trace(params, x)
        ops = obs_device.parse_hlo_ops(traced.lower().compile().as_text())
        return obs_device.recompute_report(ops, makers,
                                           traced.jaxpr.jaxpr)[sl.MLP_PRE]

    assert report() == {"maker": sl.MLP_PRE, "step_bodies": 1, "forward": 2,
                        "backward": 0,
                        "kept_bytes": 2 * ROWS * POS * MLP_WIDTH * 4}
    with precision.policy("bfloat16"):
        assert report()["kept_bytes"] == 2 * ROWS * POS * MLP_WIDTH * 2
    monkeypatch.setattr(net_mod, "_kept_names", lambda layers: ())
    got = report()
    assert (got["forward"], got["backward"]) == (2, 2)


def test_an_expert_layers_shared_expert_names_nothing():
    """The shared expert runs `_swiglu` as the dense layer does but under no
    name: an expert block's policy names the routing alone (`moe_route`: the
    ids and their logits, the plan's five arrays, the group sizes), and a
    gradient through the layer names no other value."""
    assert sl.KEPT_NAMES["MoE"] == (sl.MOE_ROUTE,)
    assert sl.KEPT_NAMES["MTP"] == (sl.ATTN_CORE, sl.MOE_ROUTE)
    net = _net()
    by_block = {}
    for l in net.spec.layers_for_phase("TRAIN"):
        by_block.setdefault(l.block, []).append(l)
    expert = [ls for b, ls in by_block.items() if b is not None
              and any(l.type == "MoE" for l in ls)]
    assert expert and all(
        net_mod._kept_names(ls) == (sl.ATTN_CORE, sl.MOE_ROUTE) for ls in expert)
    dense = [ls for b, ls in by_block.items() if b is not None
             and any(l.type == "GatedMLP" for l in ls)]
    assert [net_mod._kept_names(ls) for ls in dense] == [
        (sl.ATTN_CORE, sl.MLP_PRE)]
    assert MOE_P.n_shared_experts == 1
    p, x = _params(1), _x(2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(sl.moe(MOE_P, p, x, CTX)[0])))(p, x)
    named = re.findall(r"name\[name=(\w+)\]", str(jaxpr))
    assert named and set(named) == {sl.MOE_ROUTE} and len(named) % 8 == 0


RECOMPUTE_HLO = '''HloModule jit_train_round

%body.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %splash_mha_fwd_residuals.1 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MLAttention/l0_attn)/core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call"}
  %splash_mha_fwd_residuals.2 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/attention/core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call"}
  %splash_mha_fwd_residuals.3 = f32[4]{0} custom-call(%splash_mha_fwd_residuals.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(jvp()))/checkpoint/rematted_computation/MTP/mtp/attention/core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call"}
  %splash_mha_dkv_no_residuals.1 = f32[4]{0} custom-call(%splash_mha_fwd_residuals.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(jvp()))/checkpoint/MTP/mtp/attention/core/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/pallas_call"}
  ROOT %add.2 = f32[4]{0} add(%splash_mha_fwd_residuals.1, %splash_mha_dkv_no_residuals.1), metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(MoE/l1_moe))/experts/add"}
}

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %splash_mha_fwd_residuals.4 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/tau_step/jvp(MLAttention/l0_attn)/core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call"}
  ROOT %call.1 = f32[4]{0} call(%splash_mha_fwd_residuals.4), to_apply=%body.1
}
'''


def test_the_report_counts_a_kept_values_kernel_by_the_pass_it_runs_in():
    """Two cores in the loop's body, one of them run again for the backward
    pass (its name did not reach the block's policy), one in the peeled
    step: the step body that has most is the one reported."""
    from sparknet_tpu.obs import MetricsRegistry
    from sparknet_tpu.obs import device as obs_device
    ops = obs_device.parse_hlo_ops(RECOMPUTE_HLO)
    assert ops["%splash_mha_fwd_residuals.3"]["phase"] == "backward"
    assert ops["%splash_mha_fwd_residuals.3"]["computation"] == "body.1"
    assert ops["%splash_mha_fwd_residuals.4"]["computation"] == "main.1"
    got = obs_device.recompute_report(ops, {sl.ATTN_CORE: "splash_mha_fwd"})
    assert got == {sl.ATTN_CORE: {"maker": "splash_mha_fwd", "step_bodies": 2,
                                  "forward": 2, "backward": 1, "kept_bytes": None}}
    assert obs_device.recompute_report(ops, {}) == {}
    # ... and the gauge beside the program's memory gauges reads it
    obs_device.register_program("a_round", lambda: {
        "memory": {"temp": 1, "argument": 2, "output": 3}, "ops": ops,
        "recompute": got})
    registry = MetricsRegistry()
    obs_device.attach_program_gauges(registry, "a_round")
    assert "\nsparknet_a_round_recompute_core_forward_in_backward " not in \
        registry.render_prometheus(), "no sample until the report has run"
    obs_device.program_report("a_round")
    assert registry.gauge(
        "sparknet_a_round_recompute_core_forward_in_backward").value() == 1.0
    assert obs_device.program_part("recompute")["a_round"] == got


MOVES_HLO = '''HloModule jit_train_round

%fused_turn (a: f32[2,16,8], i: s32[4]) -> f32[2,16,4] {
  %a = f32[2,16,8]{2,1,0} parameter(0)
  %i = s32[4]{0} parameter(1)
  ROOT %gather.1 = f32[2,16,4]{2,1,0} gather(%a, %i), offset_dims={0,1}, collapsed_slice_dims={2}, start_index_map={2}, index_vector_dim=1, slice_sizes={2,16,1}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MLAttention/l0_attn)/gather"}
}

%fused_weight (w: f32[12,6], i: s32[4]) -> f32[12,4] {
  %w = f32[12,6]{1,0} parameter(0)
  %i = s32[4]{0} parameter(1)
  ROOT %gather.2 = f32[12,4]{1,0} gather(%w, %i), offset_dims={0}, collapsed_slice_dims={1}, start_index_map={1}, index_vector_dim=1, slice_sizes={12,1}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MLAttention/l0_attn)/gather"}
}

%fused_dot (x: bf16[2,16,8], w: bf16[8,6]) -> bf16[2,16,6] {
  %x = bf16[2,16,8]{2,1,0} parameter(0)
  %w = bf16[8,6]{1,0} parameter(1)
  ROOT %dot.1 = bf16[2,16,6]{2,1,0} dot(%x, %w), lhs_contracting_dims={2}, rhs_contracting_dims={0}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MLAttention/l0_attn)/dot_general"}
}

%body.1 (x: bf16[2,16,8], a: f32[2,16,8], w: bf16[8,6], i: s32[4], m: f32[12,6]) -> bf16[2,16,6] {
  %x = bf16[2,16,8]{2,1,0} parameter(0)
  %a = f32[2,16,8]{2,1,0} parameter(1)
  %w = bf16[8,6]{1,0} parameter(2)
  %i = s32[4]{0} parameter(3)
  %m = f32[12,6]{1,0} parameter(4)
  %turn.1 = f32[2,16,4]{2,1,0} fusion(%a, %i), kind=kLoop, calls=%fused_turn
  %weight.1 = f32[12,4]{1,0} fusion(%m, %i), kind=kLoop, calls=%fused_weight
  %copy.1 = bf16[2,16,8]{1,2,0} copy(%x), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/attention/transpose"}
  %copy.2 = bf16[2,16,8]{1,2,0} copy(%x), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/moe/experts/transpose"}
  %kernel.1 = bf16[2,16,8]{2,1,0} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/attention/core/pallas_call"}
  %bitcast.1 = bf16[2,16,8]{2,1,0} bitcast(%kernel.1), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/attention/reshape"}
  ROOT %project.1 = bf16[2,16,6]{2,1,0} fusion(%bitcast.1, %w), kind=kOutput, calls=%fused_dot
}

ENTRY %main.1 (x: bf16[2,16,8], a: f32[2,16,8], w: bf16[8,6], i: s32[4], m: f32[12,6]) -> bf16[2,16,6] {
  %x = bf16[2,16,8]{2,1,0} parameter(0)
  %a = f32[2,16,8]{2,1,0} parameter(1)
  %w = bf16[8,6]{1,0} parameter(2)
  %i = s32[4]{0} parameter(3)
  %m = f32[12,6]{1,0} parameter(4)
  %copy.3 = bf16[2,16,8]{1,2,0} copy(%x), metadata={op_name="jit(train_round)/tau_step/jvp(MLAttention/l0_attn)/transpose"}
  ROOT %call.1 = bf16[2,16,6]{2,1,0} call(%x, %a, %w, %i, %m), to_apply=%body.1
}
'''


def test_attention_moves_counts_what_attention_moves_without_computing():
    """In the loop's body: a fusion that gathers along an activation's lanes
    ([rows 2, positions 16, 8] -> 4), one that gathers a weight's columns,
    and a copy, under the two kinds of attention scope; a copy under the MTP
    module's experts, a kernel, a bitcast and a matmul fusion, none of which
    count. The peeled step holds one copy: the body that moves most is the
    one reported."""
    from sparknet_tpu.obs import MetricsRegistry
    from sparknet_tpu.obs import device as obs_device
    ops = obs_device.parse_hlo_ops(MOVES_HLO)
    assert ops["%project.1"]["matmul"] and not ops["%copy.1"]["matmul"]
    assert ops["%copy.1"]["bytes"] == 2 * 2 * 16 * 8 * 2
    assert ops["%turn.1"]["bytes"] == 4 * (2 * 16 * 8 + 4 + 2 * 16 * 4)
    assert ops["%bitcast.1"]["bytes"] == 0
    got = obs_device.attention_moves(ops, sl.ATTENTION_SCOPES, positions=16)
    assert got == {"instructions": 3, "gathers_scatters": 1,
                   "bytes": ops["%turn.1"]["bytes"] + ops["%weight.1"]["bytes"]
                   + ops["%copy.1"]["bytes"]}
    # the compiler may split the positions in two; a weight's axis is no position
    assert obs_device.attention_moves(ops, sl.ATTENTION_SCOPES, 32)["gathers_scatters"] == 1
    assert obs_device.attention_moves(ops, sl.ATTENTION_SCOPES, 8)["gathers_scatters"] == 2
    assert obs_device.attention_moves(ops, {}, 0) == {}
    # the net says which scopes and how many positions
    assert _net().attention_scopes() == (
        {"MLAttention": "", "MTP": "attention"}, POS)  # of ITS layers' types
    # ... and the gauges beside the program's memory gauges read it
    obs_device.register_program("b_round", lambda: {
        "memory": {"temp": 1, "argument": 2, "output": 3}, "ops": ops,
        "recompute": {}, "attention_moves": got})
    registry = MetricsRegistry()
    obs_device.attach_program_gauges(registry, "b_round")
    assert "\nsparknet_b_round_attention_moves_bytes " not in \
        registry.render_prometheus(), "no sample until the report has run"
    obs_device.program_report("b_round")
    assert registry.gauge("sparknet_b_round_attention_moves_bytes").value() == got["bytes"]
    assert registry.gauge("sparknet_b_round_attention_moves_gathers_scatters").value() == 1.0
    assert obs_device.program_part("attention_moves")["b_round"] == got


ROUTES_HLO = '''HloModule jit_train_round

%fused_rows (x: bf16[4,8], i: s32[6]) -> bf16[6,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  ROOT %gather.1 = bf16[6,8]{1,0} gather(%x, %i), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,8}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/dispatch/gather"}
}

%fused_sum (y: bf16[6,8], i: s32[4], j: s32[4]) -> bf16[4,8] {
  %y = bf16[6,8]{1,0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %j = s32[4]{0} parameter(2)
  %gather.2 = bf16[4,8]{1,0} gather(%y, %i), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,8}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/moe/combine/gather"}
  %gather.3 = bf16[4,8]{1,0} gather(%y, %j), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,8}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/moe/combine/gather"}
  ROOT %add.1 = bf16[4,8]{1,0} add(%gather.2, %gather.3), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MTP/mtp)/moe/combine/add"}
}

%fused_weights (w: f32[8], i: s32[6]) -> f32[6] {
  %w = f32[8]{0} parameter(0)
  %i = s32[6]{0} parameter(1)
  ROOT %gather.4 = f32[6]{0} gather(%w, %i), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}, metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(MoE/l1_moe))/combine/gather"}
}

%fused_fetch (u: f32[6,8], i: s32[6]) -> f32[6,8] {
  %u = f32[6,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  ROOT %gather.5 = f32[6,8]{1,0} gather(%u, %i), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,8}, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/combine/scatter-add"}
}

%fused_scatter (z: f32[4,8], i: s32[6], u: f32[6,8]) -> f32[4,8] {
  %z = f32[4,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %u = f32[6,8]{1,0} parameter(2)
  ROOT %scatter.1 = f32[4,8]{1,0} scatter(%z, %i, %u), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, indices_are_sorted=true, to_apply=%add_f32, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/combine/scatter-add"}
}

%fused_sorted_add (z: f32[4,8], i: s32[6], u: f32[6,8]) -> f32[4,8] {
  %z = f32[4,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %u = f32[6,8]{1,0} parameter(2)
  %fetch.1 = f32[6,8]{1,0} fusion(%u, %i), kind=kCustom, calls=%fused_fetch
  ROOT %inner.1 = f32[4,8]{1,0} fusion(%z, %i, %fetch.1), kind=kCustom, calls=%fused_scatter
}

%fused_fetch_scalars (v: f32[6], i: s32[6]) -> f32[6] {
  %v = f32[6]{0} parameter(0)
  %i = s32[6]{0} parameter(1)
  ROOT %gather.6 = f32[6]{0} gather(%v, %i), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}, metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(MoE/l1_moe))/combine/scatter-add"}
}

%fused_place (q: f32[8], i: s32[6], v: f32[6]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %v = f32[6]{0} parameter(2)
  ROOT %scatter.2 = f32[8]{0} scatter(%q, %i, %v), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, indices_are_sorted=true, to_apply=%add_f32
}

%fused_sorted_place (q: f32[8], i: s32[6], v: f32[6]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %v = f32[6]{0} parameter(2)
  %fetch.2 = f32[6]{0} fusion(%v, %i), kind=kCustom, calls=%fused_fetch_scalars
  ROOT %inner.2 = f32[8]{0} fusion(%q, %i, %fetch.2), kind=kCustom, calls=%fused_place
}

%body.1 (x: bf16[4,8], i: s32[6], j: s32[4], w: f32[8], z: f32[4,8], u: f32[6,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %j = s32[4]{0} parameter(2)
  %w = f32[8]{0} parameter(3)
  %z = f32[4,8]{1,0} parameter(4)
  %u = f32[6,8]{1,0} parameter(5)
  %added.1 = f32[4,8]{1,0} fusion(%z, %i, %u), kind=kCustom, calls=%fused_sorted_add, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/combine/scatter-add"}
  %rows.1 = bf16[6,8]{1,0} fusion(%x, %i), kind=kLoop, calls=%fused_rows
  %weights.1 = f32[6]{0} fusion(%w, %i), kind=kLoop, calls=%fused_weights
  %placed.1 = f32[8]{0} fusion(%w, %i, %weights.1), kind=kCustom, calls=%fused_sorted_place, metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(MoE/l1_moe))/combine/scatter-add"}
  %copy.1 = bf16[6,8]{0,1} copy(%rows.1), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/experts/transpose"}
  %sort.1 = s32[4]{0} sort(%j), dimensions={0}, to_apply=%lt, metadata={op_name="jit(train_round)/while/body/tau_step/jvp(MoE/l1_moe)/router/sort"}
  ROOT %sum.1 = bf16[4,8]{1,0} fusion(%rows.1, %j, %sort.1), kind=kLoop, calls=%fused_sum
}

ENTRY %main.1 (x: bf16[4,8], i: s32[6], j: s32[4], w: f32[8], z: f32[4,8], u: f32[6,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %j = s32[4]{0} parameter(2)
  %w = f32[8]{0} parameter(3)
  %z = f32[4,8]{1,0} parameter(4)
  %u = f32[6,8]{1,0} parameter(5)
  %peeled.1 = bf16[6,8]{1,0} fusion(%x, %i), kind=kLoop, calls=%fused_rows
  ROOT %call.1 = bf16[4,8]{1,0} call(%x, %i, %j, %w, %z, %u), to_apply=%body.1
}
'''


def test_routing_moves_counts_the_rows_routing_gathers():
    """In the loop's body, under an expert layer's and the MTP module's
    routing scopes: a fusion that gathers 6 rows of width 8, one that holds
    two gathers of 4 rows, one that gathers 6 SCALARS (no row: one move of
    single elements, 6 of them), a sort, a scatter-add of 6 rows into [4, 8]
    as the TPU compiler writes one -- a fusion that holds a fusion with the
    fetch of the updates in sorted order and a fusion with the scatter: one
    scatter of 6 rows, and the compiler's own fetch no row gather of
    routing's -- and a scatter-add of 6 scalars into [8] written the same
    way: a second move of single elements, the compiler's fetch inside it
    none; a copy under `experts`, which is no routing. The peeled step holds
    one gather: the body that moves most is the one reported.
    `attention_moves` and `routing_moves` are two calls of one query."""
    from sparknet_tpu.obs import device as obs_device
    ops = obs_device.parse_hlo_ops(ROUTES_HLO)
    assert ops["%sum.1"]["gathered"] == [(4, 8), (4, 8)]
    assert ops["%weights.1"]["gathered"] == [(6,)]
    assert ops["%added.1"]["scattered"] == [((4, 8), (6, 8))]
    assert "gathered" not in ops["%added.1"] and "scattered" not in ops["%sum.1"]
    assert ops["%weights.1"]["scalars"] == [6] == ops["%placed.1"]["scalars"]
    assert ops["%placed.1"]["scattered"] == [((8,), (6,))]
    assert not any("scalars" in ops[n] for n in ("%rows.1", "%sum.1", "%added.1"))
    got = obs_device.routing_moves(ops, sl.ROUTING_SCOPES, width=8)
    counted = ("%rows.1", "%weights.1", "%placed.1", "%sort.1", "%sum.1",
               "%added.1")
    assert got == {"instructions": 6, "row_gathers": 3, "rows_gathered": 14,
                   "row_scatters": 1, "rows_scattered": 6,
                   "slot_scalar_moves": 2, "slot_scalars_moved": 12,
                   "bytes": sum(ops[n]["bytes"] for n in counted)}
    # another width: the same ops, no rows of it fetched; the scatter's 6
    # rows of 8 are a slab of half its columns (3 rows' worth), and no slab
    # of a narrower width's
    assert obs_device.routing_moves(ops, sl.ROUTING_SCOPES, 16) == {
        **got, "row_gathers": 0, "rows_gathered": 0, "rows_scattered": 3}
    assert obs_device.routing_moves(ops, sl.ROUTING_SCOPES, 4) == {
        **got, "row_gathers": 0, "rows_gathered": 0, "row_scatters": 0,
        "rows_scattered": 0}
    assert obs_device.routing_moves(ops, (), 0) == {}
    # the query both counters are calls of
    under_experts = obs_device.moves_under(
        ops, lambda op, parts: "experts" in parts, {"copies": lambda op: 1})
    assert under_experts == {"instructions": 1, "copies": 1,
                             "bytes": ops["%copy.1"]["bytes"]}
    assert obs_device.moves_under(ops, lambda op, parts: False, {"n": len}) == {
        "instructions": 0, "bytes": 0, "n": 0}
    # the net says which scopes and which width; a net without expert layers none
    assert _net().routing_scopes() == (sl.ROUTING_SCOPES, D)
    assert "routing_moves" in obs_device.REPORT_PARTS


# -- the compiled text's multi-line instructions -----------------------------

def test_parse_hlo_ops_reads_an_instruction_that_runs_over_lines():
    """A Pallas kernel's metadata holds line breaks, one line of it starting
    with a brace: the computation goes on after it."""
    from sparknet_tpu.obs.device import parse_hlo_ops
    text = '''HloModule jit_train_round

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %splash.1 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{}"
}}, metadata={op_name="jit(train_round)/tau_step/jvp(MLAttention/l0_attn)/core/pallas_call"}
  ROOT %add.2 = f32[4]{0} add(%splash.1, %p), metadata={op_name="jit(train_round)/tau_step/transpose(jvp(MoE/l1_moe))/experts/add"}
}
'''
    ops = parse_hlo_ops(text)
    assert ops["%splash.1"]["layer_type"] == "MLAttention"
    assert ops["%splash.1"]["scope"].endswith("l0_attn)/core")
    assert ops["%splash.1"]["phase"] == "forward"
    assert ops["%add.2"]["phase"] == "backward" and ops["%add.2"]["layer"] == "l1_moe"
