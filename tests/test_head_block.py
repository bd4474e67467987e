"""A head's logits are made once a step.

Every sequence model's head stands with its final norm and its loss in one
recomputation block, the last of the forward pass. An `InnerProduct` that
stands in a block names its result (`seq_layers.IP_OUT`), so the block keeps
the logits the forward made and its backward makes no product twice: three
products a head a step (forward, dX, dW), four under the bare
`jax.checkpoint`. What is held to here, on the CPU at the models' tiny size:
the rule (a test of `layer.block` alone), the policy the blocks get, what a
step keeps, that the kept logits are the bits the second product made (every
gradient leaf equal in float32), and which side of a tensor-parallel head's
`all_gather` carries the name. The rounds' own accounts
(`program_report("train_round")["recompute"]`) are read in each model's suite
(`model_cases.check_products_kept`); the compiled chip programs' in
`tests/test_chip_compile.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from model_cases import BLOCK_PRODUCTS, POS, ROWS, case, compiled
from sparknet_tpu import precision
from sparknet_tpu.model import net as net_mod
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import ApplyCtx, apply_innerproduct
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (EmbedParam, Filler, InnerProductParam,
                                     InputSpec, LayerSpec, LossParam, NetSpec,
                                     RMSNormParam)
from sparknet_tpu.obs.device import _named_bytes

MODELS = sorted(BLOCK_PRODUCTS)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of the jaxprs it calls with them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _names(jaxpr):
    return [e.params["name"] for e in _eqns(jaxpr) if e.primitive.name == "name"]


@functools.cache
def _gradient_jaxpr(model, policy="float32"):
    """The jaxpr of the gradient of a model's training loss: traced, never
    run, once a process (`.__wrapped__` traces anew: a block's policy is
    read at trace time)."""
    net = compiled(model)
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct(
        (1 if model == "evabyte" else ROWS, POS), jnp.int32)
    loss_fn = net.loss_fn("loss")
    with precision.policy(policy):
        return jax.make_jaxpr(jax.grad(
            lambda p, i: loss_fn(p, {"tokens": i}, None)[0]))(params, ids).jaxpr


def _without_the_name(monkeypatch):
    """Strike IP_OUT from every block's policy: the parent's blocks."""
    kept = net_mod._kept_names
    monkeypatch.setattr(net_mod, "_kept_names", lambda layers: tuple(
        n for n in kept(layers) if n != sl.IP_OUT))


@pytest.mark.parametrize("model", MODELS)
def test_the_head_block_is_checkpointed_under_a_policy(model):
    """Every block that holds an `InnerProduct` gets
    `save_only_these_names`, where the bare `jax.checkpoint` stood; a block
    whose layers name nothing (an expert layer beside a short convolution or
    a Mamba-2 mixer) still gets the bare one."""
    net = compiled(model)
    assert net.kept_makers()[sl.IP_OUT] == sl.IP_OUT == sl.KEPT_MAKERS[sl.IP_OUT]
    blocks = {}
    for l in net.spec.layers_for_phase("TRAIN"):
        if l.block is not None:
            blocks.setdefault(l.block, []).append(l)
    with_product = [b for b, ls in blocks.items()
                    if any(l.type == "InnerProduct" for l in ls)]
    assert "head" in with_product
    assert all(sl.IP_OUT in net_mod._kept_names(blocks[b]) for b in with_product)
    remats = [e for e in _eqns(_gradient_jaxpr(model))
              if e.primitive.name == "remat2"]
    # (a checkpoint inside a layer -- the delta rule's rows, the reference
    # has none -- holds no InnerProduct and is not the net's block)
    holding = [e for e in remats if any(
        "InnerProduct/" in str(q.source_info.name_stack)
        for q in _eqns(e.params["jaxpr"]))]
    assert len(holding) == len(with_product)
    assert all(e.params["policy"] is not None for e in holding)
    nothing_named = [b for b, ls in blocks.items() if not net_mod._kept_names(ls)]
    if nothing_named:
        assert sum(e.params["policy"] is None for e in remats) >= len(nothing_named)


@pytest.mark.parametrize("model", MODELS)
def test_blocks_that_keep_the_same_names_share_one_policy(model):
    """The blocks' policies are one object a set of kept names
    (`net._keep`): jax memoises a block's partial evaluation by the policy's
    identity, and a policy made anew a block lowers every jitted part of
    every block to functions of its own (Nemotron's round: 868 functions
    where shared policies give 154; PR 52)."""
    net = compiled(model)
    blocks = {}
    for l in net.spec.layers_for_phase("TRAIN"):
        if l.block is not None:
            blocks.setdefault(l.block, []).append(l)
    kept = {net_mod._kept_names(ls) for ls in blocks.values()} - {()}
    policies = {id(e.params["policy"]) for e in _eqns(_gradient_jaxpr(model))
                if e.primitive.name == "remat2"
                and e.params["policy"] is not None}
    assert kept and len(policies) == len(kept), (kept, len(policies))
    assert net_mod._keep(next(iter(kept))) is net_mod._keep(next(iter(kept)))


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("model", MODELS)
def test_a_step_keeps_every_blocks_product_whole_in_its_own_dtype(model, policy):
    """One name a product, on the product's own result: float32 under the
    float32 policy, bf16 under the bf16 one -- and float32 under both where
    the head says so (EvaByte's `fp32_logits`)."""
    jaxpr = _gradient_jaxpr(model, policy)
    columns = BLOCK_PRODUCTS[model]
    assert _names(jaxpr).count(sl.IP_OUT) == len(columns)
    wide = policy == "float32" or model == "evabyte"
    rows = 1 if model == "evabyte" else ROWS
    assert _named_bytes(jaxpr, sl.IP_OUT) == rows * POS * sum(columns) * (
        4 if wide else 2)
    kept = [e.outvars[0].aval for e in _eqns(jaxpr)
            if e.primitive.name == "name" and e.params["name"] == sl.IP_OUT]
    assert {str(a.dtype) for a in kept} == {"float32" if wide else "bfloat16"}


#: one head; two heads, the second on the first's matrix (`param_from`); a
#: tied head (`transposed`, on the embedding's table)
EXACT = ["smallthinker", "glm4_moe_lite", "lfm2_moe"]


@pytest.mark.parametrize("model", EXACT)
def test_the_kept_logits_are_the_bits_the_second_product_made(model, monkeypatch):
    """In float32 the loss and EVERY gradient leaf equal the bare block's,
    bit for bit: nothing is cast, nothing of the loss is left out."""
    net, c = compiled(model), case(model)
    head = net.spec.layer_by_name("lm_head")
    assert (head.param_from, head.inner_product.transposed) == {
        "smallthinker": (None, False), "glm4_moe_lite": (None, False),
        "lfm2_moe": ("embed", True)}[model]
    if model == "glm4_moe_lite":
        assert net.spec.layer_by_name("mtp_head").param_from == "lm_head"
    params, ids = c.params(5), c.ids(6)
    loss_fn = net.loss_fn("loss")

    def run():  # (a fresh function a trace: the policy is no part of the key)
        return jax.jit(jax.value_and_grad(
            lambda p, i: loss_fn(p, {"tokens": i}, None)[0]))(params, ids)

    loss, grads = run()
    _without_the_name(monkeypatch)
    bare_loss, bare = run()
    assert float(loss) == float(bare_loss) and float(loss) > 0
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree_util.tree_leaves(bare)) > 10
    for (path, g), b in zip(leaves, jax.tree_util.tree_leaves(bare)):
        assert np.array_equal(np.asarray(g), np.asarray(b)), path
    assert float(jnp.max(jnp.abs(grads[head.param_from or "lm_head"]["w"]))) > 0


def _products(jaxpr, columns):
    """The matrix products of a jaxpr with `columns` among their
    dimensions: forward, dX and dW of a head that wide."""
    return sum(e.primitive.name == "dot_general" and any(
        columns in v.aval.shape for v in (*e.invars, *e.outvars))
        for e in _eqns(jaxpr))


@pytest.mark.parametrize("model", EXACT)
def test_three_products_a_head_a_step_not_four(model, monkeypatch):
    heads = len(BLOCK_PRODUCTS[model])
    assert _products(_gradient_jaxpr(model), 256) == 3 * heads
    _without_the_name(monkeypatch)
    assert _products(_gradient_jaxpr.__wrapped__(model), 256) == 4 * heads


def _head(block, **param):
    return LayerSpec(name="ip", type="InnerProduct", bottoms=("x",),
                     tops=("y",), block=block, inner_product=InnerProductParam(
                         num_output=8, bias_term=False, axis=-1, **param))


@pytest.mark.parametrize("float32_out", [False, True])
def test_the_name_goes_on_where_the_layer_stands_in_a_block_and_nowhere_else(
        float32_out):
    """The rule is a test of `layer.block` alone: the same layer outside a
    block traces to the program it was (no `name` equation, no scope of its
    own), in training and out of it."""
    x = jnp.ones((2, 4, 6), jnp.float32)
    w = {"w": jnp.ones((6, 8), jnp.float32)}
    for train in (True, False):
        ctx = ApplyCtx(train=train)
        with precision.policy("bfloat16"):
            free, held = (jax.make_jaxpr(lambda w, x: apply_innerproduct(
                _head(block, float32_out=float32_out), w, (x,), ctx)[0])(w, x)
                for block in (None, "head"))
        assert _names(free.jaxpr) == [] and _names(held.jaxpr) == [sl.IP_OUT]
        named, = (e for e in held.jaxpr.eqns if e.primitive.name == "name")
        assert named.invars[0] is next(
            e for e in held.jaxpr.eqns
            if e.primitive.name == "dot_general").outvars[0]
        assert str(named.outvars[0].aval.dtype) == (
            "float32" if float32_out else "bfloat16")
        scopes = lambda j: {str(e.source_info.name_stack) for e in j.jaxpr.eqns}
        assert scopes(free) == {""} and sl.IP_OUT in scopes(held)
        strip = lambda j: [e.primitive.name for e in j.jaxpr.eqns
                           if e.primitive.name != "name"]
        assert strip(free) == strip(held)


def _tp_head_net(rows=2, positions=8, d=16, vocab=32):
    tag = dict(block="head")
    return CompiledNet.compile(NetSpec(
        name="tp_head", inputs=(InputSpec("tokens", (rows, positions), "int32"),),
        layers=(
            LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                      tops=("x",), embed=EmbedParam(num_embeddings=vocab, dim=d,
                                                    std=1.0)),
            LayerSpec(name="final_norm", type="RMSNorm", bottoms=("x",),
                      tops=("xn",), rmsnorm=RMSNormParam(), **tag),
            LayerSpec(name="lm_head", type="InnerProduct", bottoms=("xn",),
                      tops=("lm_head",), inner_product=InnerProductParam(
                          num_output=vocab, bias_term=False, axis=-1,
                          weight_filler=Filler(type="gaussian", std=0.5)),
                      **tag),
            LayerSpec(name="loss", type="SoftmaxWithLoss",
                      bottoms=("lm_head", "tokens"), tops=("loss",),
                      loss=LossParam(label_shift=1), **tag))))


def test_a_tensor_parallel_head_names_the_gathered_side(monkeypatch):
    """Column-sharded over two devices, the head's block keeps the GATHERED
    logits: the gradient's program holds one `all_gather` (the forward's; its
    transpose is the cotangent's reduce-scatter), where the bare block's
    holds two, and one product fewer; the gradients are the bare block's
    bit for bit."""
    net = _tp_head_net()
    params = net.init_params(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, 32, jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    specs = {l: {"w": P(None, "model") if l == "lm_head" else P()}
             if l != "final_norm" else {"scale": P()} for l in params}
    tp_loss = net.loss_fn("loss", tp_axis="model", tp_size=2)

    def grad():  # (a fresh function a trace)
        return jax.shard_map(
            jax.grad(lambda p, i: tp_loss(p, {"tokens": i}, None)[0]),
            mesh=mesh, in_specs=(specs, P()), out_specs=specs, check_vma=False)

    def count(jaxpr, primitive):
        return sum(e.primitive.name == primitive for e in _eqns(jaxpr))

    kept = jax.make_jaxpr(grad())(params, ids).jaxpr
    assert (count(kept, "all_gather"), _products(kept, 16)) == (1, 3)
    named, = (e for e in _eqns(kept) if e.primitive.name == "name")
    assert named.outvars[0].aval.shape == (2, 8, 32)  # not this device's 16
    got = jax.jit(grad())(params, ids)
    _without_the_name(monkeypatch)
    bare = jax.make_jaxpr(grad())(params, ids).jaxpr
    assert (count(bare, "all_gather"), _products(bare, 16)) == (2, 4)
    want = jax.jit(grad())(params, ids)
    for layer, lp in want.items():
        for name, g in lp.items():
            assert np.array_equal(np.asarray(got[layer][name]), np.asarray(g))
            assert float(jnp.max(jnp.abs(g))) > 0, (layer, name)
