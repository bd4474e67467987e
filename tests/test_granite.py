"""Granite-4.0-H-Micro (`zoo.granitemoehybrid`) on rows that hold several
documents, against the benchmark's plain reference
(`benchmark/configs/granite4-h-micro-pp4-tau4.reference.py`, which imports
nothing of the program) at a tiny size on the CPU: two Mamba-2 mixers of one
group and one attention without a rotary turn, a dense SwiGLU after each,
the four multipliers, a tied head, three to four documents a row. The loss,
every gradient leaf, the state after a tau-round through `build_trainer`;
what the builder refuses; a layer fed a row that is one document gives the
bits it gave without ids; and the six accepted sequence models' training
jaxprs are what they were before this model came (a net fed no document
ids is the net it was).
"""
from __future__ import annotations

import functools
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_cases as mc
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import ApplyCtx
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (GQAttentionParam, InputSpec, LayerSpec,
                                     Mamba2Param)

ROWS, POS = mc.ROWS, mc.POS
#: hidden 64; Mamba-2 with 8 heads of 16 in ONE group of state 16 (expand 2),
#: chunks of 16: 32 positions are two; attention with 4 query heads over 2 of
#: 16, scores times 0.1 (1/sqrt(16) is 0.25); SwiGLUs of 96; the multipliers
#: as published; vocabulary 256; mamba, attention, mamba
TINY = {
    "model_type": "granitemoehybrid", "hidden_size": 64, "intermediate_size": 96,
    "shared_intermediate_size": 96, "attention_bias": False,
    "attention_multiplier": 0.1, "embedding_multiplier": 12, "hidden_act": "silu",
    "layer_types": ["mamba", "attention", "mamba"], "logits_scaling": 8,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 8, "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts_per_tok": 0,
    "num_local_experts": 0, "num_hidden_layers": 3, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "vocab_size": 256, "seq_len": 32,
    "share": {"first_layer": 0, "vocab_rows": [0, 256],
              "chips_sharing_the_vocabulary": 4, "pipeline_stages": 4}}
REF = mc.reference("granite4-h-micro-pp4-tau4")
LAYERS = REF.layer_table(TINY)
#: four documents in the first row (one of a single position), three in the
#: second; ids that are not consecutive
DOCS = np.array([[3] * 5 + [4] * 16 + [9] * 1 + [11] * 10,
                 [7] * 17 + [8] * 3 + [20] * 12], np.int32)
CTX = ApplyCtx(train=True)


@functools.cache
def _net():
    return CompiledNet.compile(zoo.granitemoehybrid(TINY, ROWS, POS))


@functools.cache
def _params(seed=3):
    # 0.16 at a hidden size of 64 gives the projections the size 0.02 gives
    # them at 2,048, so the scan adds what the skip does
    return REF.init_params(seed, LAYERS, std=0.16)


def _ids(seed, shape=(ROWS, POS)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256, jnp.int32)


@functools.cache
def _program(policy):
    loss_fn = _net().loss_fn("loss")
    fn = jax.jit(jax.value_and_grad(
        lambda p, ids, docs: loss_fn(p, {"tokens": ids, "doc_ids": docs}, None),
        has_aux=True))

    def under_policy(*a):
        with precision.policy(policy):
            return fn(*a)

    return under_policy


@functools.cache
def _reference(**kw):
    @jax.jit
    def fn(params, ids, docs):
        targets = jnp.sum(jax.vmap(REF.targets_of)(docs))
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: sum(
                REF.row_loss(p, ids[r], docs[r], layers=LAYERS, targets=targets, **kw)
                for r in range(ids.shape[0])))(params)
    return fn


# -- the net -------------------------------------------------------------------

def test_the_builder_makes_two_sublayers_a_layer_and_feeds_the_ids_where_they_cut():
    spec = zoo.granitemoehybrid(TINY, ROWS, POS)
    assert zoo.SEQUENCE_MODELS["granitemoehybrid"] is zoo.granitemoehybrid
    assert spec.inputs == (InputSpec("tokens", (ROWS, POS), "int32"),
                           InputSpec("doc_ids", (ROWS, POS), "int32"))
    kinds = [l.type for l in spec.layers if l.type in ("Mamba2", "GQAttention", "GatedMLP")]
    assert kinds == ["Mamba2", "GatedMLP", "GQAttention", "GatedMLP", "Mamba2", "GatedMLP"]
    reads_ids = {l.name for l in spec.layers if "doc_ids" in l.bottoms}
    assert reads_ids == {"l0_mamba", "l1_attn", "l2_mamba", "loss"}
    by = {l.name: l for l in spec.layers}
    assert by["l0_mamba"].tops == ("l0_mamba", "l0_mamba_counters")
    assert by["l0_mamba"].mamba2 == Mamba2Param(
        num_heads=8, head_dim=16, n_groups=1, state_size=16, taps=4, chunk_size=16)
    assert by["l1_attn"].gqa == GQAttentionParam(
        num_heads=4, num_kv_heads=2, head_dim=16, rotary=False, qk_norm=False,
        score_scale=0.1)
    assert by["embed"].embed.multiplier == 12.0
    assert by["lm_head"].param_from == "embed" and by["lm_head"].inner_product.transposed
    assert by["lm_head"].inner_product.divisor == 8.0
    for name in ("l0_res", "l0_mlp_res", "l1_res", "l2_mlp_res"):
        assert by[name].eltwise.coeff == (1.0, 0.22), name
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "l2", "head"}
    net = _net()
    assert net.counter_blobs() == {"l0_mamba_counters": ("doc_boundaries",),
                                   "l2_mamba_counters": ("doc_boundaries",)}
    assert net.ssd_kernels() == {"l0_mamba": {"documents": 1}, "l2_mamba": {"documents": 1}}
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == REF.param_shapes(LAYERS)


@pytest.mark.parametrize("change,says", [
    ({"num_local_experts": 8, "num_experts_per_tok": 2}, "no experts"),
    ({"position_embedding_type": "rope"}, "nope"),
    ({"attention_bias": True}, "no other bias"),
    ({"mamba_proj_bias": True}, "no other bias"),
    ({"mamba_conv_bias": False}, "biased"),
    ({"tie_word_embeddings": False}, "tied head"),
    ({"layer_types": ["mamba", "full_attention", "mamba"]}, "mamba | attention"),
    ({"layer_types": ["mamba", "attention"]}, "each of the 3 layers"),
    ({"vocab_size": 128}, "share block"),
])
def test_the_builder_refuses_what_it_does_not_build(change, says):
    with pytest.raises(ValueError, match=re.escape(says)):
        zoo.granitemoehybrid({**TINY, **change}, ROWS, POS)


def test_a_nemotron_mixer_counts_nothing_and_the_trainer_carries_no_blob_for_it():
    """`COUNTER_TOPS` names Mamba2, but a mixer fed no document ids has one
    top: a net without ids has no counter blob of a mixer's."""
    net = mc.compiled("nemotron_h")
    assert all(not b.endswith("mamba_counters") for b in net.counter_blobs())
    assert set(net.ssd_kernels()) == {"l0_mamba", "l2_mamba"}
    assert all(s["documents"] == 0 for s in net.ssd_kernels().values())


# -- against the reference -------------------------------------------------------

@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf_against_the_reference(policy):
    params, ids, docs = _params(), _ids(1), jnp.asarray(DOCS)
    (loss, blobs), grads = _program(policy)(params, ids, docs)
    want, want_grads = _reference()(params, ids, docs)
    assert abs(float(loss) - float(want)) < (2e-5 if policy == "float32" else 2e-3)
    assert set(grads) == set(want_grads) and "lm_head" not in grads
    for layer, lp in want_grads.items():
        assert set(grads[layer]) == set(lp)
        for name, g in lp.items():
            err = mc.norm_err(grads[layer][name], g)
            assert err < (2e-5 if policy == "float32" else 0.05), (layer, name, err)
    # every mixer counted the rows' five boundaries
    assert {b: float(v[0]) for b, v in blobs.items() if b.endswith("_counters")} == {
        "l0_mamba_counters": 5.0, "l2_mamba_counters": 5.0}


def test_the_loss_is_one_mean_over_the_positions_whose_target_is_of_their_document():
    """57 targets of 64 positions: the rows' last positions and five
    documents' last have none. The reference with its mixers leaking keeps
    those targets and gives ANOTHER loss; the program is not that one. And
    the id of a one-position document reaches that position's stream alone
    (its time step is in the chunk's running sums, whose differences the
    positions after it take: they move in their last bits and no further)."""
    params, ids, docs = _params(), _ids(1), jnp.asarray(DOCS)
    assert int(jnp.sum(jax.vmap(REF.targets_of)(docs))) == 64 - 2 - 5 == 57
    (loss, _), grads = _program("float32")(params, ids, docs)
    leak, leak_grads = _reference(leak=True)(params, ids, docs)
    assert abs(float(loss) - float(leak)) > 1e-3
    assert mc.norm_err(grads["l0_mamba"]["in_proj"], leak_grads["l0_mamba"]["in_proj"]) > 0.1
    stream = jax.jit(lambda i: _net().apply(
        params, {"tokens": i, "doc_ids": docs})["x3"])
    assert DOCS[0, 21] == 9 and list(DOCS[0]).count(9) == 1
    moved = np.array(jnp.max(jnp.abs(
        stream(ids.at[0, 21].set((ids[0, 21] + 1) % 256)) - stream(ids)), axis=-1))
    assert moved[0, 21] > 1.0
    moved[0, 21] = 0.0
    assert moved.max() < 1e-5 and (moved[0, :21] == 0).all() and (moved[1] == 0).all()


def test_one_round_through_the_trainer_gives_the_references_state(tmp_path):
    """tau = 2 steps through `RunConfig` -> `resolve_spec` -> `build_trainer`
    -> `train_round`, float32: every leaf of the parameters and of the
    momentum after the round, the loss, and the boundaries the round's
    mixers counted."""
    from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.utils.config import RunConfig
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = RunConfig.from_dict({
        "model": str(path), "tau": 2, "local_batch": ROWS, "precision": "float32",
        "solver": mc.SOLVER, "n_devices": 1, "health": {"enabled": False}})
    spec = resolve_spec(cfg)
    assert spec.name == "granitemoehybrid"
    trainer = build_trainer(cfg, spec, make_mesh(1))
    params = _params(8)
    ids = np.asarray(_ids(78, (2, ROWS, POS)))
    docs = np.stack([DOCS, DOCS[::-1]])
    want = REF.round_reference(params, lambda t, w: (ids[t], docs[t]), tau=2,
                               solver=mc.SOLVER, layers=LAYERS)
    state, loss = trainer.train_round(
        trainer.state_from_params(params),
        trainer.place_batches({"tokens": ids, "doc_ids": docs}), jax.random.PRNGKey(0))
    assert abs(float(loss) - want["loss"]) < 2e-5
    norm = lambda x: float(jnp.linalg.norm(x))
    for layer, lp in params.items():
        for name, p0 in lp.items():
            key = f"{layer}/{name}"
            upd = norm(state.params[layer][name][0] - p0)
            assert abs(upd - want["update_norms"][key]) < 2e-4 * max(
                want["update_norms"][key], 1e-3), key
            mom = norm(state.momentum[layer][name][0])
            assert abs(mom - want["momentum_norms"][0][key]) < 2e-4 * max(
                want["momentum_norms"][0][key], 1e-3), key
    assert mc.norm_err(state.momentum["l0_mamba"]["in_proj"][0], want["probe"][0]) < 2e-5
    counted = {b: float(v[0]) for b, v in trainer.last_counters.items()}
    assert counted == {"l0_mamba_counters": 10.0, "l2_mamba_counters": 10.0}
    assert trainer.counter_values()["l0_mamba_counters"] == {"doc_boundaries": 10.0}


# -- a row that is one document ---------------------------------------------------

def test_layers_fed_a_row_that_is_one_document_give_the_bits_they_gave_without_ids():
    x = mc._x(5)
    one = jnp.full((ROWS, POS), 7, jnp.int32)
    by = {l.name: l for l in _net().spec.layers}
    params = _params()
    for policy in ("float32", "bfloat16"):
        with precision.policy(policy):
            u = precision.cast_in(x)
            m = by["l0_mamba"].mamba2
            plain = sl.mamba2(m, params["l0_mamba"], u, CTX)
            under, counted = sl.mamba2(m, params["l0_mamba"], u, CTX, one)
            assert bool(jnp.all(plain == under)) and float(counted[0]) == 0.0
            a = by["l1_attn"].gqa
            assert bool(jnp.all(sl.gqa(a, params["l1_attn"], u, CTX)
                                == sl.gqa(a, params["l1_attn"], u, CTX, one)))
    logits = jax.random.normal(jax.random.PRNGKey(6), (ROWS, POS, 256))
    from sparknet_tpu.model import layers
    loss = by["loss"]
    ids = _ids(2)
    assert float(layers.apply_softmaxwithloss(loss, None, (logits, ids), CTX)[0]) \
        == float(layers.apply_softmaxwithloss(loss, None, (logits, ids, one), CTX)[0])


def test_the_multipliers_leave_a_net_without_them_as_it_was():
    """1.0 everywhere is no multiply at all: the accepted models' layers
    trace to the equations they traced to."""
    from sparknet_tpu.model import layers
    from sparknet_tpu.model.spec import EmbedParam, InnerProductParam
    embed = LayerSpec(name="e", type="Embed", embed=EmbedParam(num_embeddings=256, dim=64))
    text = str(jax.make_jaxpr(lambda w, i: sl.apply_embed(embed, {"w": w}, (i,), CTX))(
        jnp.zeros((256, 64)), _ids(0)))
    assert "mul" not in text
    head = LayerSpec(name="h", type="InnerProduct", inner_product=InnerProductParam(
        num_output=256, bias_term=False, axis=-1, transposed=True))
    text = str(jax.make_jaxpr(lambda w, x: layers.apply_innerproduct(
        head, {"w": w}, (x,), CTX))(jnp.zeros((256, 64)), mc._x(0)))
    assert "div" not in text and "mul" not in text


# -- the accepted models are what they were ----------------------------------------

#: sha256 of the text of the jaxpr of each accepted sequence model's training
#: gradient at its tiny size (`model_cases`), bfloat16 policy, memory
#: addresses struck out: taken from the tree BEFORE this model and its
#: document cuts came (PR 48's commit), where this PR's tree gave the same
#: texts. A change to what one of these models traces to changes its digest:
#: say in the PR that makes it why, and put the new digest here. PR 52: the
#: five models with expert layers trace to a new text (`route`'s sigmoid
#: after the selection, the `moe_route` names, their blocks' policies, a
#: plan that holds the side its sums read, the dispatch's transpose that
#: weighs every landed row 1); EvaByte, which has none, traced to the text
#: it had until its blocks' policies became one object a set of names
#: (`net._keep`), since when all six print the sub-jaxprs their blocks share
#: once
JAXPR_DIGESTS = {
    "glm4_moe_lite": "cf39d5cde8159063080350bf4ed670dfcf4529dd7f10c0c4ad46708d8cc91131",
    "lfm2_moe": "dde13ab2057848f86eaa2a09675079659835e51a356913fd2e555d0715c53765",
    "ling3_flash": "af48c0eec711a70842bbae9f6efd307a3a6252240736ccd0d55dc73b69d9496d",
    "evabyte": "a2dd66d1827281f6a7ea89bb495e9bfe1a75fbe94a9422c239b6df8ffadefd0d",
    "nemotron_h": "c209c2bcc90d8724144320f5e94c4704a76e7ec89466fb9ce57d70a1490da476",
    "smallthinker": "bc16078fca367b2bffae3d4371a6b5d5ef8b1ed6beb3426215eef838c732e187",
}


@pytest.mark.parametrize("model", sorted(JAXPR_DIGESTS))
def test_an_accepted_models_training_jaxpr_is_what_it_was(model):
    c = mc.case(model)
    loss_fn = mc.compiled(model).loss_fn("loss")
    with precision.policy("bfloat16"):
        text = str(jax.make_jaxpr(jax.grad(
            lambda p, i: loss_fn(p, {"tokens": i}, None)[0]))(c.params(1), c.ids(2)))
    assert "doc_ids" not in text
    digest = hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()
    assert digest == JAXPR_DIGESTS[model], digest
