"""The linear-attention hybrid (`zoo.ling3_flash`): Kimi Delta Attention in
the layers the published period gives it, latent attention with a direct
query projection in the others, head-wise output gates, experts chosen among
the best groups -- against the benchmark's plain reference
(`benchmark/configs/ling3-flash-ep64-tau4.reference.py`, which imports
nothing of the program and runs the delta rule a position at a time) at small
widths on the CPU: layer by layer, the loss and every stored parameter's
gradient, one tau-round through `ParallelTrainer.train_round`, the share
arithmetic, and what the builder refuses.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import MLA_P as LATENT_WITH_RANK
from model_cases import _params as _glm_params
from model_cases import (CTX, D, POS, ROWS, _ids, _per_row, _x, case,
                         check_layer, check_loss_and_every_gradient,
                         check_products_kept, check_round, compiled,
                         program_round, tiny_round)
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.spec import (KDAttentionParam, MLAttentionParam,
                                     MoEParam)

LING = case("ling3_flash")
ref, TINY, LAYERS, TABLE = LING.ref, LING.tiny, LING.layers, LING.table
KDA_P = KDAttentionParam(num_heads=4, head_dim=16, taps=4, lower_bound=-5.0,
                         eps=1e-6)
MLA_P = MLAttentionParam(num_heads=4, q_lora_rank=None, kv_lora_rank=32,
                         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                         rope_theta=6e6, eps=1e-6, output_gate=True)
MOE_P = MoEParam(n_routed_experts=16, experts_held=(4, 2), num_experts_per_tok=2,
                 intermediate_size=48, n_shared_experts=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True, n_group=4,
                 topk_group=2)


def _net():
    return compiled("ling3_flash")


def _params(seed, layer, bias_scale=1.0):
    p = LING.params(seed)[layer]
    if "router_bias" in p:  # a bias large enough to change who is chosen
        p = dict(p, router_bias=p["router_bias"] * bias_scale)
    if "q_conv" in p:  # taps and gates of a size that shows: decays spread
        # over (-5, 0), strengths over (0, 1), heads that differ
        p = dict(p, **{n: p[n] * 50.0 for n in ("q_conv", "k_conv", "v_conv")},
                 a=p["a"] * 20.0, dt_bias=p["dt_bias"] * 20.0,
                 A_log=0.3 * _x(seed + 13, (4,)), beta=p["beta"] * 20.0,
                 o_norm=1.0 + 0.1 * _x(seed + 11, (16,)))
    if "out_gate" in p:
        p = dict(p, out_gate=p["out_gate"] * 20.0)
    return p


# -- layer by layer against the reference ------------------------------------

#: kind -> (seed -> the layer's weights, the program's layer, the reference's
#: on one row)
LAYER_TABLE = {
    "kda": (lambda seed: _params(seed, "l0_kda"),
            lambda p, x: sl.kda(KDA_P, p, x, CTX),
            lambda p, r: ref.kda(TABLE["l0_kda"][1], p, r, "float32")),
    "mla": (lambda seed: _params(seed, "l2_attn"),
            lambda p, x: sl.mla(MLA_P, p, x, CTX),
            lambda p, r: ref.mla(TABLE["l2_attn"][1], p, r, "float32")),
    "moe": (lambda seed: _params(seed, "l1_moe", bias_scale=20.0),
            lambda p, x: sl.moe(MOE_P, p, x, CTX)[0],
            lambda p, r: ref.moe(TABLE["l1_moe"][1], p, r, "float32")[0]),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["kda", "mla", "moe"])
def test_layer_matches_the_reference(kind, policy):
    check_layer(LAYER_TABLE, kind, policy)


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_kda_on_the_kernels_equals_kda_in_jnp_loss_and_every_gradient(policy):
    """A layer whose shape is the kernels' (heads of 128, 512 positions: a
    tile of `ops.pallas_kda_shape`, four of `ops.pallas_delta_rule`) with
    every kernel under the Pallas interpreter, against the same layer in the
    `jnp` forms: the loss and every parameter's and the input's gradient."""
    from sparknet_tpu.model.layers import ApplyCtx
    wide = KDAttentionParam(num_heads=2, head_dim=128, taps=4, lower_bound=-5.0,
                            eps=1e-6)
    layer = sl.LayerSpec(name="k", type="KDAttention", kda=wide)
    p = sl.init_kdattention(jax.random.PRNGKey(5), layer, ((ROWS, 512, D),))
    p = dict(p, **{n: p[n] * 50.0 for n in ("q_conv", "k_conv", "v_conv")},
             a=p["a"] * 20.0, dt_bias=p["dt_bias"] * 20.0,
             A_log=0.3 * _x(7, (2,)), beta=p["beta"] * 20.0)
    x = _x(41, (ROWS, 512, D))
    loss = lambda ctx: jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(jnp.sin(sl.kda(wide, p, x, ctx))), argnums=(0, 1)))
    with precision.policy(policy):
        assert "kda_shape_fwd" in str(jax.make_jaxpr(
            lambda p, x: sl.kda(wide, p, x, ApplyCtx(train=True, interpret=True)))(p, x))
        want, (gp_want, gx_want) = loss(CTX)(p, x)
        got, (gp_got, gx_got) = loss(ApplyCtx(train=True, interpret=True))(p, x)
    tol = 1e-4 if policy == "float32" else 0.05
    assert float(got) == pytest.approx(float(want), rel=tol, abs=tol)
    for name in ["x"] + sorted(p):
        a, b = (gx_got, gx_want) if name == "x" else (gp_got[name], gp_want[name])
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-30)
        assert err < tol, (name, err)


def test_kda_is_causal_and_its_parts_are_what_the_formula_says():
    """Nothing at position t moves when what follows it changes; the decay
    lies in (-5, 0) and differs by head and channel; with the writing
    strength at 0 (a large negative w_beta x) nothing is written and the
    layer returns zeros."""
    p, x = _params(3, "l0_kda"), _x(23)
    got = sl.kda(KDA_P, p, x, CTX)
    moved = sl.kda(KDA_P, p, x.at[:, 20:].add(1.0), CTX)
    # (a chunk is solved as a whole: equal to rounding, not bit for bit)
    assert np.allclose(moved[:, :20], got[:, :20], atol=1e-6)
    assert not np.allclose(moved[:, 20], got[:, 20], atol=1e-3)
    a = x @ p["a"] + p["dt_bias"]
    g = -5.0 * jax.nn.sigmoid(jnp.repeat(jnp.exp(p["A_log"]), 16) * a)
    assert -5 < float(jnp.min(g)) < -4 and -1 < float(jnp.max(g)) < 0
    shut = dict(p, beta=jnp.full_like(p["beta"], -1e3))  # w_beta |x| << 0
    assert float(jnp.max(jnp.abs(sl.kda(KDA_P, shut, jnp.abs(x), CTX)))) == 0.0


def test_kda_convolutions_have_four_taps_and_silu():
    """`causal_taps` over heads-first activations equals the written-out
    loop, and position 0 sees its own tap alone."""
    s, w = _x(5, (ROWS, 4, POS, 16)), _x(6, (4, 16, 4))
    got = np.asarray(sl.causal_taps(s, w))
    want = np.zeros_like(got)
    for t in range(POS):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, :, t] += np.asarray(w[None, :, :, j] * s[:, :, t - 3 + j])
    assert np.allclose(got, want, atol=1e-5)
    assert np.allclose(got[:, :, 0], s[:, :, 0] * w[None, :, :, 3], atol=1e-6)
    # the short convolution's three-dimensional call is untouched
    s3, w3 = _x(7), _x(8, (D, 3))
    assert np.allclose(sl.causal_taps(s3, w3)[:, 1],
                       s3[:, 1] * w3[:, 2] + s3[:, 0] * w3[:, 1], atol=1e-6)


def test_direct_query_mla_has_no_latent_and_the_ranked_one_is_unchanged():
    p = _params(4, "l2_attn")
    assert set(p) == {"q", "kv_a", "kv_a_norm", "kv_b", "out_gate", "o"}
    got = jax.eval_shape(lambda k: sl.init_mla(k, MLA_P, D), jax.random.PRNGKey(0))
    assert {n: tuple(v.shape) for n, v in got.items()} == ref.param_shapes(LAYERS)["l2_attn"]
    for rank in (0, None):
        same = MLAttentionParam(**{**MLA_P.__dict__, "q_lora_rank": rank})
        assert np.array_equal(sl.mla(same, p, _x(24), CTX), sl.mla(MLA_P, p, _x(24), CTX))
    # with the gate wide open (sigmoid -> 1) the layer is the ungated one
    open_gate = dict(p, out_gate=jnp.zeros_like(p["out_gate"]))
    ungated = MLAttentionParam(**{**MLA_P.__dict__, "output_gate": False})
    x = _x(25)
    assert np.allclose(sl.mla(MLA_P, open_gate, x, CTX),
                       0.5 * sl.mla(ungated, open_gate, x, CTX), atol=1e-6)
    # a latent of rank 24 still makes q_a, q_a_norm, q_b and no gate
    ranked = jax.eval_shape(lambda k: sl.init_mla(k, LATENT_WITH_RANK, D),
                            jax.random.PRNGKey(0))
    assert set(ranked) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o"}


# -- group-limited routing ---------------------------------------------------

def _route_by_loop(p, params, x, groups, kept, k):
    """The choice by a plain loop over positions and groups, in numpy."""
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(params["router"], np.float64))))
    choice = s + np.asarray(params["router_bias"], np.float64)
    size = choice.shape[1] // groups
    out = []
    for t in range(choice.shape[0]):
        score = [np.sort(choice[t, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(groups)]
        best = sorted(range(groups), key=lambda g: -score[g])[:kept]
        allowed = [e for g in best for e in range(g * size, (g + 1) * size)]
        out.append(sorted(allowed, key=lambda e: -choice[t, e])[:k])
    return np.asarray(out), s


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_grouped_route_equals_a_plain_loop_over_groups(seed):
    p = _params(seed, "l1_moe", bias_scale=20.0)
    x = _x(seed + 30, (48, D))
    idx, w = sl.route(MOE_P, p, x)
    want, s = _route_by_loop(MOE_P, p, x, 4, 2, 2)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    assert np.allclose(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)
    # every chosen expert lies in one of two groups, and the choice is not
    # the plain top 2 everywhere (the groups bind)
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(idx))
    plain, _ = sl.route(MoEParam(**{**MOE_P.__dict__, "n_group": 1, "topk_group": 1}), p, x)
    ref_idx, ref_w = ref.route(TABLE["l1_moe"][1], p, x)
    assert np.array_equal(idx, ref_idx) and np.allclose(w, ref_w, atol=1e-7)
    wide = MoEParam(**{**MOE_P.__dict__, "topk_group": 1})
    narrow, _ = sl.route(wide, p, x)
    assert all(len({e // 4 for e in row}) == 1 for row in np.asarray(narrow))
    assert not np.array_equal(np.sort(np.asarray(narrow), -1), np.sort(np.asarray(plain), -1))


@pytest.mark.parametrize("seed", [1, 2])
def test_one_group_reproduces_the_plain_choice_bit_for_bit(seed):
    """`n_group` 1 is today's `route()`: the top k of score + bias, written
    here as it stood."""
    p = _glm_params(seed, bias_scale=20.0)
    one = MoEParam(n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
                   intermediate_size=48, routed_scaling_factor=1.8)
    assert (one.n_group, one.topk_group) == (1, 1)
    x = _x(seed + 40, (64, D))
    idx, w = sl.route(one, p, x)
    s = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(s + p["router_bias"], 2)
    ws = jnp.take_along_axis(s, want, axis=-1)
    assert np.array_equal(idx, want)
    assert np.array_equal(w, ws / (jnp.sum(ws, -1, keepdims=True) + 1e-20) * 1.8)
    # all the groups kept is the plain choice too
    every = MoEParam(**{**one.__dict__, "n_group": 4, "topk_group": 4})
    assert np.array_equal(sl.route(every, p, x)[0], want)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(seed):
    """The parts of an expert layer's result that the eight shares give (2 of
    16 experts each), their shared expert counted once, equal the uncut
    reference's layer: all 16 experts held, the same groups."""
    uncut = ref.layer_table(dict(TINY, num_experts=16, share=dict(
        TINY["share"], experts_held=[0, 16])))
    a = {n: x for n, k, x in uncut}["l1_moe"]
    p = ref.init_params(seed, uncut)["l1_moe"]
    p = dict(p, router_bias=p["router_bias"] * 20.0)
    x = _x(seed + 40)
    whole = _per_row(lambda r: ref.moe(a, p, r, "float32")[0], x)
    shared = _per_row(lambda r: ref.swiglu(r, p["shared_gate"], p["shared_up"],
                                           p["shared_down"], "float32"), x)
    total, landed = -7 * shared, 0.0  # eight shares carry it eight times
    for first in range(0, 16, 2):
        mine = dict(p, **{k: p[k][first:first + 2] for k in
                          ("experts_gate", "experts_up", "experts_down")})
        part, counters, _ = sl.moe(MoEParam(**{
            **MOE_P.__dict__, "experts_held": (first, 2)}), mine, x, CTX)
        total = total + part
        landed += float(counters[0])
        assert float(counters[1]) == 0
    assert landed == ROWS * POS * 2, "every routed slot lands on exactly one share"
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5 * float(jnp.max(jnp.abs(whole)))


# -- the whole model ---------------------------------------------------------

@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("bfloat16", 1)])
def test_loss_and_every_stored_gradient_match_the_reference(policy, seed):
    assert _net().param_layers() == list(ref.param_shapes(LAYERS))
    # in bfloat16 a position near a tie chooses another expert: the held
    # experts' own gradients differ by whole slots
    f32 = policy == "float32"
    loose = lambda name: 0.6 if name.startswith("experts_") else 0.3
    _, _, want_grads = check_loss_and_every_gradient(
        "ling3_flash", policy, LING.params(seed), _ids(seed + 70),
        loss_tol=2e-5 if f32 else 2e-3, grad_tol=5e-5 if f32 else loose)
    seen = {name for lp in want_grads.values() for name in lp}
    assert {"q_conv", "k_conv", "v_conv", "a", "dt_bias", "A_log", "beta",
            "out_gate", "o_norm", "q", "kv_b", "shared_down"} <= seen


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tmp_path):
    from sparknet_tpu.obs import device as obs_device

    # (`mtp_weight`: accepted, unread)
    case_ = tiny_round("ling3_flash", tmp_path, tau=2, mtp_weight=0.3)
    trainer = case_.make_trainer()
    _, got = program_round("ling3_flash", trainer, case_.params, case_.ids)
    check_round(got, case_.want, rel=3e-4)
    assert set(case_.want["chosen"]) == {"l1_moe", "l2_moe", "l3_moe"}
    assert set(trainer.counter_values()) == {
        "l1_moe_counters", "l2_moe_counters", "l3_moe_counters"}
    # the round's account of itself: the one latent block keeps its core's
    # output, the delta-rule blocks keep nothing; 32 positions are ONE chunk,
    # so the scan over chunks has one trip and the compiler unrolls it (the
    # loops themselves: the lone layer's test below)
    report = obs_device.program_report("train_round")
    assert report["recompute"][sl.ATTN_CORE]["kept_bytes"] == ROWS * POS * 4 * 16 * 4
    check_products_kept("ling3_flash", report, tau=2)
    delta = report["delta_rule"]
    assert set(delta) == {"loops", "trips", "kernel_calls", "shape_kernel_calls",
                          "carried_bytes", "instructions", "bytes", "kept_bytes"}
    assert delta["loops"] == 0 and delta["instructions"] > 0 and delta["bytes"] > 0
    # the `jnp` forms, of the rule and of the stage before it: no TPU, narrow heads
    assert delta["kernel_calls"] == 0 and delta["shape_kernel_calls"] == 0
    # the three delta-rule blocks keep their layers' results, float32 here
    assert delta["kept_bytes"] == 3 * ROWS * POS * D * 4
    assert obs_device.program_part("delta_rule")["train_round"] == delta
    scopes = {op["scope"] for op in report["ops"].values()}
    # (a row at a time: the six scopes lie under the rows' loop)
    for part in ("in_proj", "conv", "gates", "delta", "out_gate", "out_proj"):
        assert any("KDAttention/l0_kda" in s and part in s.split("/")
                   for s in scopes), part
    assert any("MLAttention/l2_attn)/core" in s for s in scopes)
    assert not any("KDAttention" in s and "core" in s.split("/") for s in scopes)


def test_the_delta_rule_compiles_to_one_loop_over_chunks_a_pass():
    """A lone layer at 1,024 positions (two segments of eight chunks of 64)
    in a recomputation block, forward + backward: under `delta` the scan
    over segments and, inside it, the scan over a segment's chunks, each
    once forward, once made again and once backward, with their trip counts
    read from the text; what a trip carries holds the float32 state."""
    from sparknet_tpu.obs import device as obs_device
    p, x = _params(1, "l0_kda"), _x(31, (ROWS, 1024, D))

    def loss(p, x):
        with jax.named_scope("tau_step"), jax.named_scope("KDAttention/l0_kda"):
            return jnp.sum(jnp.sin(jax.checkpoint(
                lambda p, x: sl.kda(KDA_P, p, x, CTX))(p, x)))

    ops = obs_device.parse_hlo_ops(jax.jit(jax.grad(loss)).lower(p, x).compile().as_text())
    got = obs_device.delta_rule(ops, sl.DELTA_SCOPES, kept_bytes=0)
    assert got["loops"] >= 6 and got["trips"] >= 2 * 3 + 8 * 3, got
    assert got["carried_bytes"] >= ROWS * 4 * 16 * 16 * 4
    assert got["instructions"] > 0 and got["bytes"] > 0
    # heads of 16: the `jnp` forms, here and on a TPU
    assert got["kernel_calls"] == 0 and got["shape_kernel_calls"] == 0
    assert obs_device.delta_rule(ops, {}) == {}
    # a net of other layers: nothing under such a scope
    assert obs_device.delta_rule(ops, {"GQAttention": "delta"})["loops"] == 0


# -- the builder -------------------------------------------------------------

def test_zoo_follows_the_published_period_and_names_what_a_block_keeps():
    spec = LING.spec()
    ops = [(l.name, l.type) for l in spec.layers if l.type in ("KDAttention", "MLAttention")]
    # published layers 3, 4, 5, 6: (j + 1) % 6 == 0 at 5
    assert ops == [("l0_kda", "KDAttention"), ("l1_kda", "KDAttention"),
                   ("l2_attn", "MLAttention"), ("l3_kda", "KDAttention")]
    from_zero = zoo.ling3_flash(dict(TINY, num_hidden_layers=7, share=dict(
        TINY["share"], first_layer=0)), rows=ROWS, positions=POS)
    assert [l.type == "MLAttention" for l in from_zero.layers
            if l.type in ("KDAttention", "MLAttention")] == [False] * 5 + [True, False]
    ff = [(l.name, l.type) for l in spec.layers if l.type in ("GatedMLP", "MoE")]
    assert ff == [("l0_mlp", "GatedMLP"), ("l1_moe", "MoE"), ("l2_moe", "MoE"),
                  ("l3_moe", "MoE")]
    moe = spec.layer_by_name("l1_moe").moe
    assert (moe.n_routed_experts, moe.experts_held, moe.n_shared_experts, moe.n_group,
            moe.topk_group, moe.routed_scaling_factor) == (16, (4, 2), 1, 4, 2, 2.5)
    latent = spec.layer_by_name("l2_attn").mla
    assert latent.q_lora_rank is None and latent.output_gate
    assert spec.layer_by_name("l0_kda").kda == KDA_P
    head = spec.layer_by_name("lm_head")
    assert head.param_from is None and not head.inner_product.transposed  # untied
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "l2", "l3", "head"}
    net = _net()
    assert net.kept_makers() == {  # nothing marks what makes kda_out
        sl.ATTN_CORE: "splash_mha_fwd", sl.MLP_PRE: sl.MLP_PRE, sl.IP_OUT: sl.IP_OUT,
        sl.MOE_ROUTE: "router"}
    assert net.attention_scopes() == ({"KDAttention": "", "MLAttention": ""}, POS)
    assert net.delta_scopes() == ({"KDAttention": "delta"}, (sl.KDA_OUT,))
    assert sl.KEPT_NAMES["KDAttention"] == (sl.KDA_OUT,)
    assert sum(int(np.prod(s)) for lp in ref.param_shapes(LAYERS).values()
               for s in lp.values()) == sum(
        int(np.prod(v.shape)) for lp in jax.eval_shape(
            net.init_params, jax.random.PRNGKey(0)).values() for v in lp.values())
    assert zoo.SEQUENCE_MODELS["ling3_flash"] is zoo.ling3_flash
    # the other builders' nets have no delta rule to report
    assert compiled("lfm2_moe").delta_scopes() == ({}, ())


@pytest.mark.parametrize("change,match", [
    ({"share": {**TINY["share"], "experts_held": [4, 4]}}, "disagree"),
    ({"share": {**TINY["share"], "vocab_rows": [0, 128]}}, "disagree"),
    # published layers 3 to 7: the clamp is on at layer 7
    ({"num_hidden_layers": 5}, "swiglu"),
    ({"share_expert_swiglu_limit_list": [0, 0, 0, 7, 0, 0, 0, 0]}, "swiglu"),
    ({"gated_attention_proj_granularity_type": "element_wise"}, "asks for something else"),
    ({"kda_safe_gate": False}, "asks for something else"),
    ({"q_lora_rank": 24}, "asks for something else"),
])
def test_zoo_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        zoo.ling3_flash(dict(TINY, **change), rows=ROWS, positions=POS)


def test_resolve_spec_refuses_an_unknown_model_type(tmp_path):
    from sparknet_tpu.apps.train_loop import resolve_spec
    from sparknet_tpu.utils.config import RunConfig
    path = tmp_path / "other.json"
    path.write_text(json.dumps(dict(TINY, model_type="ling9_flash")))
    with pytest.raises(ValueError, match="model_type 'ling9_flash' is not one of"):
        resolve_spec(RunConfig.from_dict({"model": str(path), "local_batch": ROWS}))
