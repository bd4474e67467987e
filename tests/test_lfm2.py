"""The hybrid sequence model (`zoo.lfm2_moe`): gated short convolutions among
grouped-query attention, routed experts without a shared one, a tied head --
against the benchmark's plain reference
(`benchmark/configs/lfm2-8b-a1b-ep4-tau4.reference.py`, which imports nothing
of the program) at small widths on the CPU: layer by layer, the loss and every
stored parameter's gradient, one tau-round through
`ParallelTrainer.train_round`, the share arithmetic, and what the layers
needed of the core, the head and the grouped products' tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (CTX, D, POS, ROWS, _ids, _per_row, _x, case,
                         check_layer, check_loss_and_every_gradient,
                         check_products_kept, check_round, check_routing_kept,
                         compiled, program_round, tiny_round)
from sparknet_tpu import zoo
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import LAYER_IMPLS
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (GQAttentionParam, InnerProductParam,
                                     LayerSpec, MoEParam, ShortConvParam)

LFM2 = case("lfm2_moe")
ref, TINY, LAYERS, TABLE = LFM2.ref, LFM2.tiny, LFM2.layers, LFM2.table
GQA_P = GQAttentionParam(num_heads=4, num_kv_heads=2, head_dim=16,
                         rope_theta=1e6, eps=1e-5)
MOE_P = MoEParam(n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
                 intermediate_size=48, n_shared_experts=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 norm_topk_eps=1e-6)


def _net():
    return compiled("lfm2_moe")


def _params(seed, layer, bias_scale=1.0):
    p = LFM2.params(seed)[layer]
    if "router_bias" in p:  # a bias large enough to change who is chosen
        p = dict(p, router_bias=p["router_bias"] * bias_scale)
    if "conv" in p:  # taps of the size of a gate, so that each one shows
        p = dict(p, conv=p["conv"] * 50.0)
    if "q_norm" in p:  # scales that are not all ones
        p = dict(p, q_norm=1.0 + 0.1 * _x(seed + 11, (16,)),
                 k_norm=1.0 + 0.1 * _x(seed + 12, (16,)))
    return p


def _apply(kind, layer, params, x):
    return LAYER_IMPLS[kind][1](layer, params, (x,), CTX)[0]


# -- layer by layer against the reference ------------------------------------

#: kind -> (seed -> the layer's weights, the program's layer, the reference's
#: on one row)
LAYER_TABLE = {
    "shortconv": (lambda seed: _params(seed, "l0_conv"),
                  lambda p, x: _apply("ShortConv", LayerSpec(
                      name="c", type="ShortConv",
                      shortconv=ShortConvParam(taps=3)), p, x),
                  lambda p, r: ref.shortconv(TABLE["l0_conv"][1], p, r, "float32")),
    "gqa": (lambda seed: _params(seed, "l1_attn"),
            lambda p, x: sl.gqa(GQA_P, p, x, CTX),
            lambda p, r: ref.gqa(TABLE["l1_attn"][1], p, r, "float32")),
    "moe": (lambda seed: _params(seed, "l1_moe", bias_scale=20.0),
            lambda p, x: sl.moe(MOE_P, p, x, CTX)[0],
            lambda p, r: ref.moe(TABLE["l1_moe"][1], p, r, "float32")[0]),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["shortconv", "gqa", "moe"])
def test_layer_matches_the_reference(kind, policy):
    check_layer(LAYER_TABLE, kind, policy)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_shortconv_equals_a_written_out_loop_and_is_causal(seed):
    """Position t's output from the formula, one position and one tap at a
    time; positions 0 and 1 see zeros where the row has not begun; nothing
    at position t moves when what follows it changes."""
    p = {k: np.asarray(v, np.float64) for k, v in _params(seed, "l0_conv").items()}
    x = np.asarray(_x(seed + 20), np.float64)
    want = np.zeros_like(x)
    for r in range(ROWS):
        bcz = x[r] @ p["in_proj"]
        b, c, z = bcz[:, :D], bcz[:, D:2 * D], bcz[:, 2 * D:]
        s = b * z
        for t in range(POS):
            conv = np.zeros(D)
            for j in range(3):
                if t - 2 + j >= 0:
                    conv += p["conv"][:, j] * s[t - 2 + j]
            want[r, t] = (c[t] * conv) @ p["out_proj"]
    layer = LayerSpec(name="c", type="ShortConv", shortconv=ShortConvParam(taps=3))
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_apply("ShortConv", layer, p32, jnp.asarray(x, jnp.float32)))
        later = x.copy()
        later[:, 20:] += 1.0
        moved = np.asarray(_apply("ShortConv", layer, p32, jnp.asarray(later, jnp.float32)))
    assert np.max(np.abs(got - want)) < 2e-5 * np.max(np.abs(want))
    assert np.array_equal(moved[:, :20], got[:, :20])
    assert not np.allclose(moved[:, 20], got[:, 20])
    # the taps alone: position 0 sees its own tap only, position 1 two
    s, w = _x(seed + 30), jnp.asarray(p["conv"], jnp.float32)
    taps = sl.causal_taps(s, w)
    assert np.allclose(taps[:, 0], s[:, 0] * w[:, 2], atol=1e-6)
    assert np.allclose(taps[:, 1], s[:, 1] * w[:, 2] + s[:, 0] * w[:, 1], atol=1e-6)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_grouped_core_equals_the_same_heads_repeated(seed):
    """Query heads 2g, 2g + 1 read key/value head g: the core over 4 query
    and 2 key/value heads equals the core over 4 and 4 with every key/value
    head written out twice, and so do the gradients (a key/value head's is
    the sum over its group)."""
    q, k, v = (_x(seed + i, (ROWS, h, POS, 16)) for i, h in ((0, 4), (1, 2), (2, 2)))
    rep = lambda t: jnp.repeat(t, 2, axis=1)
    grouped = lambda q, k, v: sl.attention_core(q, k, v, CTX)
    spread = lambda q, k, v: sl.attention_core(q, rep(k), rep(v), CTX)
    assert np.allclose(grouped(q, k, v), spread(q, k, v), atol=1e-6)
    # head 3 reads key/value head 1 and not head 0
    other = grouped(q, k.at[:, 0].add(1.0), v.at[:, 0].add(1.0))
    assert np.array_equal(other[:, 2:], grouped(q, k, v)[:, 2:])
    assert not np.allclose(other[:, :2], grouped(q, k, v)[:, :2])
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    g1 = jax.grad(loss(grouped), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(spread), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert np.allclose(a, b, atol=2e-6)


def test_gqa_rotary_is_over_the_whole_head_on_contiguous_halves():
    x = _x(5, (POS, 4, 16))
    want = ref.rotary(x, 1e6)
    got = sl.rotary(jnp.transpose(x, (1, 0, 2))[None], 1e6, 16)[0]
    assert np.allclose(jnp.transpose(got, (1, 0, 2)), want, atol=1e-6)
    assert np.allclose(want[0], x[0])  # position 0 is not turned


# -- the whole model ---------------------------------------------------------

@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("float32", 3), ("bfloat16", 1)])
def test_loss_and_every_stored_gradient_match_the_reference(policy, seed):
    assert _net().param_layers() == list(ref.param_shapes(LAYERS))
    f32 = policy == "float32"
    _, grads, want_grads = check_loss_and_every_gradient(
        "lfm2_moe", policy, LFM2.params(seed), _ids(seed + 70),
        loss_tol=2e-5 if f32 else 2e-3, grad_tol=2e-5 if f32 else 0.3)
    assert "lm_head" not in grads
    seen = {name for lp in want_grads.values() for name in lp}
    assert {"conv", "q_norm", "k_norm", "w", "in_proj", "out_proj"} <= seen


def test_the_tied_matrix_gradient_is_the_embeddings_plus_the_heads():
    """The head runs on the embedding's table transposed, and the table's
    gradient holds both uses: it equals the gradient of an untied net's
    table plus that of its head, transposed."""
    spec = LFM2.spec()
    head = spec.layer_by_name("lm_head")
    assert head.param_from == "embed" and head.inner_product.transposed
    untied = CompiledNet.compile(spec.replace(layers=tuple(
        LayerSpec(**{**l.__dict__, "param_from": None,
                     "inner_product": InnerProductParam(
                         num_output=256, bias_term=False, axis=-1)})
        if l.name == "lm_head" else l for l in spec.layers)))
    params, ids = LFM2.params(4), _ids(74)
    loss = lambda net: lambda p: net.loss_fn("loss")(p, {"tokens": ids}, None)[0]
    l1, g1 = jax.jit(jax.value_and_grad(loss(_net())))(params)
    l2, g2 = jax.jit(jax.value_and_grad(loss(untied)))(
        dict(params, lm_head={"w": params["embed"]["w"].T}))
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    assert np.allclose(g1["embed"]["w"], g2["embed"]["w"] + g2["lm_head"]["w"].T,
                       rtol=1e-4, atol=1e-8)
    assert float(jnp.linalg.norm(g2["lm_head"]["w"])) > 0
    # a transposed product over a matrix of its own is stored (out, in)
    own = LayerSpec(name="h", type="InnerProduct", inner_product=InnerProductParam(
        num_output=7, bias_term=False, axis=-1, transposed=True))
    w = LAYER_IMPLS["InnerProduct"][0](jax.random.PRNGKey(0), own, ((2, 5, D),))["w"]
    assert w.shape == (7, D)
    x = _x(9, (2, 5, D))
    assert np.allclose(LAYER_IMPLS["InnerProduct"][1](own, {"w": w}, (x,), CTX)[0],
                       x @ w.T, atol=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_four_shares_add_up_to_the_uncut_layer(seed):
    """The parts of an expert layer's result that the four shares give (2 of
    8 experts each, no shared expert, nothing counted twice) equal the uncut
    reference's layer: all 8 experts held."""
    uncut = ref.layer_table(dict(TINY, num_experts=8, share=dict(
        TINY["share"], experts_held=[0, 8])))
    a = {n: x for n, k, x in uncut}["l1_moe"]
    p = ref.init_params(seed, uncut)["l1_moe"]
    p = dict(p, router_bias=p["router_bias"] * 20.0)
    x = _x(seed + 40)
    whole = _per_row(lambda r: ref.moe(a, p, r, "float32")[0], x)
    total, landed = 0.0, 0.0
    for first in range(0, 8, 2):
        mine = dict(p, **{k: p[k][first:first + 2] for k in
                          ("experts_gate", "experts_up", "experts_down")})
        part, counters, _ = sl.moe(MoEParam(**{
            **MOE_P.__dict__, "experts_held": (first, 2)}), mine, x, CTX)
        total = total + part
        landed += float(counters[0])
        assert float(counters[1]) == 0
    assert landed == ROWS * POS * 2, "every routed slot lands on exactly one share"
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5 * float(jnp.max(jnp.abs(whole)))


def test_the_routers_epsilon_is_the_layers_own():
    """`norm_topk_eps` reaches the division: 1e-6 (this model) against the
    other family's 1e-20, told apart where the chosen scores are tiny."""
    p = dict(_params(1, "l1_moe"), router=jnp.zeros((D, 8)).at[:, 0].set(-1.0))
    x = jnp.full((4, D), 0.5)  # expert 0 scores sigmoid(-32): 1e-14
    big = dict(p, router_bias=jnp.zeros((8,)).at[0].set(9.0))
    _, w6 = sl.route(MOE_P, big, x)
    _, w20 = sl.route(MoEParam(**{**MOE_P.__dict__, "norm_topk_eps": 1e-20}), big, x)
    assert np.allclose(jnp.sum(w6, -1), 0.5 / (0.5 + 1e-6), atol=1e-6)
    assert np.allclose(jnp.sum(w20, -1), 1.0, atol=1e-6)
    _, want = ref.route(TABLE["l1_moe"][1], big, x)
    assert np.allclose(w6, want, atol=1e-7)


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tmp_path):
    from sparknet_tpu.obs import device as obs_device

    # (`mtp_weight`: accepted, unread)
    case_ = tiny_round("lfm2_moe", tmp_path, tau=2, mtp_weight=0.3)
    trainer = case_.make_trainer()
    _, got = program_round("lfm2_moe", trainer, case_.params, case_.ids)
    check_round(got, case_.want, rel=2e-4)
    assert set(case_.want["chosen"]) == {"l1_moe", "l2_moe", "l3_moe"}
    assert set(trainer.counter_values()) == {
        "l1_moe_counters", "l2_moe_counters", "l3_moe_counters"}
    # what the round's one attention block keeps: the core's output, and off
    # the chip no kernel
    report = obs_device.program_report("train_round")
    kept = dict(report["recompute"])
    pre = kept.pop(sl.MLP_PRE)
    # ... the tied head its logits, made once a step
    check_products_kept("lfm2_moe", report, tau=2)
    del kept[sl.IP_OUT]
    # ... the three expert layers their routing: no score product made again
    check_routing_kept(report, 2, [case_.spec.layer_by_name(n).moe
                                   for n in sorted(case_.want["chosen"])])
    del kept[sl.MOE_ROUTE]
    assert kept == {sl.ATTN_CORE: {
        "maker": "splash_mha_fwd", "step_bodies": 0, "forward": 0, "backward": 0,
        "kept_bytes": ROWS * POS * GQA_P.num_heads * GQA_P.head_dim * 4}}
    # ... and the dense block its SwiGLU's two input products: none of them
    # is made again for the backward pass
    assert (pre["maker"], pre["backward"]) == (sl.MLP_PRE, 0) and pre["forward"] >= 2
    assert pre["kept_bytes"] == 2 * ROWS * POS * TINY["intermediate_size"] * 4
    assert report["attention_moves"]["instructions"] > 0
    # ... and what its three expert layers move around their products (what
    # the counts come to is the chip compiler's: tests/test_chip_compile.py)
    moves = report["routing_moves"]
    assert set(moves) == {"instructions", "bytes", "row_gathers", "rows_gathered",
                          "row_scatters", "rows_scattered", "slot_scalar_moves",
                          "slot_scalars_moved"}
    assert moves["row_gathers"] > 0 and moves["rows_gathered"] % ROWS == 0
    assert moves["rows_scattered"] == 0  # k = 2 x 64 tokens: the gathers' side
    scopes = {op["scope"] for op in report["ops"].values()}
    for part in ("ShortConv/l0_conv)/in_proj", "ShortConv/l0_conv)/mix",
                 "ShortConv/l0_conv)/out_proj", "GQAttention/l1_attn)/core"):
        assert any(part in s for s in scopes), part


# -- the builder -------------------------------------------------------------

def test_zoo_follows_layer_types_and_names_what_a_block_keeps():
    spec = zoo.lfm2_moe(TINY, rows=ROWS, positions=POS)
    ops = [(l.name, l.type) for l in spec.layers if l.type in ("ShortConv", "GQAttention")]
    assert ops == [("l0_conv", "ShortConv"), ("l1_attn", "GQAttention"),
                   ("l2_conv", "ShortConv"), ("l3_conv", "ShortConv")]
    ff = [(l.name, l.type) for l in spec.layers if l.type in ("GatedMLP", "MoE")]
    assert ff == [("l0_mlp", "GatedMLP"), ("l1_moe", "MoE"), ("l2_moe", "MoE"),
                  ("l3_moe", "MoE")]
    moe = spec.layer_by_name("l1_moe").moe
    assert (moe.n_routed_experts, moe.experts_held, moe.n_shared_experts,
            moe.norm_topk_eps) == (8, (2, 2), 0, 1e-6)
    assert spec.layer_by_name("l1_attn").gqa.head_dim == 16  # hidden / heads
    assert not any(l.type == "MTP" for l in spec.layers)
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "l2", "l3", "head"}
    net = _net()
    assert net.kept_makers() == {sl.ATTN_CORE: "splash_mha_fwd", sl.MLP_PRE: sl.MLP_PRE,
                                 sl.IP_OUT: sl.IP_OUT, sl.MOE_ROUTE: "router"}
    assert net.attention_scopes() == ({"GQAttention": ""}, POS)
    assert net.routing_scopes() == (sl.ROUTING_SCOPES, TINY["hidden_size"])
    assert sum(int(np.prod(s)) for lp in ref.param_shapes(LAYERS).values()
               for s in lp.values()) == sum(
        int(np.prod(v.shape)) for lp in jax.eval_shape(
            net.init_params, jax.random.PRNGKey(0)).values() for v in lp.values())
    assert zoo.SEQUENCE_MODELS["lfm2_moe"] is zoo.lfm2_moe


@pytest.mark.parametrize("change,match", [
    ({"share": {**TINY["share"], "experts_held": [2, 4]}}, "disagree"),
    ({"share": {**TINY["share"], "vocab_rows": [0, 128]}}, "disagree"),
    ({"layer_types": ["conv", "full_attention", "conv"]}, "layer_types"),
    ({"layer_types": ["conv", "full_attention", "conv", "sliding"]}, "layer_types"),
    ({"conv_bias": True}, "not built"),
])
def test_zoo_refuses_a_file_that_disagrees_with_itself(change, match):
    with pytest.raises(ValueError, match=match):
        zoo.lfm2_moe(dict(TINY, **change), rows=ROWS, positions=POS)


# -- the grouped products' tiles ---------------------------------------------

@pytest.mark.parametrize("k,n", [(256, 192), (192, 256), (320, 128)])
def test_a_grouped_product_whose_tiles_overhang_equals_the_ragged_dot(k, n):
    """The experts' width (1,792) is no multiple of the grouped matmul's
    512-wide tiles, and on the chip tiles that overhang beat tiles that
    divide (PERF.md section 6, PR 31): megablox masks the overhang. Here, at
    tiles of 128 over widths of 192, 256 and 320 under the Pallas
    interpreter: the product and both gradients equal `lax.ragged_dot`'s."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    x = _x(k, (256, k))
    w = 0.1 * _x(n, (3, k, n))
    sizes = jnp.asarray([100, 28, 90], jnp.int32)  # 38 rows past the groups' end
    kernel = lambda x, w: megablox.gmm(x, w, sizes, preferred_element_type=jnp.float32,
                                       tiling=(128, 128, 128), interpret=True)
    exact = lambda x, w: jax.lax.ragged_dot(x, w, sizes,
                                            precision=jax.lax.Precision.HIGHEST)
    mask = (jnp.arange(256) < 218)[:, None]
    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(jnp.where(mask, f(x, w), 0.0)))
    with jax.default_matmul_precision("highest"):
        assert np.allclose(jnp.where(mask, kernel(x, w), 0.0), exact(x, w), atol=2e-5)
        for a, b in zip(jax.grad(loss(kernel), argnums=(0, 1))(x, w),
                        jax.grad(loss(exact), argnums=(0, 1))(x, w)):
            assert np.allclose(jnp.where(mask, a, 0.0) if a.shape == x.shape else a,
                               b, atol=2e-5)
