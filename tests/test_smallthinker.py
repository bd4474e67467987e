"""The sliding-window sparse-expert model (`zoo.smallthinker`): grouped-query
attention at seven query heads a key/value head, global without a rotary
turn or over a sliding window with one, a router that reads the stream BEFORE
the attention its experts follow, ReGLU experts weighted by a softmax over
the chosen logits -- against the benchmark's plain reference
(`benchmark/configs/smallthinker-21b-ep4-tau4.reference.py`, which imports
nothing of the program) at small widths on the CPU. Only what no other model
has; what the suites share is `model_cases`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (CTX, D, POS, ROWS, _close, _per_row, _x, case,
                         check_layer, check_loss_and_every_gradient,
                         check_products_kept, check_round, compiled,
                         program_round, tiny_round)
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.spec import GQAttentionParam, MoEParam
from sparknet_tpu.ops.attention import SlidingWindowMask

ST = case("smallthinker")
ref, TINY, LAYERS, TABLE = ST.ref, ST.tiny, ST.layers, ST.table
WINDOW = TINY["sliding_window_size"]  # 8 of 32 positions: the mask bites
GLOBAL_P = GQAttentionParam(num_heads=14, num_kv_heads=2, head_dim=16,
                            rope_theta=1.5e6, eps=1e-6, qk_norm=False,
                            rotary=False)
SLIDING_P = GQAttentionParam(**{**GLOBAL_P.__dict__, "rotary": True,
                                "window": WINDOW})
MOE_P = MoEParam(n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
                 intermediate_size=48, n_shared_experts=0,
                 score_func="softmax_topk", expert_form="reglu")
#: the router's input of a lone layer: another tensor than the experts'
other = lambda x: x[..., ::-1]


def _params(seed, layer):
    p = ST.params(seed)[layer]
    if "router" in p:  # logits apart enough that the choice is no coin toss
        p = dict(p, router=p["router"] * 20.0)
    return p


# -- layer by layer against the reference ------------------------------------

LAYER_TABLE = {
    "global_nope": (lambda seed: _params(seed, "l0_attn"),
                    lambda p, x: sl.gqa(GLOBAL_P, p, x, CTX),
                    lambda p, r: ref.gqa(TABLE["l0_attn"][1], p, r, "float32")),
    "sliding": (lambda seed: _params(seed, "l1_attn"),
                lambda p, x: sl.gqa(SLIDING_P, p, x, CTX),
                lambda p, r: ref.gqa(TABLE["l1_attn"][1], p, r, "float32")),
    "moe": (lambda seed: _params(seed, "l1_moe"),
            lambda p, x: sl.moe(MOE_P, p, x, CTX, other(x))[0],
            lambda p, r: ref.moe(TABLE["l1_moe"][1], p, r, other(r), "float32")[0]),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["global_nope", "sliding", "moe"])
def test_layer_matches_the_reference(kind, policy):
    assert (TABLE["l0_attn"][1]["window"], TABLE["l0_attn"][1]["rotary"]) == (None, False)
    assert (TABLE["l1_attn"][1]["window"], TABLE["l1_attn"][1]["rotary"]) == (WINDOW, True)
    check_layer(LAYER_TABLE, kind, policy)


def test_a_sliding_layer_reads_its_window_and_a_global_layer_every_key():
    """Position 20's result: unmoved under the window when position 12 (the
    ninth back) changes, moved when position 13 (the eighth, the window's
    last) does; the global layer is moved by both, and neither by what comes
    after."""
    p, x = _params(3, "l1_attn"), _x(31)
    at = lambda prm, x: np.asarray(sl.gqa(prm, p, x, CTX))[:, 20]
    for prm, sees_12 in ((SLIDING_P, False), (GLOBAL_P, True)):
        base = at(prm, x)
        assert np.array_equal(at(prm, x.at[:, 12].add(1.0)), base) != sees_12
        assert not np.array_equal(at(prm, x.at[:, 13].add(1.0)), base)
        assert np.array_equal(at(prm, x.at[:, 21:].add(1.0)), base)
    # a window at least as long as the row is plain causal attention: no mask
    assert sl.gqa_mask(SLIDING_P, POS) == SlidingWindowMask(POS, WINDOW)
    assert sl.gqa_mask(SLIDING_P, WINDOW) is None and sl.gqa_mask(GLOBAL_P, POS) is None
    dense = SlidingWindowMask(POS, WINDOW).dense()
    assert dense.sum() == WINDOW * POS - WINDOW * (WINDOW - 1) // 2
    assert dense[20, 13] and not dense[20, 12] and not dense[20, 21]


def test_the_kernel_under_the_interpreter_equals_the_exact_path(monkeypatch):
    """splash attention under the sliding mask at seven query heads over one
    key/value head (512 positions, a window of 200 -- no whole tiles -- tiles
    of 128), forward and every gradient, against the exact path; the kernel's
    tables leave out the tiles the window empties."""
    monkeypatch.setattr(sl, "ATTN_BLOCKS", (128, 128, 128))
    n, w, d = 512, 200, 128
    mask = SlidingWindowMask(n, w)
    q = (_x(18, (1, 7, n, d)) / np.sqrt(d)).astype(jnp.bfloat16)
    k, v = (_x(s, (1, 1, n, d)).astype(jnp.bfloat16) for s in (19, 20))
    t = _x(21, (1, 7, n, d))
    kernel = sl._splash(7, n, mask, True)
    table = np.asarray(kernel.fwd_mask_info.block_mask)
    # query block i meets key blocks i - 2 .. i (199 back from its first row)
    assert np.count_nonzero(table) == 1 + 2 + 3 + 3 and table.shape[-1] == 3
    causal = np.asarray(sl._splash(7, n, None, True).fwd_mask_info.block_mask)
    assert np.count_nonzero(causal) == 1 + 2 + 3 + 4

    def by_kernel(q, k, v):
        return jnp.sum(jax.vmap(kernel)(q, k, v).astype(jnp.float32) * t)

    def exact(q, k, v):
        with precision.policy("bfloat16"):
            return jnp.sum(sl.attention_core(q, k, v, CTX, mask).astype(jnp.float32) * t)

    got, g_got = jax.value_and_grad(by_kernel, (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(exact, (0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) < 0.02 * abs(float(want)) + 0.5
    for a, b in zip(g_got, g_want):
        _close(a, b, "bfloat16")


# -- the expert layer --------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_the_router_reads_its_own_input_and_not_the_experts(seed):
    """The chosen experts are those of the router's input; fed the experts'
    input instead (one bottom) the layer chooses otherwise and gives another
    result; the experts' products read the first input alone."""
    p, x = _params(seed, "l1_moe"), _x(seed + 50)
    xf, rf = x.reshape(-1, D), other(x).reshape(-1, D)
    y, _, chosen = sl.moe(MOE_P, p, x, CTX, other(x))
    idx_r, _ = sl.route(MOE_P, p, rf)
    idx_x, _ = sl.route(MOE_P, p, xf)
    assert np.array_equal(chosen.reshape(-1, 2), idx_r)
    assert not np.array_equal(idx_r, idx_x)
    y_one, _, chosen_one = sl.moe(MOE_P, p, x, CTX)
    assert np.array_equal(chosen_one.reshape(-1, 2), idx_x)
    assert not np.allclose(y, y_one, atol=1e-4)
    # the same routing over other rows moves the result: the experts read x
    y_moved = sl.moe(MOE_P, p, x + 1.0, CTX, other(x))[0]
    assert not np.allclose(y, y_moved, atol=1e-4)
    # through the layer's own door: two bottoms, no bias stored
    from sparknet_tpu.model.layers import LAYER_IMPLS
    from sparknet_tpu.model.spec import LayerSpec
    layer = LayerSpec(name="m", type="MoE", moe=MOE_P)
    init, apply, _ = LAYER_IMPLS["MoE"]
    assert set(init(jax.random.PRNGKey(0), layer, ((ROWS, POS, D),))) == {
        "router", "experts_gate", "experts_up", "experts_down"}
    assert np.array_equal(apply(layer, p, (x, other(x)), CTX)[0], y)
    assert np.array_equal(apply(layer, p, (x,), CTX)[0], y_one)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_weights_are_a_softmax_over_the_chosen_logits(seed):
    """Values and the logits' gradient against `take_along_axis` + `softmax`;
    equal to a softmax over all the logits with the chosen renormalised."""
    p, x = _params(seed, "l0_moe"), _x(seed + 60).reshape(-1, D)
    t = _x(seed + 61, (ROWS * POS, 2))

    def plain(router):
        z = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
        _, idx = jax.lax.top_k(z, 2)
        return idx, jax.nn.softmax(jnp.take_along_axis(z, idx, axis=-1), axis=-1), z

    idx, w = sl.route(MOE_P, p, x)
    want_idx, want_w, z = plain(p["router"])
    assert np.array_equal(idx, want_idx) and np.allclose(w, want_w, atol=1e-6)
    assert np.allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    full = jax.nn.softmax(z, axis=-1)
    chosen = jnp.take_along_axis(full, idx, axis=-1)
    assert np.allclose(w, chosen / jnp.sum(chosen, -1, keepdims=True), atol=1e-6)
    got = jax.grad(lambda r: jnp.sum(sl.route(MOE_P, dict(p, router=r), x)[1] * t))(
        p["router"])
    want = jax.grad(lambda r: jnp.sum(plain(r)[1] * t))(p["router"])
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    assert np.allclose(got, want, atol=2e-6 * float(jnp.max(jnp.abs(want))) + 1e-9)
    ref_idx, ref_w = ref.route(TABLE["l0_moe"][1], p, x)
    assert np.array_equal(idx, ref_idx) and np.allclose(w, ref_w, atol=1e-6)


def test_the_experts_are_reglu():
    """One expert holding every slot: (relu(x Wg) * x Wu) Wd by hand; SwiGLU
    on the same weights is another result; the forms that are not built and a
    shared expert beside ReGLU are refused."""
    one = MoEParam(**{**MOE_P.__dict__, "n_routed_experts": 1, "experts_held": (0, 1),
                      "num_experts_per_tok": 1})
    p = {k: v[:1] if v.ndim == 3 else v[:, :1] for k, v in _params(4, "l1_moe").items()}
    x = _x(44)
    want = (np.maximum(np.asarray(x) @ np.asarray(p["experts_gate"][0]), 0)
            * (np.asarray(x) @ np.asarray(p["experts_up"][0]))) @ np.asarray(
                p["experts_down"][0])
    with jax.default_matmul_precision("highest"):
        got = sl.moe(one, p, x, CTX)[0]
        silu = sl.moe(MoEParam(**{**one.__dict__, "expert_form": "swiglu"}), p, x, CTX)[0]
    assert np.allclose(got, want, atol=2e-5 * np.max(np.abs(want)))
    assert not np.allclose(silu, want, atol=1e-3 * np.max(np.abs(want)))
    for change, match in (({"expert_form": "geglu"}, "expert_form"),
                          ({"score_func": "softmax"}, "score_func"),
                          ({"n_shared_experts": 1}, "shared expert")):
        with pytest.raises(ValueError, match=match):
            sl.init_moe_params(jax.random.PRNGKey(0),
                               MoEParam(**{**MOE_P.__dict__, **change}), D)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_four_shares_add_up_to_the_uncut_layer(seed):
    """The parts of an expert layer's result that the four shares give (2 of
    8 experts each -- in the file, 16 of 64 -- no shared expert, nothing
    counted twice) equal the uncut reference's layer: all 8 experts held."""
    uncut = ref.layer_table(dict(TINY, moe_num_primary_experts=8, share=dict(
        TINY["share"], experts_held=[0, 8])))
    a = {n: x for n, k, x in uncut}["l1_moe"]
    p = ref.init_params(seed, uncut)["l1_moe"]
    p = dict(p, router=p["router"] * 20.0)
    x = _x(seed + 40)
    whole = _per_row(lambda r: ref.moe(a, p, r, other(r), "float32")[0], x)
    total, landed = 0.0, 0.0
    for first in range(0, 8, 2):
        mine = dict(p, **{k: p[k][first:first + 2] for k in
                          ("experts_gate", "experts_up", "experts_down")})
        part, counters, _ = sl.moe(MoEParam(**{
            **MOE_P.__dict__, "experts_held": (first, 2)}), mine, x, CTX, other(x))
        total = total + part
        landed += float(counters[0])
        assert float(counters[1]) == 0
    assert landed == ROWS * POS * 2, "every routed slot lands on exactly one share"
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5 * float(jnp.max(jnp.abs(whole)))


# -- the whole model ---------------------------------------------------------

@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("bfloat16", 1)])
def test_loss_and_every_stored_gradient_match_the_reference(policy, seed):
    assert compiled("smallthinker").param_layers() == list(ref.param_shapes(LAYERS))
    f32 = policy == "float32"
    _, grads, want_grads = check_loss_and_every_gradient(
        "smallthinker", policy, ST.params(seed), ST.ids(seed + 70),
        loss_tol=2e-5 if f32 else 2e-3, grad_tol=2e-5 if f32 else 0.3)
    assert "lm_head" in grads, "an untied head holds a matrix of its own"
    assert not any("router_bias" in lp for lp in grads.values())


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tmp_path):
    from sparknet_tpu.obs import device as obs_device

    case_ = tiny_round("smallthinker", tmp_path, tau=2)
    trainer = case_.make_trainer()
    _, got = program_round("smallthinker", trainer, case_.params, case_.ids)
    check_round(got, case_.want, rel=2e-4)
    assert set(case_.want["chosen"]) == {"l0_moe", "l1_moe"}
    assert set(trainer.counter_values()) == {"l0_moe_counters", "l1_moe_counters"}
    report = obs_device.program_report("train_round")
    assert report["window"] == {
        "layers": {"l0_attn": {"window": None, "blocks_visited": 0, "blocks_causal": 0,
                               "core_forward_calls": 0, "core_backward_calls": 0},
                   "l1_attn": {"window": WINDOW, "blocks_visited": 0, "blocks_causal": 0,
                               "core_forward_calls": 0, "core_backward_calls": 0}},
        "windowed_layers": 1, "blocks_visited": 0, "blocks_causal": 0}
    assert obs_device.program_part("window")["train_round"] == report["window"]
    assert report["eva"] == {} and report["ssm"] == {}
    check_products_kept("smallthinker", report, tau=2)
    scopes = {op["scope"] for op in report["ops"].values()}
    for part in ("GQAttention/l0_attn)/core", "GQAttention/l1_attn)/core",
                 "MoE/l0_moe)/router", "MoE/l1_moe)/experts"):
        assert any(part in s for s in scopes), part


# -- the builder -------------------------------------------------------------

def test_zoo_follows_the_two_layouts_and_feeds_the_router_the_first_norm():
    spec = zoo.smallthinker(TINY, rows=ROWS, positions=POS)
    attn = [(l.name, l.gqa.window, l.gqa.rotary, l.gqa.qk_norm)
            for l in spec.layers if l.type == "GQAttention"]
    assert attn == [("l0_attn", None, False, False), ("l1_attn", WINDOW, True, False)]
    assert spec.layer_by_name("l1_attn").gqa.held() == (14, 2)  # seven a group
    moe = spec.layer_by_name("l1_moe")
    assert moe.bottoms == ("l1_mlp_norm", "l1_op_norm")
    assert spec.layer_by_name("l1_attn").bottoms == ("l1_op_norm",)
    assert (moe.moe.n_routed_experts, moe.moe.experts_held, moe.moe.n_shared_experts,
            moe.moe.score_func, moe.moe.expert_form) == (8, (2, 2), 0, "softmax_topk",
                                                         "reglu")
    assert not any(l.type in ("GatedMLP", "MTP") for l in spec.layers)
    assert spec.layer_by_name("lm_head").param_from is None
    # the table draws the matrices' 0.02 unless the file gives its own spread
    assert spec.layer_by_name("embed").embed.std == 0.02
    own = zoo.smallthinker(dict(TINY, embed_init_std=1.0), rows=ROWS, positions=POS)
    assert own.layer_by_name("embed").embed.std == 1.0
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "head"}
    net = compiled("smallthinker")
    assert net.kept_makers() == {sl.ATTN_CORE: "splash_mha_fwd", sl.IP_OUT: sl.IP_OUT,
                                 sl.MOE_ROUTE: "router"}
    assert net.attention_scopes() == ({"GQAttention": ""}, POS)
    assert net.routing_scopes() == (sl.ROUTING_SCOPES, TINY["hidden_size"])
    assert net.window_scopes()[0] == {"GQAttention": "core"}
    # a net whose grouped-query layers have no window reports none
    assert compiled("lfm2_moe").window_scopes() == ({}, {})
    assert sum(int(np.prod(s)) for lp in ref.param_shapes(LAYERS).values()
               for s in lp.values()) == sum(
        int(np.prod(v.shape)) for lp in jax.eval_shape(
            net.init_params, jax.random.PRNGKey(0)).values() for v in lp.values())
    assert zoo.SEQUENCE_MODELS["smallthinker"] is zoo.smallthinker


@pytest.mark.parametrize("change,match", [
    ({"share": {**TINY["share"], "experts_held": [2, 4]}}, "disagree"),
    ({"share": {**TINY["share"], "vocab_rows": [0, 128]}}, "disagree"),
    ({"sliding_window_layout": [0, 1, 1]}, "sliding_window_layout"),
    ({"rope_layout": [0, 2]}, "rope_layout"),
    ({"moe_primary_router_apply_softmax": False}, "not built"),
    ({"tie_word_embeddings": True}, "not built"),
])
def test_zoo_refuses_a_file_that_disagrees_with_itself(change, match):
    with pytest.raises(ValueError, match=match):
        zoo.smallthinker(dict(TINY, **change), rows=ROWS, positions=POS)
