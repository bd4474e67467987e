"""The sequence-model layer set (model/seq_layers.py), the builder
`zoo.glm4_moe_lite` and what they needed of the net, the solver and the
trainer -- against the benchmark's plain reference
(`benchmark/configs/glm47-flash-ep8-tau4.reference.py`, which imports nothing
of the program) at small widths on the CPU: layer by layer, the two-headed
loss and its gradients, one tau-round through `ParallelTrainer.train_round`,
and the share arithmetic (the parts all the shares give add up to the uncut
layer).
"""
from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision, zoo
from sparknet_tpu.model import net as net_mod
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import LAYER_IMPLS, ApplyCtx
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (EltwiseParam, EmbedParam,
                                     InputSpec, LayerSpec, LossParam,
                                     MLAttentionParam, MoEParam,
                                     NetSpec, ParamSpec, RMSNormParam)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "glm_reference", os.path.join(ROOT, "benchmark", "configs",
                                  "glm47-flash-ep8-tau4.reference.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: hidden 64, 2 heads of 16+8 / 16, ranks 24 / 16, 8 experts top-2 of which
#: 2 are held (experts 2 and 3), vocabulary 256, 32 positions
TINY = {
    "model_type": "glm4_moe_lite", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 48, "num_attention_heads": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1.8,
    "norm_topk_prob": True, "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "vocab_size": 256, "seq_len": 32, "n_group": 1, "topk_group": 1,
    "share": {"chips_sharing_a_layer": 4, "n_routed_experts": 8,
              "experts_held": [2, 2], "vocab_rows": [0, 256],
              "mtp_loss_weight": 0.3}}
ROWS, POS, D = 2, 32, 64
LAYERS = ref.layer_table(TINY)
TABLE = {name: (kind, a) for name, kind, a in LAYERS}
ATTN, MOE = TABLE["l0_attn"][1], TABLE["l1_moe"][1]
MLA_P = MLAttentionParam(num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                         rope_theta=1e6, eps=1e-5)
MOE_P = MoEParam(n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
                 intermediate_size=48, n_shared_experts=1,
                 routed_scaling_factor=1.8, norm_topk_prob=True)
#: bf16 against the float32 reference: relative to the result's own scale
BF16_TOL = 0.03
CTX = ApplyCtx(train=True)


def _params(seed, layer="l1_moe", bias_scale=1.0):
    p = ref.init_params(seed, LAYERS)[layer]
    if "router_bias" in p:  # a bias large enough to change who is chosen
        p = dict(p, router_bias=p["router_bias"] * bias_scale)
    return p


def _x(seed, shape=(ROWS, POS, D)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(got, want, policy):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want))) + 1e-30
    tol = 2e-5 if policy == "float32" else BF16_TOL
    assert float(np.max(np.abs(got - want))) / scale < tol


def _per_row(fn, x):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fn(x[r]) for r in range(x.shape[0])])


# -- layer by layer against the reference ------------------------------------

def _layer_case(kind, seed):
    """(program's result, reference's result) of one layer on one input."""
    x = _x(seed)
    if kind == "rmsnorm":
        scale = 1.0 + 0.1 * _x(seed + 1, (D,))
        return (sl._rms(x, scale, 1e-5), ref.rmsnorm(x, scale, 1e-5))
    if kind == "mla":
        p = _params(seed, "l0_attn")
        return (sl.mla(MLA_P, p, x, CTX),
                _per_row(lambda r: ref.mla(ATTN, p, r, "float32"), x))
    if kind == "mlp":
        p = _params(seed, "l0_mlp")
        return (sl._swiglu(x, p["gate"], p["up"], p["down"]),
                _per_row(lambda r: ref.swiglu(r, p["gate"], p["up"], p["down"],
                                              "float32"), x))
    if kind == "moe":
        p = _params(seed, "l1_moe", bias_scale=20.0)
        return (sl.moe(MOE_P, p, x, CTX)[0],
                _per_row(lambda r: ref.moe(MOE, p, r, "float32")[0], x))
    raise AssertionError(kind)


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["rmsnorm", "mla", "mlp", "moe"])
def test_layer_matches_the_reference(kind, policy):
    for seed in (1, 2):  # two weight draws
        with precision.policy(policy):
            got, want = _layer_case(kind, seed)
        _close(got, want, policy)


def test_embed_shift_and_eltwise():
    table = _x(3, (256, D))
    ids = jax.random.randint(jax.random.PRNGKey(4), (ROWS, POS), 0, 256, jnp.int32)
    embed = lambda shift: LAYER_IMPLS["Embed"][1](
        LayerSpec(name="e", type="Embed",
                  embed=EmbedParam(num_embeddings=256, dim=D, shift=shift)),
        {"w": table}, (ids,), CTX)[0]
    assert np.array_equal(embed(0), np.asarray(table)[np.asarray(ids)])
    nxt = np.asarray(embed(1))
    assert np.array_equal(nxt[:, :-1], np.asarray(table)[np.asarray(ids)[:, 1:]])
    assert np.array_equal(nxt[:, -1], np.broadcast_to(table[0], (ROWS, D)))
    a, b = _x(5), _x(6)
    elt = lambda p, *xs: LAYER_IMPLS["Eltwise"][1](
        LayerSpec(name="s", type="Eltwise", eltwise=p), None, xs, CTX)[0]
    assert np.array_equal(elt(None, a, b), a + b)
    assert np.allclose(elt(EltwiseParam(coeff=(1.0, 0.3)), a, b), a + 0.3 * b)
    with pytest.raises(ValueError, match="is not built"):
        elt(EltwiseParam(operation="MAX"), a, b)


@pytest.mark.parametrize("shift,weight", [(1, 1.0), (2, 0.3)])
def test_masked_softmax_loss_by_hand(shift, weight):
    """[rows, positions, V] logits against the ids `shift` positions on: the
    mean over the positions that have a target, times the loss weight."""
    logits = _x(7, (ROWS, POS, 50))
    ids = jax.random.randint(jax.random.PRNGKey(8), (ROWS, POS), 0, 50, jnp.int32)
    layer = LayerSpec(name="l", type="SoftmaxWithLoss",
                      loss=LossParam(label_shift=shift, loss_weight=weight))
    got = LAYER_IMPLS["SoftmaxWithLoss"][1](layer, None, (logits, ids), CTX)[0]
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    want = -np.mean([logp[r, i, int(ids[r, i + shift])]
                     for r in range(ROWS) for i in range(POS - shift)])
    assert float(got) == pytest.approx(weight * want, rel=1e-5)
    # an ignore label with no shift: those positions leave the mean
    masked = np.asarray(ids).copy()
    masked[:, ::3] = -1
    layer = LayerSpec(name="l", type="SoftmaxWithLoss", loss=LossParam(ignore_label=-1))
    got = LAYER_IMPLS["SoftmaxWithLoss"][1](layer, None, (logits, jnp.asarray(masked)), CTX)[0]
    keep = masked >= 0
    want = -np.mean(np.take_along_axis(logp, np.maximum(masked, 0)[..., None], -1)[..., 0][keep])
    assert float(got) == pytest.approx(want, rel=1e-5)


# -- the causal mask and the rotary embedding against a direct formula -------

def test_rotary_against_the_direct_formula():
    """The half-split turn of columns `half_split` has de-interleaved is the
    direct formula on interleaved pairs, under the same permutation; lanes
    before the last `rope` pass untouched."""
    x = np.asarray(_x(9, (1, 3, 5, 12)))  # [rows, heads, positions, 4 + 8]
    split = jnp.concatenate([x[..., :4], sl.half_split(jnp.asarray(x[..., 4:]))], -1)
    assert np.array_equal(split[..., 4:8], x[..., 4::2])
    assert np.array_equal(split[..., 8:], x[..., 5::2])
    got = np.asarray(sl.rotary(split, 1e6, 8))
    assert np.array_equal(got[..., :4], x[..., :4])
    for pos in range(5):
        for i in range(4):  # pair (x[2i], x[2i+1]) turned by pos * theta^(-2i/d)
            ang = pos * 1e6 ** (-2 * i / 8)
            a, b = x[0, :, pos, 4 + 2 * i], x[0, :, pos, 4 + 2 * i + 1]
            assert np.allclose(got[0, :, pos, 4 + i], a * np.cos(ang) - b * np.sin(ang), atol=1e-5)
            assert np.allclose(got[0, :, pos, 8 + i], b * np.cos(ang) + a * np.sin(ang), atol=1e-5)
    # what attention sees depends on the distance alone
    q, k = sl.half_split(_x(10, (1, 9, 8))), sl.half_split(_x(11, (1, 9, 8)))
    same = lambda s: float(jnp.dot(sl.rotary(jnp.roll(q, s, 1), 1e4, 8)[0, 4 + s],
                                   sl.rotary(jnp.roll(k, s, 1), 1e4, 8)[0, 2 + s]))
    assert same(0) == pytest.approx(same(3), rel=1e-4)
    # the reference turns interleaved pairs and writes them out half-split
    assert np.allclose(ref.rotary(_x(10, (9, 8)), 1e4),
                       sl.rotary(sl.half_split(_x(10, (1, 9, 8))), 1e4, 8)[0], atol=1e-6)


def test_attention_core_is_causal_and_exact():
    q, k, v = _x(12, (1, 6, 2, 8)), _x(13, (1, 6, 2, 8)), _x(14, (1, 6, 2, 4))
    # the core reads heads first, and q scaled
    core = lambda q, k, v: jnp.swapaxes(sl.attention_core(
        *(jnp.swapaxes(t, 1, 2) for t in (q / np.sqrt(8), k, v)), CTX), 1, 2)
    got = np.asarray(core(q, k, v))
    for h in range(2):
        for i in range(6):
            s = np.asarray(q)[0, i, h] @ np.asarray(k)[0, :i + 1, h].T / np.sqrt(8)
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ np.asarray(v)[0, :i + 1, h]
            assert np.allclose(got[0, i, h], want, atol=1e-5)
    # a later key changes no earlier position
    k2 = k.at[0, 5].add(3.0)
    again = np.asarray(core(q, k2, v))
    assert np.array_equal(again[0, :5], got[0, :5]) and not np.allclose(again[0, 5], got[0, 5])
    # the reference's blocked core is the same function
    with jax.default_matmul_precision("highest"):
        for block, groups in ((2, 1), (2, 3), (1, 2), (6, 4)):
            blocked = ref.causal_attention(q[0], k[0], v[0], "float32",
                                           block=block, groups=groups)
            assert np.allclose(blocked, got[0], atol=1e-5), (block, groups)


# -- the layout lives in the weights: the stored parameters see nothing ------

def _plain_mla(p, params, x):
    """Latent attention as the published code writes it: positions first,
    heads split and sliced on the activations, interleaved rotary pairs
    (x[2i], x[2i+1]), the scores scaled, an exact causal softmax. float32."""
    hi = dict(precision=jax.lax.Precision.HIGHEST)
    r, n, _ = x.shape
    h, nope, rope, dv = (p.num_heads, p.qk_nope_head_dim, p.qk_rope_head_dim,
                         p.v_head_dim)
    rms = lambda t, g: t * jax.lax.rsqrt(
        jnp.mean(jnp.square(t), axis=-1, keepdims=True) + p.eps) * g

    def turn(t):  # [rows, positions, ..., rope], position along axis 1
        inv = 1.0 / (p.rope_theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
        ang = jnp.asarray(np.arange(n)[:, None] * inv[None, :], jnp.float32)
        ang = ang.reshape((1, n) + (1,) * (t.ndim - 3) + (rope // 2,))
        a, b = t[..., 0::2], t[..., 1::2]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)

    q = jnp.dot(rms(jnp.dot(x, params["q_a"], **hi), params["q_a_norm"]),
                params["q_b"], **hi).reshape(r, n, h, nope + rope)
    kv_a = jnp.dot(x, params["kv_a"], **hi)
    kv = jnp.dot(rms(kv_a[..., :p.kv_lora_rank], params["kv_a_norm"]),
                 params["kv_b"], **hi).reshape(r, n, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
    k_rope = turn(kv_a[..., p.kv_lora_rank:])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (r, n, h, rope))], axis=-1)
    s = jnp.einsum("rnhd,rmhd->rhnm", q, k, **hi) / np.sqrt(nope + rope)
    s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :], s, -jnp.inf)
    o = jnp.einsum("rhnm,rmhd->rnhd", jax.nn.softmax(s, axis=-1),
                   kv[..., nope:], **hi)
    return jnp.dot(o.reshape(r, n, h * dv), params["o"], **hi)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_mla_and_every_stored_gradient_equal_the_positions_first_formula(seed):
    """`mla` permutes, splits and scales views of its weights, never the
    stored matrices: the result and every stored parameter's gradient, in
    its published shape and column order, are the plain formula's -- a
    checkpoint written before the layout moved trains on identically."""
    p = {k: jnp.asarray(v) for k, v in _params(seed, "l0_attn").items()}
    p = dict(p, q_a_norm=1.0 + 0.1 * _x(seed + 20, p["q_a_norm"].shape),
             kv_a_norm=1.0 + 0.1 * _x(seed + 21, p["kv_a_norm"].shape))
    x, weigh = _x(seed + 30), _x(seed + 31)
    assert {k: v.shape for k, v in p.items()} == {
        k: v.shape for k, v in sl.init_mla(jax.random.PRNGKey(0), MLA_P, D).items()}
    loss = lambda fn: lambda p, x: jnp.sum(fn(p, x) * weigh)
    got, want = (jax.value_and_grad(loss(fn), argnums=(0, 1))(p, x) for fn in (
        lambda p, x: sl.mla(MLA_P, p, x, CTX),
        lambda p, x: _plain_mla(MLA_P, p, x)))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert rel(sl.mla(MLA_P, p, x, CTX), _plain_mla(MLA_P, p, x)) < 1e-5
    assert rel(got[1][1], want[1][1]) < 1e-5
    for name in p:  # q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, o
        assert got[1][0][name].shape == p[name].shape
        assert rel(got[1][0][name], want[1][0][name]) < 1e-5, name


def test_the_scale_folded_into_the_weight_gives_bit_equal_bf16_q():
    """GLM's heads are 256 wide: 1/sqrt(256) = 2^-4 shifts an exponent, so
    q from the scaled weight is q scaled, to the last bit of every bf16."""
    c_q = _x(40, (2, 64, 24)).astype(jnp.bfloat16)
    w = 0.02 * _x(41, (24, 3, 256))
    with precision.policy("bfloat16"):
        folded = sl._project("rnc,chd->rhnd", c_q, w / np.sqrt(256))
        scaled = sl._project("rnc,chd->rhnd", c_q, w) * jnp.bfloat16(1 / 16)
    assert folded.dtype == jnp.bfloat16 and float(jnp.max(jnp.abs(folded))) > 0
    assert np.array_equal(np.asarray(folded, np.float32),
                          np.asarray(scaled, np.float32))


# -- the expert layer: shares, drops, counters -------------------------------

def _uncut(seed, bias_scale=20.0):
    """An expert layer with all 8 experts' weights, and the reference's
    result for the whole (uncut) layer."""
    table = ref.layer_table(dict(TINY, n_routed_experts=8, share=dict(
        TINY["share"], experts_held=[0, 8])))
    a = {n: x for n, k, x in table}["l1_moe"]
    p = ref.init_params(seed, table)["l1_moe"]
    return a, dict(p, router_bias=p["router_bias"] * bias_scale)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """The parts of the result that the four shares give, the shared expert
    counted once, equal the uncut reference."""
    a, p = _uncut(seed)
    x = _x(seed + 40)
    whole = _per_row(lambda r: ref.moe(a, p, r, "float32")[0], x)
    shared = _per_row(lambda r: ref.swiglu(r, p["shared_gate"], p["shared_up"],
                                           p["shared_down"], "float32"), x)
    total, landed = shared, 0.0
    for first in range(0, 8, 2):
        mine = dict(p, **{k: p[k][first:first + 2] for k in
                          ("experts_gate", "experts_up", "experts_down")})
        part, counters, _ = sl.moe(MOE_P.__class__(**{
            **MOE_P.__dict__, "experts_held": (first, 2)}), mine, x, CTX)
        total = total + (part - shared)
        landed += float(counters[0])
        assert float(counters[1]) == 0
    assert landed == ROWS * POS * 2, "every routed slot lands on exactly one share"
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5 * float(jnp.max(jnp.abs(whole)))


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_no_slot_is_dropped_over_weight_draws(seed):
    p = _params(seed, bias_scale=20.0)
    out, counters, chosen = sl.moe(MOE_P, p, _x(seed + 50), CTX)
    landed, dropped, fullest, emptiest = map(float, counters)
    assert dropped == 0 and 0 <= landed <= ROWS * POS * 2
    assert landed == float(np.sum((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 4)))
    assert emptiest <= landed / 2 <= fullest and fullest + emptiest == landed
    assert chosen.shape == (ROWS, POS, 2) and bool(jnp.all(jnp.isfinite(out)))


def test_every_token_to_one_held_expert_drops_nothing_and_tight_room_counts():
    """A bias that sends every token to held expert 3 (and, top 2, to absent
    expert 6): that expert takes every token, nothing is dropped, and the
    result is the reference's. With room for half the even share the rest is
    counted as dropped, not lost silently."""
    p = _params(5)
    bias = jnp.zeros((8,)).at[3].set(50.0).at[6].set(40.0)
    p = dict(p, router_bias=bias)
    x = _x(60)
    out, counters, chosen = sl.moe(MOE_P, p, x, CTX)
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.broadcast_to([3, 6], (ROWS, POS, 2)))
    assert list(map(float, counters)) == [ROWS * POS, 0.0, ROWS * POS, 0.0]
    want = _per_row(lambda r: ref.moe(MOE, p, r, "float32")[0], x)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    tight = MoEParam(**{**MOE_P.__dict__, "capacity_factor": 0.5})
    assert sl.moe_capacity(tight, ROWS * POS, tile=8) == 16
    assert sl.moe_capacity(MOE_P, ROWS * POS, tile=8) == ROWS * POS * 2
    room = sl.moe_capacity(tight, ROWS * POS)  # a tile of the grouped product
    _, counters, _ = sl.moe(tight, p, x, CTX)
    assert float(counters[1]) == max(0, ROWS * POS - room)


def test_moe_gradients_match_autodiff_of_the_reference():
    """Dispatch and combine carry hand-written transposes (gathers both
    ways): the gradients are the reference's."""
    p, x = _params(6, bias_scale=20.0), _x(61)
    mine = jax.grad(lambda p, x: jnp.sum(sl.moe(MOE_P, p, x, CTX)[0] ** 2),
                    argnums=(0, 1))(p, x)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: sum(
            jnp.sum(ref.moe(MOE, p, x[r], "float32")[0] ** 2)
            for r in range(ROWS)), argnums=(0, 1))(p, x)
    for name in want[0]:
        if name != "router_bias":
            _close(mine[0][name], want[0][name], "float32")
    _close(mine[1], want[1], "float32")
    assert float(jnp.max(jnp.abs(mine[0]["router_bias"]))) == 0


# -- the pair dispatch and combine are written as ---------------------------

def _tight_keep(chosen, experts_held, rows):
    """Which slots [tokens, k] land AND find room, as the layer's rule has
    it: sorted by held expert (stable), dropped from the END."""
    first, held = experts_held
    flat = np.asarray(chosen).reshape(-1)
    key = np.where((flat >= first) & (flat < first + held), flat - first, held)
    order = np.argsort(key, kind="stable")
    keep = np.zeros(flat.shape, bool)
    keep[order[:min(rows, int(np.sum(key < held)))]] = True
    return keep.reshape(np.shape(chosen))


#: landing share -> the held experts of 8 (every token chooses k distinct of
#: 8; for "none", of the seven that are not held)
_SHARES = {"none": (7, 1), "eighth": (3, 1), "quarter": (2, 2), "every": (0, 8)}


#: (landing share, the buffer's rows or None for every slot that can land, top
#: k, whether slots are dropped, the form `sum_walks_buffer` picks for the
#: weighted sum) at 48 tokens: room for all and a tight buffer that drops, on
#: either side of SCATTER_ROW_COST x rows = k x tokens
_PAIR_CASES = [
    *[(share, None, k, False, "gathers") for k in (2, 4)
      for share in ("none", "eighth", "quarter", "every")],
    ("every", 40, 2, True, "gathers"), ("every", 64, 4, True, "gathers"),
    ("none", 8, 2, False, "buffer"), ("none", 8, 4, False, "buffer"),
    ("eighth", 20, 2, False, "buffer"), ("eighth", 40, 4, False, "buffer"),
    ("eighth", 48, 6, False, "buffer"),
    ("quarter", 16, 2, True, "buffer"), ("quarter", 16, 4, True, "buffer"),
    ("quarter", 64, 6, True, "buffer"),  # tokens with both slots landed
    ("every", 16, 2, True, "buffer"), ("every", 40, 4, True, "buffer")]


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("share,room,k,drops,form", _PAIR_CASES)
def test_sum_by_token_is_the_dense_formula_and_rows_of_tokens_its_transpose(
        share, room, k, drops, form, policy):
    """`rows_of_tokens` and `sum_by_token` against the dense one-hot matrix
    D[r, t] = (row r holds a slot of token t that landed and found room):
    rows = D xf on those rows, sum = D^T (w_row * rows) in float32, and each
    one's `jax.vjp` is the other -- whatever share of the slots lands (none,
    1 in 8, 1 in 4, every one), for 2, 4 and 6 choices a token, with room for
    all and with a tight buffer that drops, in both policies, and in both
    forms of the weighted sum: the k gathers where the buffer is long beside
    the slots, the one scatter-add over its rows where it is short (the
    shapes are such that the layer's own rule picks the form named). Rows no
    slot landed in hold NaN: nothing may read them."""
    tokens, d = 48, 16
    rng = np.random.default_rng(7 + k)
    held = _SHARES[share]
    idx = jnp.asarray(np.stack([rng.permutation(7 if share == "none" else 8)[:k]
                                for _ in range(tokens)]), jnp.int32)
    rows = room or max(8, tokens * min(k, held[1]))
    plan, sizes, kept_sizes = sl._plan(idx, held, rows)
    keep = _tight_keep(idx, held, rows)
    assert sl.sum_walks_buffer(rows, tokens, k) == (form == "buffer")
    assert int(jnp.sum(kept_sizes)) == keep.sum() <= int(jnp.sum(sizes))
    assert (keep.sum() == rows < int(jnp.sum(sizes))) if drops else (
        keep.sum() == int(jnp.sum(sizes))), "it drops, or all find room"
    assert np.array_equal(np.asarray(plan["slot_ok"]), keep)
    n = int(keep.sum())
    assert np.asarray(plan["row_ok"]).tolist() == [True] * n + [False] * (rows - n)
    # the dense matrix, from the plan's row side alone
    ok, tok = np.asarray(plan["row_ok"]), np.asarray(plan["tok"])
    dense = jnp.asarray(ok[:, None] & (tok[:, None] == np.arange(tokens)),
                        jnp.float32)
    assert np.array_equal(np.asarray(dense.sum(0)), keep.sum(1))  # <= k a token
    if (share, room) in (("quarter", 64), ("every", 40), ("every", None)):
        assert float(dense.sum(0).max()) > 1, "tokens with several landed slots"
    dtype = jnp.float32 if policy == "float32" else jnp.bfloat16
    xf = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    w = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    y = jnp.where(ok[:, None], jnp.asarray(rng.standard_normal((rows, d)), dtype),
                  jnp.nan)
    g = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    w_row = jnp.where(ok, w.reshape(-1)[np.asarray(plan["row_slot"])], 0.0)
    clean = lambda a: jnp.where(ok[:, None], a.astype(jnp.float32), 0.0)

    def dense_sum(y, w_row):
        return jnp.einsum("rt,r,rd->td", dense, w_row, clean(y),
                          precision="highest")

    got, vjp_rows = jax.vjp(lambda x: sl.rows_of_tokens(x, plan), xf)
    assert np.array_equal(np.asarray(clean(got)), np.asarray(dense @ xf.astype(
        jnp.float32))), "a gather: exact"
    out, vjp_sum = jax.vjp(lambda y, w: sl.sum_by_token(y, w, plan), y, w)
    assert out.dtype == dtype and bool(jnp.all(jnp.isfinite(out)))
    _close(out, dense_sum(y, w_row), policy)
    # each is the other's transpose: rows' cotangent (NaN where nothing
    # landed) summed by token, the sum's cotangent fetched by row
    (dxf,) = vjp_rows(y)
    _close(dxf, dense_sum(y, ok.astype(np.float32)), policy)
    dy, dw = vjp_sum(g)
    want_dy, want_dw_row = jax.vjp(dense_sum, clean(y), w_row)[1](
        g.astype(jnp.float32))
    assert not np.any(np.asarray(dy, np.float32)[~ok]), "zero where nothing landed"
    _close(dy, want_dy, policy)
    want_dw = np.zeros((tokens * k,), np.float32)
    want_dw[np.asarray(plan["row_slot"])[ok]] = np.asarray(want_dw_row)[ok]
    assert not np.any(np.asarray(dw)[~keep])
    _close(dw, want_dw.reshape(tokens, k), policy)


@pytest.mark.parametrize("d", [sl.SCATTER_COLUMNS, sl.SCATTER_COLUMNS + 128,
                               2 * sl.SCATTER_COLUMNS])
def test_the_buffer_form_adds_a_slab_of_columns_at_a_time(d, monkeypatch):
    """Rows wider than SCATTER_COLUMNS are added in slabs of that many
    columns, the last one as narrow as what is left: one scatter-add a slab,
    and to the bit what one scatter-add of whole rows gives (a column's adds
    are the same adds in the same order)."""
    tokens, k, rows = 48, 4, 16
    rng = np.random.default_rng(11)
    idx = jnp.asarray(np.stack([rng.permutation(8)[:k] for _ in range(tokens)]),
                      jnp.int32)
    plan, _, _ = sl._plan(idx, _SHARES["quarter"], rows)
    assert sl.sum_walks_buffer(rows, tokens, k)
    y = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
    w = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    slabs = -(-d // sl.SCATTER_COLUMNS)
    by_slab = jax.jit(lambda y, w: sl.sum_by_token(y, w, plan))
    assert str(jax.make_jaxpr(by_slab)(y, w)).count("scatter-add") == slabs
    got = by_slab(y, w)
    monkeypatch.setattr(sl, "SCATTER_COLUMNS", d)
    whole = jax.jit(lambda y, w: sl.sum_by_token(y, w, plan))
    assert str(jax.make_jaxpr(whole)(y, w)).count("scatter-add") == 1
    assert got.shape == (tokens, d) and np.array_equal(
        np.asarray(got, np.float32), np.asarray(whole(y, w), np.float32))


@pytest.mark.parametrize("factor,positions,form", [
    (0.5, 1024, "buffer"), (1.0, 1024, "gathers"),
    (0.75, 2048, "buffer"), (1.0, 2048, "gathers")])
def test_moe_gradients_match_autodiff_of_the_reference_at_a_tight_buffer(
        factor, positions, form):
    """The gradients of the whole layer -- the router's (through `dw`), the
    experts' (through `dy`) and the input's (through `dxf` and the router) --
    when the buffer is too small and slots are dropped: the reference's, with
    the dropped slots' weights zeroed in it. A bias sends most tokens to the
    two held experts, so that much more lands than finds room (2,048 or 4,096
    tokens: the buffer is whole tiles of the grouped product, 512 to 2,048
    rows), and the layer's rule takes the weighted sums over the buffer's
    rows at the shorter buffers and as k gathers at the longer."""
    tight = MoEParam(**{**MOE_P.__dict__, "capacity_factor": factor})
    p = _params(7)
    p = dict(p, router_bias=jnp.zeros((8,)).at[2].set(0.4).at[3].set(0.3))
    x = _x(62, (ROWS, positions, D))
    room = sl.moe_capacity(tight, ROWS * positions)
    assert sl.sum_walks_buffer(room, ROWS * positions, 2) == (form == "buffer")
    _, counters, chosen = sl.moe(tight, p, x, CTX)
    assert float(counters[1]) == float(counters[0]) - room > 0, "it drops"
    keep = jnp.asarray(_tight_keep(chosen.reshape(-1, 2), (2, 2), room))

    def reference(p, x):
        xf = x.reshape(-1, D)
        idx, w = ref.route(MOE, p, xf)
        w = jnp.where(keep, w, 0.0)
        y = ref.swiglu(xf, p["shared_gate"], p["shared_up"], p["shared_down"],
                       "float32")
        for e in range(2):
            w_e = jnp.sum(jnp.where(idx == 2 + e, w, 0.0), axis=-1)
            y = y + w_e[:, None] * ref.swiglu(
                xf, p["experts_gate"][e], p["experts_up"][e],
                p["experts_down"][e], "float32")
        return jnp.sum(y ** 2)

    mine = jax.grad(lambda p, x: jnp.sum(sl.moe(tight, p, x, CTX)[0] ** 2),
                    argnums=(0, 1))(p, x)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(reference, argnums=(0, 1))(p, x)
    for name in want[0]:
        if name != "router_bias":
            _close(mine[0][name], want[0][name], "float32")
    _close(mine[1], want[1], "float32")


def benchmark_expert_layers(config: str):
    """(every expert layer's MoEParam, a step's tokens, the model's width) of
    the benchmark's configuration `config`, built from its own file."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark", "configs", config + ".json")
    with open(path) as f:
        c = json.load(f)
    spec = zoo.SEQUENCE_MODELS[c["model_type"]](
        c, rows=c["local_batch"], positions=c["seq_len"])
    return ([l.moe for l in spec.layers if l.type == "MoE"],
            c["local_batch"] * c["seq_len"], c["hidden_size"])


@pytest.mark.parametrize("config,k,rows,form", [
    ("nemotron3-super-tp4-ep64-tau4", 22, 22528, "buffer"),
    ("ling3-flash-ep64-tau4", 8, 4096, "buffer"),
    ("glm47-flash-ep8-tau4", 4, 16384, "gathers"),
    ("lfm2-8b-a1b-ep4-tau4", 4, 32768, "gathers")])
def test_the_weighted_sums_form_follows_the_cells_shapes(config, k, rows, form):
    """Which form the weighted sum by token takes is a function of (the
    buffer's rows, k, tokens) alone: at the benchmark's own configurations'
    expert layers -- built from their files, a step's 2 x 8,192 tokens, the
    buffer `moe_capacity` gives -- the buffer's rows are 1 in 16 of the slots
    (Nemotron-3-Super, k = 22) and 1 in 32 (Ling-3.0-flash), where one
    scatter-add over them is the cheaper, and 1 in 4 (GLM-4.7-Flash) and 1
    in 2 (LFM2-8B-A1B), where the k gathers stay."""
    layers, tokens, _ = benchmark_expert_layers(config)
    assert layers and tokens == 16384
    for p in layers:
        assert (p.num_experts_per_tok, sl.moe_capacity(p, tokens)) == (k, rows)
        assert sl.sum_walks_buffer(rows, tokens, k) == (form == "buffer")
    # one comparison of row counts, at the constant's own edge
    c_rows = sl.SCATTER_ROW_COST
    assert not sl.sum_walks_buffer(k * tokens // c_rows, tokens, k)
    assert sl.sum_walks_buffer(k * tokens // c_rows - 1, tokens, k)


# -- no per-slot scalar travels by index -------------------------------------

def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


@pytest.mark.parametrize("config,k,experts,n_group,topk_group", [
    ("nemotron3-super-tp4-ep64-tau4", 22, 512, 1, 1),
    ("ling3-flash-ep64-tau4", 8, 512, 8, 4),
    ("glm47-flash-ep8-tau4", 4, 64, 1, 1),
    ("lfm2-8b-a1b-ep4-tau4", 4, 32, 1, 1)])
def test_route_selects_the_scores_the_gather_fetched_to_the_bit(
        config, k, experts, n_group, topk_group, monkeypatch):
    """`route()` at the four configurations' own routers (their files' k,
    columns, groups, normalisation and scaling; 300 tokens of width 48): the
    chosen experts, their weights and the gradients of a weighted sum of the
    weights with respect to the tokens and to the router equal, BIT FOR BIT,
    those of the form the layer had -- `take_along_axis` over the scores,
    whose transpose is a scatter-add -- with exact ties among the scores (two
    pairs of columns with one weight vector and one bias: `top_k`'s order on
    ties is the same order) and a column whose score overflows to exactly 1.
    Op by op: a token's k columns are distinct, so every select-and-sum has
    one term that is not 0. Compiled as one program XLA folds the
    normaliser's sum over k into the select's sum over the columns, which
    may add the k scores in another order: the same experts, the weights and
    gradients to a few units in the last place."""
    p = benchmark_expert_layers(config)[0][0]
    assert (p.num_experts_per_tok, p.n_routed_experts, p.n_group,
            p.topk_group) == (k, experts, n_group, topk_group)
    tokens, d = 300, 48
    rng = np.random.default_rng(experts + k)
    router = rng.standard_normal((d, experts)).astype(np.float32)
    bias = (0.1 * rng.standard_normal(experts)).astype(np.float32)
    for a, b in ((1, 5), (experts - 2, 7)):  # exact ties, within and across groups
        router[:, a], bias[a] = router[:, b], bias[b]
    params = {"router": jnp.asarray(router), "router_bias": jnp.asarray(bias)}
    xf = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    xf = xf.at[0].set(40.0 * jnp.sign(params["router"][:, 3]))  # sigmoid -> 1.0
    c = jnp.asarray(rng.standard_normal((tokens, k)), jnp.float32)

    def run():  # a function of its own a form: jax caches traces by function
        def weighed(params, xf):
            idx, w = sl.route(p, params, xf)
            return jnp.sum(w * c), (idx, w)

        both = jax.value_and_grad(weighed, argnums=(0, 1), has_aux=True)
        return (both(params, xf), jax.jit(both)(params, xf),
                str(jax.make_jaxpr(both)(params, xf)))

    got, got_jit, text = run()
    assert "gather" not in text and "scatter" not in text
    monkeypatch.setattr(sl, "chosen_scores",
                        lambda s, idx: jnp.take_along_axis(s, idx, axis=-1))
    want, want_jit, text = run()
    assert "gather" in text and "scatter" in text
    (_, (idx, w)), (dparams, dxf) = got
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    s = np.asarray(jax.nn.sigmoid(jnp.dot(xf, params["router"],
                                          precision="highest")))
    assert s[0, 3] == 1.0 and np.any(np.sort(s + bias, axis=1)[:, 1:]
                                     == np.sort(s + bias, axis=1)[:, :-1])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(_bits(a), _bits(b))
    assert not np.any(np.asarray(dparams["router_bias"]))
    assert np.any(np.asarray(dparams["router"])) and np.any(np.asarray(dxf))
    for a, b in zip(jax.tree.leaves(got_jit), jax.tree.leaves(want_jit)):
        if a.dtype == jnp.int32:
            assert np.array_equal(a, b) and np.array_equal(a, idx)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * float(
                jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("share,room,k,what", [
    ("none", 8, 2, "nothing lands: every row is empty"),
    ("eighth", 40, 4, "rows nothing landed in, none dropped"),
    ("quarter", 16, 4, "slots dropped, the buffer full"),
    ("every", 40, 2, "slots dropped, several landed slots a token")])
def test_dw_is_scattered_from_the_rows_as_the_slots_fetched_it(
        share, room, k, what):
    """`sum_by_token`'s gradient with respect to the weights: one scalar a
    buffer row, <rows[r], g[its token]>, placed at the row's slot by ONE
    scatter-add of the buffer's scalars -- bit for bit what the slot side
    fetched (`where(slot_ok, dw_row[slot_row], 0)`, tokens x k fetches): a
    slot lands in at most one row, a dropped slot in none, and a row nothing
    landed in (NaN in it) adds 0 wherever its `row_slot` points -- at a slot
    that landed nowhere here, at a dropped one, or at slot 0 where the buffer
    is longer than the slots."""
    tokens, d = 48, 16
    rng = np.random.default_rng(3 + k)
    held = _SHARES[share]
    idx = jnp.asarray(np.stack([rng.permutation(7 if share == "none" else 8)[:k]
                                for _ in range(tokens)]), jnp.int32)
    plan, sizes, kept_sizes = sl._plan(idx, held, room)
    ok = np.asarray(plan["row_ok"])
    landed, kept = int(jnp.sum(sizes)), int(jnp.sum(kept_sizes))
    assert (kept < landed) == what.startswith("slots dropped")
    assert kept == ok.sum()
    y = jnp.where(ok[:, None], jnp.asarray(rng.standard_normal((room, d)),
                                           jnp.bfloat16), jnp.nan)
    w = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    g = jnp.asarray(rng.standard_normal((tokens, d)), jnp.bfloat16)
    grad = lambda y, w: jax.vjp(lambda y, w: sl.sum_by_token(y, w, plan),
                                y, w)[1](g)
    assert str(jax.make_jaxpr(grad)(y, w)).count("scatter-add") >= 1
    for dy, dw in (grad(y, w), jax.jit(grad)(y, w)):
        dw_row = jnp.sum(y.astype(jnp.float32) * sl.rows_of_tokens(g, plan).astype(
            jnp.float32), axis=-1)
        by_slot = jnp.where(plan["slot_ok"], jnp.take(dw_row, plan["slot_row"]), 0.0)
        assert dw.shape == (tokens, k) and dw.dtype == jnp.float32
        assert np.array_equal(_bits(dw), _bits(by_slot))
        assert np.count_nonzero(np.asarray(dw)) == kept
        assert not np.any(np.asarray(dy, np.float32)[~ok])


# -- the whole model ---------------------------------------------------------

def _net():
    return CompiledNet.compile(zoo.glm4_moe_lite(TINY, rows=ROWS, positions=POS))


def _ids(seed, shape=(ROWS, POS)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256, jnp.int32)


def _reference_loss_and_grads(params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: sum(
            ref.row_loss(p, ids[r], layers=LAYERS)[0] for r in range(ROWS)) / ROWS)(params)


@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("float32", 3), ("bfloat16", 1)])
def test_two_headed_loss_and_gradients_match_the_reference(policy, seed):
    net, params, ids = _net(), ref.init_params(seed, LAYERS), _ids(seed + 70)
    with precision.policy(policy):
        (loss, blobs), grads = jax.jit(jax.value_and_grad(
            lambda p: net.loss_fn("loss")(p, {"tokens": ids}, None),
            has_aux=True))(params)
    want, want_grads = _reference_loss_and_grads(params, ids)
    assert float(loss) == pytest.approx(float(want), abs=2e-5 if policy == "float32" else 2e-3)
    assert float(blobs["loss_next"] + blobs["loss_mtp"]) == pytest.approx(float(loss), rel=1e-6)
    assert set(grads) == set(want_grads)
    for layer, lp in want_grads.items():
        for name, g in lp.items():
            err = float(jnp.linalg.norm(grads[layer][name] - g)) / (
                float(jnp.linalg.norm(g)) + 1e-30)
            if name == "router_bias":
                assert float(jnp.max(jnp.abs(grads[layer][name]))) == 0
            else:
                # bf16: an expert here sees some tens of tokens, and one
                # slot that flips its expert on a rounding moves its gradient
                assert err < (2e-5 if policy == "float32" else 0.3), (layer, name, err)


@pytest.mark.parametrize("other", ["no_blocks", "blocks_that_keep_inputs_only"])
def test_recomputation_blocks_change_no_number_and_sharing_sums_gradients(
        other, monkeypatch):
    """The net as built (blocks that keep what their layers name) against
    the same net with no recomputation at all, and against blocks under the
    bare `jax.checkpoint`: the same loss and gradients to the bit."""
    spec = zoo.glm4_moe_lite(TINY, rows=ROWS, positions=POS)
    assert {l.block for l in spec.layers} == {None, "l0", "l1", "l2", "head", "mtp",
                                              "mtp_head"}
    params, ids = ref.init_params(7, LAYERS), _ids(77)
    f = lambda net: jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn("loss")(p, {"tokens": ids}, None)[0]))(params)
    l1, g1 = f(_net())
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
    no_mtp = CompiledNet.compile(zoo.glm4_moe_lite(
        dict(TINY, num_nextn_predict_layers=0), rows=ROWS, positions=POS))
    g0 = jax.grad(lambda p: no_mtp.loss_fn("loss")(p, {"tokens": ids}, None)[0])(
        {k: v for k, v in params.items() if k != "mtp"})
    assert not np.allclose(g0["lm_head"]["w"], g1["lm_head"]["w"], rtol=1e-3)
    with pytest.raises(ValueError, match="param_from"):
        CompiledNet.compile(spec.replace(layers=tuple(
            LayerSpec(**{**l.__dict__, "param_from": "nowhere"})
            if l.name == "mtp_head" else l for l in spec.layers)))


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tmp_path):
    from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.utils.config import RunConfig

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    solver = {"base_lr": 0.02, "lr_policy": "fixed", "momentum": 0.9,
              "weight_decay": 1e-4}
    cfg = RunConfig.from_dict({
        "model": str(path), "tau": 3, "local_batch": ROWS, "precision": "float32",
        "solver": solver, "n_devices": 1, "health": {"enabled": False}})
    spec = resolve_spec(cfg)
    assert spec.inputs == (InputSpec("tokens", (ROWS, POS), "int32"),)
    assert resolve_spec(cfg, tokens=(ROWS, 16)).inputs[0].shape == (ROWS, 16)
    trainer = build_trainer(cfg, spec, make_mesh(1))
    params, ids = ref.init_params(8, LAYERS), np.asarray(_ids(78, (3, ROWS, POS)))
    # ids stay int32 on their way to the device
    placed = trainer.place_batches({"tokens": ids})
    assert placed["tokens"].dtype == jnp.int32
    assert np.array_equal(np.asarray(placed["tokens"]), ids)
    state, loss = trainer.train_round(trainer.state_from_params(params), placed,
                                      jax.random.PRNGKey(0))
    want = ref.round_reference(params, lambda t, w: ids[t], tau=3, solver=solver,
                               layers=LAYERS, mtp_weight=0.3)
    assert float(loss) == pytest.approx(want["loss"], abs=2e-5)
    for layer, lp in params.items():
        for name, p0 in lp.items():
            key = f"{layer}/{name}"
            upd = float(jnp.linalg.norm(state.params[layer][name][0] - p0))
            mom = float(jnp.linalg.norm(state.momentum[layer][name][0]))
            assert upd == pytest.approx(want["update_norms"][key], rel=2e-4, abs=1e-9), key
            assert mom == pytest.approx(want["momentum_norms"][0][key], rel=2e-4, abs=1e-9), key
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
    assert kept == {sl.ATTN_CORE: {
        "maker": "splash_mha_fwd", "step_bodies": 0, "forward": 0, "backward": 0,
        "kept_bytes": 4 * ROWS * POS * MLA_P.num_heads * MLA_P.v_head_dim * 4}}
    # ... and the one dense block its SwiGLU's two input products, neither
    # made again (the shared experts of the other blocks name nothing)
    assert (pre["maker"], pre["backward"]) == (sl.MLP_PRE, 0) and pre["forward"] >= 2
    assert pre["kept_bytes"] == 2 * ROWS * POS * TINY["intermediate_size"] * 4
    assert obs_device.program_part("recompute")["train_round"] == report["recompute"]
    with pytest.raises(ValueError, match="model_type"):
        path.write_text(json.dumps(dict(TINY, model_type="other")))
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


def _attention_block(mla_p=MLA_P, positions=POS, d=D):
    """(net, params, x, loss) of one recomputation block as a decoder's
    attention half is: norm, latent attention, residual sum."""
    tag = dict(block="b")
    net = CompiledNet.compile(NetSpec(
        name="blk", inputs=(InputSpec("x", (ROWS, positions, d)),), layers=(
            LayerSpec(name="n", type="RMSNorm", bottoms=("x",), tops=("xn",),
                      rmsnorm=RMSNormParam(), **tag),
            LayerSpec(name="a", type="MLAttention", bottoms=("xn",), tops=("y",),
                      mla=mla_p, **tag),
            LayerSpec(name="r", type="Eltwise", bottoms=("x", "y"), tops=("z",),
                      **tag))))
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    loss = lambda p, x: jnp.sum(
        net.apply(p, {"x": x}, train=True)["z"].astype(jnp.float32))
    return net, params, jax.ShapeDtypeStruct((ROWS, positions, d), jnp.float32), loss


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
    _, params, x, loss = _attention_block(KERNEL_MLA_P, KERNEL_POS)
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
    _, params, x, loss = _attention_block()
    assert _kept(loss, params, x) == [
        ((ROWS, MLA_P.num_heads, POS, MLA_P.v_head_dim), "float32")]


def test_a_block_keeps_the_cores_output_and_statistics_on_the_kernel_path(
        kernel_path, monkeypatch):
    net, params, x, loss = _attention_block(KERNEL_MLA_P, KERNEL_POS)
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
    name: an expert block's policy is what it was (nothing for `MoE`), and
    a gradient through the layer names no value."""
    assert "MoE" not in sl.KEPT_NAMES and sl.KEPT_NAMES["MTP"] == (sl.ATTN_CORE,)
    net = _net()
    by_block = {}
    for l in net.spec.layers_for_phase("TRAIN"):
        by_block.setdefault(l.block, []).append(l)
    expert = [ls for b, ls in by_block.items() if b is not None
              and any(l.type == "MoE" for l in ls)]
    assert expert and all(
        net_mod._kept_names(ls) == (sl.ATTN_CORE,) for ls in expert)
    dense = [ls for b, ls in by_block.items() if b is not None
             and any(l.type == "GatedMLP" for l in ls)]
    assert [net_mod._kept_names(ls) for ls in dense] == [
        (sl.ATTN_CORE, sl.MLP_PRE)]
    assert MOE_P.n_shared_experts == 1
    p, x = _params(1), _x(2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(sl.moe(MOE_P, p, x, CTX)[0])))(p, x)
    assert "name=" + sl.MLP_PRE not in str(jaxpr) and " name[" not in str(jaxpr)


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
