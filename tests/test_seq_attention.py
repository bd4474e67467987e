"""The sequence-model layer set (model/seq_layers.py) against the benchmark's
plain reference (`benchmark/configs/glm47-flash-ep8-tau4.reference.py`, which
imports nothing of the program) at small widths on the CPU: GLM's layers one
by one, the embedding's shift and the masked loss by hand, the rotary turn
and the causal core against direct formulas, and latent attention against
the positions-first formula the published code writes. The expert layer is
`test_seq_experts.py`, the whole model `test_seq_model.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (CTX, D, MLA_P, MOE_P, POS, ROWS, _params, _x, case,
                         check_layer)
from sparknet_tpu import precision
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.layers import LAYER_IMPLS
from sparknet_tpu.model.spec import (EltwiseParam, EmbedParam, LayerSpec,
                                     LossParam)

GLM = case("glm4_moe_lite")
ref = GLM.ref
ATTN, MOE = GLM.table["l0_attn"][1], GLM.table["l1_moe"][1]


# -- layer by layer against the reference ------------------------------------

#: kind -> (seed -> the layer's weights, the program's layer, the reference's
#: on one row)
LAYER_TABLE = {
    "rmsnorm": (lambda seed: 1.0 + 0.1 * _x(seed + 1, (D,)),
                lambda scale, x: sl._rms(x, scale, 1e-5),
                lambda scale, r: ref.rmsnorm(r, scale, 1e-5)),
    "mla": (lambda seed: _params(seed, "l0_attn"),
            lambda p, x: sl.mla(MLA_P, p, x, CTX),
            lambda p, r: ref.mla(ATTN, p, r, "float32")),
    "mlp": (lambda seed: _params(seed, "l0_mlp"),
            lambda p, x: sl._swiglu(x, p["gate"], p["up"], p["down"]),
            lambda p, r: ref.swiglu(r, p["gate"], p["up"], p["down"], "float32")),
    "moe": (lambda seed: _params(seed, "l1_moe", bias_scale=20.0),
            lambda p, x: sl.moe(MOE_P, p, x, CTX)[0],
            lambda p, r: ref.moe(MOE, p, r, "float32")[0]),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["rmsnorm", "mla", "mlp", "moe"])
def test_layer_matches_the_reference(kind, policy):
    check_layer(LAYER_TABLE, kind, policy)


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

