"""The dense byte-level decoder (`zoo.evabyte`): EVA attention -- an exact
causal window beside learned chunk summaries under one softmax -- norms scaled
by 1 + w, a float32 residual stream, several next-byte heads -- against the
benchmark's plain reference
(`benchmark/configs/evabyte-l4-tau4.reference.py`, which imports nothing of
the program and writes the scores out a window at a time) at small widths on
the CPU: the layer, the whole net's loss and every stored parameter's
gradient, the mask by hand, the kernel under the Pallas interpreter against
the exact path, and what the builder refuses.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from model_cases import (CTX, D, POS, ROOT, ROWS, _close, _x, case, check_layer,
                         check_loss_and_every_gradient, check_products_kept,
                         check_round, compiled,
                         max_err, program_round, reference_loss_and_grads,
                         tiny_round)
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import layers as base_layers
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.spec import (EltwiseParam, EVAttentionParam,
                                     GQAttentionParam, LayerSpec, LossParam,
                                     RMSNormParam)
from sparknet_tpu.ops import attention as attention_ops
from sparknet_tpu.ops import eva as eva_ops

EVA = case("evabyte")
ref, TINY, LAYERS, TABLE = EVA.ref, EVA.tiny, EVA.layers, EVA.table
W, C = 8, 2
EVA_P = EVAttentionParam(num_heads=2, head_dim=32, window_size=W, chunk_size=C,
                         rope_theta=1e5)
_ids = EVA.ids  # bytes: a vocabulary of 32


def _params(seed, scale=1.0):
    """The reference's draw; `scale` > 1 spreads the attention's weights so
    that scores, summaries' weights and norms' scales differ visibly."""
    p = EVA.params(seed)
    if scale != 1.0:
        for name, (kind, _) in TABLE.items():
            if kind == "eva":
                p[name] = {k: v * scale for k, v in p[name].items()}
            elif kind == "rmsnorm":
                p[name] = {"scale": 0.1 * _x(seed + len(name), (D,))}
    return p


def _net(rows=ROWS, positions=POS, **over):
    if (rows, positions) == (ROWS, POS) and not over:
        return compiled("evabyte")
    return CompiledNet.compile(EVA.spec(rows=rows, positions=positions, **over))


# -- the layer and the net against the reference -----------------------------

#: kind -> (seed -> the layer's weights, the program's layer, the reference's
#: on one row)
LAYER_TABLE = {"eva": (lambda seed: _params(seed, scale=4.0)["l0_attn"],
                       lambda p, x: sl.eva(EVA_P, p, x, CTX),
                       lambda p, r: ref.eva(TABLE["l0_attn"][1], p, r, "float32"))}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_layer_matches_the_reference(policy):
    check_layer(LAYER_TABLE, "eva", policy)


def test_layer_gradients_match_the_reference():
    p, x = _params(3, scale=8.0)["l0_attn"], _x(3)
    t = _x(4)
    got = jax.grad(lambda p, x: jnp.sum(sl.eva(EVA_P, p, x, CTX) * t), (0, 1))(p, x)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: sum(jnp.sum(ref.eva(
            TABLE["l0_attn"][1], p, x[r], "float32") * t[r]) for r in range(ROWS)),
            (0, 1))(p, x)
    for name in p:
        _close(got[0][name], want[0][name], tol=1e-4)
        assert float(jnp.max(jnp.abs(want[0][name]))) > 0, name
    _close(got[1], want[1], tol=1e-4)


@pytest.mark.parametrize("policy,seed", [("float32", 1), ("float32", 2),
                                         ("bfloat16", 1)])
def test_loss_and_gradients_match_the_reference(policy, seed):
    f32 = policy == "float32"
    blobs, _, want = check_loss_and_every_gradient(
        "evabyte", policy, _params(seed, scale=4.0), _ids(seed),
        loss_tol=2e-5 if f32 else 2e-2, grad_tol=2e-5 if f32 else 0.08,
        err=max_err)
    assert blobs["x1"].dtype == jnp.float32 == blobs["lm_head"].dtype
    assert blobs["l0_attn"].dtype == jnp.dtype(policy)
    # the summaries' own parameters are reached, in every layer
    for layer in ("l0_attn", "l1_attn"):
        assert float(jnp.max(jnp.abs(want[layer]["phi"]))) > 0
        assert float(jnp.max(jnp.abs(want[layer]["mu"]))) > 0


def test_a_program_without_summaries_is_another_model():
    """The reference with its summary columns masked out: another loss, and
    nothing reaches mu and phi."""
    params, ids = _params(1, scale=4.0), _ids(1)
    whole, _ = reference_loss_and_grads("evabyte")(params, ids)
    blind, grads = reference_loss_and_grads("evabyte", summaries=False)(params, ids)
    assert abs(float(whole) - float(blind)) > 1e-4
    assert not np.any(np.asarray(grads["l0_attn"]["phi"]))
    assert not np.any(np.asarray(grads["l1_attn"]["mu"]))


def test_parameter_counts():
    net = _net()
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes(LAYERS)
    with open(os.path.join(ROOT, "benchmark", "configs", "evabyte-l4-tau4.json")) as f:
        config = json.load(f)
    big = CompiledNet.compile(zoo.evabyte(config, rows=1, positions=16384))
    n = sum(int(np.prod(v.shape)) for lp in jax.eval_shape(
        big.init_params, jax.random.PRNGKey(0)).values() for v in lp.values())
    assert n == ref.n_params() == config["n_params"] == 821_366_784
    # norms start at zero (the scale is 1 + w), every matrix, mu and phi drawn
    params = net.init_params(jax.random.PRNGKey(0))
    assert not np.any(np.asarray(params["l0_attn_norm"]["scale"]))
    assert 0.03 < float(jnp.std(params["l0_attn"]["phi"])) < 0.07  # init_std 0.05
    assert sl.param_defaults("scale").decay_mult == 0.0
    assert sl.param_defaults("phi").decay_mult == 1.0 == sl.param_defaults("mu").lr_mult


# -- within one window: plain causal attention -------------------------------

def test_a_row_within_one_window_is_gqa_and_plain_causal_attention(monkeypatch):
    n = W  # 8 positions: one window, no chunk behind any query
    p, x = _params(5, scale=8.0)["l0_attn"], _x(5, (ROWS, n, D))
    got = sl.eva(EVA_P, p, x, CTX)
    # grouped-query attention with as many key/value heads and its norms off
    monkeypatch.setattr(sl, "_rms", lambda t, scale, eps: (t * scale).astype(t.dtype))
    gqa_p = GQAttentionParam(num_heads=2, num_kv_heads=2, head_dim=32, rope_theta=1e5)
    ones = jnp.ones((32,), jnp.float32)
    same = sl.gqa(gqa_p, dict(p, q_norm=ones, k_norm=ones), x, CTX)
    _close(got, same, tol=1e-6)
    monkeypatch.undo()
    # and the exact op, from the projections by hand
    heads = lambda name: (x @ p[name]).reshape(ROWS, n, 2, 32)
    turn = lambda t: jnp.swapaxes(sl.rotary(jnp.swapaxes(t, 1, 2), 1e5, 32), 1, 2)
    o = attention_ops.attention(turn(heads("q")), turn(heads("k")), heads("v"),
                                causal=True)
    _close(got, o.reshape(ROWS, n, D) @ p["o"], tol=1e-5)
    # and longer rows are whole windows of whole chunks
    with pytest.raises(ValueError, match="no whole windows"):
        sl.eva(EVA_P, p, _x(5, (ROWS, 12, D)), CTX)


# -- the mask, by hand -------------------------------------------------------

def test_the_mask_by_hand():
    mask = eva_ops.WindowSummaryMask(POS, W, C)
    assert mask.shape == (32, 32 + 16)
    dense = mask.dense()
    for i in range(POS):
        w = i // W
        own = np.flatnonzero(dense[i, :POS])
        assert own.tolist() == list(range(w * W, i + 1)), i
        chunks = np.flatnonzero(dense[i, POS:])
        assert chunks.tolist() == list(range(w * W // C)), i  # every chunk before
        assert len(chunks) == w * (W // C)
    assert hash(mask) == hash(eva_ops.WindowSummaryMask(POS, W, C))
    # traced positions (inside a kernel) give what numpy's give
    traced = jax.jit(lambda q, kv: mask(q, kv))(
        jnp.arange(POS)[:, None], jnp.arange(48)[None, :])
    assert np.array_equal(np.asarray(traced), dense)
    # the published sizes: 448 summaries a query on average
    big = eva_ops.WindowSummaryMask(16384, 2048, 16)
    assert big.shape == (16384, 17408)
    assert np.mean([big(i, np.arange(16384, 17408)).sum() for i in range(0, 16384, 64)]) == 448


def test_a_query_reads_the_chunks_before_its_window_and_no_other():
    """Move one chunk's keys (chunk 5: positions 10, 11, in window 1): the
    queries of windows 0 and 1 up to position 9 stay; position 10 on (its own
    keys) and windows 2 and 3 (its summary) move."""
    p, x = _params(6, scale=8.0)["l0_attn"], _x(6, (1, POS, D))
    base = sl.eva(EVA_P, p, x, CTX)
    # a change of x at positions 10 and 11 moves those positions' k AND v AND q
    moved = sl.eva(EVA_P, p, x.at[0, 10:12].add(1.0), CTX)
    changed = np.flatnonzero(np.max(np.abs(np.asarray(moved - base))[0], axis=-1) > 1e-7)
    assert changed.tolist() == [10, 11, 12, 13, 14, 15] + list(range(16, 32))
    # the summaries alone: only later windows read chunk 5's
    q, k, v = (jnp.swapaxes(_x(s, (1, POS, 2, 32)), 1, 2) for s in (7, 8, 9))
    mask = eva_ops.WindowSummaryMask(POS, W, C)

    def core(k_s):
        k_all = jnp.concatenate([k, k_s], axis=2)
        v_all = jnp.concatenate([v, jnp.ones((1, 2, 16, 32))], axis=2)
        return sl.attention_core(q, k_all, v_all, CTX, mask)

    k_s = jnp.swapaxes(_x(10, (1, 16, 2, 32)), 1, 2)
    moved = np.max(np.abs(np.asarray(core(k_s.at[:, :, 5].add(3.0)) - core(k_s))),
                   axis=(0, 1, 3))
    assert np.flatnonzero(moved > 1e-7).tolist() == list(range(16, 32))


def test_chunk_summaries_by_hand():
    k, v = _x(11, (1, 2, 8, 32)), _x(12, (1, 2, 8, 32))
    mu, phi = _x(13, (2, 32)), _x(14, (2, 32))
    k_s, v_s = eva_ops.chunk_summaries(k, v, mu, phi, chunk=2)
    assert k_s.shape == v_s.shape == (1, 2, 4, 32)
    kn, vn, phin = (np.asarray(t, np.float64) for t in (k, v, phi))
    for h in range(2):
        for c in range(4):
            logits = kn[0, h, 2 * c:2 * c + 2] @ phin[h] / np.sqrt(32)
            a = np.exp(logits - logits.max()); a /= a.sum()
            np.testing.assert_allclose(v_s[0, h, c], a @ vn[0, h, 2 * c:2 * c + 2],
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                k_s[0, h, c], kn[0, h, 2 * c:2 * c + 2].mean(0) + np.asarray(mu)[h],
                rtol=1e-5, atol=1e-6)
    # the same key at every position of a chunk: the weights are even, the
    # summary value is the mean of the values
    same = jnp.broadcast_to(k[:, :, :1], k.shape)
    _, v_even = eva_ops.chunk_summaries(same, v, mu, phi, chunk=4)
    np.testing.assert_allclose(v_even, np.asarray(v).reshape(1, 2, 2, 4, 32).mean(3),
                               rtol=1e-5, atol=1e-6)
    # summaries keep the compute dtype and are made in float32
    kb, vb = eva_ops.chunk_summaries(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                                     mu, phi, chunk=2)
    assert kb.dtype == vb.dtype == jnp.bfloat16
    _close(kb, k_s, "bfloat16")


# -- the attention core ------------------------------------------------------

def test_attention_core_without_a_mask_is_what_it_was():
    """The same values bit for bit and the same program text as the
    expression every attention ran before there was a mask."""
    q, k, v = (_x(s, (2, 4, 16, 32)) for s in (15, 16, 17))
    k, v = k[:, :2], v[:, :2]  # two key/value heads under four query heads

    def was(q, k, v):
        swap = lambda x: jnp.swapaxes(x, 1, 2)
        spread = lambda x: swap(jnp.repeat(x, 2, axis=1))
        return checkpoint_name(swap(attention_ops.attention(
            swap(q), spread(k), spread(v), causal=True, scale=1.0)), sl.ATTN_CORE)

    now = lambda q, k, v: sl.attention_core(q, k, v, CTX)
    assert np.array_equal(np.asarray(now(q, k, v)), np.asarray(was(q, k, v)))
    assert str(jax.make_jaxpr(now)(q, k, v)) == str(jax.make_jaxpr(was)(q, k, v))
    grad = lambda f: jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a)), (0, 1, 2)))(q, k, v)
    assert str(grad(now)) == str(grad(was))


@pytest.mark.parametrize("dtype", ["bfloat16"])
def test_the_kernel_under_the_interpreter_equals_the_exact_path(monkeypatch, dtype):
    """splash attention under the new mask with more key columns than
    queries (1,024 queries, 1,152 columns: four windows of 256 in chunks of
    8, tiles of 128), forward and every gradient, against the exact path."""
    monkeypatch.setattr(sl, "ATTN_BLOCKS", (128, 128, 128))
    n, w, c, d = 1024, 256, 8, 128
    mask = eva_ops.WindowSummaryMask(n, w, c)
    assert mask.shape == (1024, 1152)
    dt = jnp.dtype(dtype)
    q = (_x(18, (1, 2, n, d)) / np.sqrt(d)).astype(dt)
    k, v = (_x(s, (1, 2, mask.shape[1], d)).astype(dt) for s in (19, 20))
    t = _x(21, (1, 2, n, d))
    kernel = sl._splash(2, n, mask, True)
    table = np.asarray(kernel.fwd_mask_info.block_mask)
    # a window's two blocks of queries meet three of its four pairs with its
    # two blocks of keys; the 128 summaries are one block, which the six
    # blocks of queries of windows 1 to 3 visit: at most three a query block
    assert np.count_nonzero(table) == 4 * 3 + 3 * 2 and table.shape[-1] == 3

    def by_kernel(q, k, v):
        return jnp.sum(jax.vmap(kernel)(q, k, v).astype(jnp.float32) * t)

    def exact(q, k, v):
        with precision.policy(dtype):
            return jnp.sum(sl.attention_core(q, k, v, CTX, mask).astype(jnp.float32) * t)

    got, g_got = jax.value_and_grad(by_kernel, (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(exact, (0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) < 0.02 * abs(float(want)) + 0.5
    for a, b in zip(g_got, g_want):
        _close(a, b, "bfloat16")
    # the summaries a query may not read get no gradient
    dk = np.asarray(g_got[1], np.float32)[0, :, n:]
    assert np.any(dk[:, :w // c * 3]) and not np.any(dk[:, w // c * 3:])


def test_eva_core_blocks_and_scopes():
    net = _net()
    assert net.eva_scopes() == ({"EVAttention": ("summaries", "core")}, {
        "keys_per_query": 48, "blocks_visited": 0, "blocks": 0})
    assert sl.eva_core_blocks(EVA_P, 8) == {"keys_per_query": 8, "blocks_visited": 0,
                                            "blocks": 0}
    assert net.kept_makers() == {sl.ATTN_CORE: "splash_mha_fwd", sl.MLP_PRE: sl.MLP_PRE, sl.IP_OUT: sl.IP_OUT}
    assert net.attention_scopes() == ({"EVAttention": ""}, POS)
    assert net.delta_scopes() == ({}, ()) and net.routing_scopes() == ((), 0)
    assert sl.KEPT_NAMES["EVAttention"] == (sl.ATTN_CORE,)
    assert zoo.SEQUENCE_MODELS["evabyte"] is zoo.evabyte
    assert compiled("glm4_moe_lite").eva_scopes() == ({}, None)
    lowered = jax.jit(lambda p, b: net.apply(p, b, train=True)["loss"]).lower(
        net.init_params(jax.random.PRNGKey(0)), {"tokens": _ids(1)}).as_text(debug_info=True)
    assert "EVAttention/l0_attn/summaries" in lowered
    assert "EVAttention/l1_attn/core" in lowered


def test_the_eva_part_of_a_compiled_text():
    """`obs.device.eva` on a made-up compiled text: kernels counted by phase
    in the step body that has most, the summaries' bytes under their scope,
    what the core is given passed through."""
    from sparknet_tpu.obs import device
    call = ('custom-call(%p), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(train_round)/tau_step/')
    text = "\n".join([
        "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        f"  %fwd.1 = f32[4]{{0}} {call}jvp(EVAttention/l0_attn)/core/splash_mha_fwd/pallas_call\"}}",
        f"  %fwd.2 = f32[4]{{0}} {call}jvp(EVAttention/l1_attn)/core/splash_mha_fwd/pallas_call\"}}",
        f"  %bwd.1 = f32[4]{{0}} {call}transpose(jvp(EVAttention/l1_attn))/core/splash_mha_dkv/pallas_call\"}}",
        '  %sum.1 = f32[4]{0} add(%fwd.1, %fwd.2), metadata={op_name="jit(train_round)/tau_step/jvp(EVAttention/l0_attn)/summaries/add"}',
        '  ROOT %out = f32[4]{0} add(%sum.1, %bwd.1), metadata={op_name="jit(train_round)/tau_step/jvp(GatedMLP/l0_mlp)/add"}',
        "}"])
    ops = device.parse_hlo_ops(text)
    core = {"keys_per_query": 48, "blocks_visited": 8, "blocks": 24}
    got = device.eva(ops, sl.EVA_SCOPES, core)
    assert got == {"layers": 2, "core_forward_calls": 2, "core_backward_calls": 1,
                   **core, "summary_instructions": 1, "summary_bytes": 48}
    assert device.eva(ops, {}) == {}
    assert "eva" in device.REPORT_PARTS


# -- the norm, the residual, the heads ---------------------------------------

def test_the_unit_offset_norm_by_hand():
    x, w = _x(22, (2, 4, 8)), 0.1 * _x(23, (8,))
    layer = lambda offset: LayerSpec(name="n", type="RMSNorm", bottoms=("x",), tops=("n",),
                                     rmsnorm=RMSNormParam(eps=1e-5, unit_offset=offset))
    xn = np.asarray(x, np.float64)
    normed = xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-5)
    got, = sl.apply_rmsnorm(layer(True), {"scale": w}, (x,), CTX)
    np.testing.assert_allclose(got, normed * (1 + np.asarray(w)), rtol=1e-5, atol=1e-6)
    got, = sl.apply_rmsnorm(layer(False), {"scale": w}, (x,), CTX)
    np.testing.assert_allclose(got, normed * np.asarray(w), rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(sl.init_rmsnorm(None, layer(True), ((2, 4, 8),))["scale"]))
    assert np.all(np.asarray(sl.init_rmsnorm(None, layer(False), ((2, 4, 8),))["scale"]) == 1)


def test_the_float32_residual_by_hand():
    """Under bfloat16 a sum taken in float32 keeps what a bfloat16 sum
    rounds away, and stays float32 for the next block."""
    layer = lambda f32: LayerSpec(name="s", type="Eltwise", bottoms=("a", "b"), tops=("s",),
                                  eltwise=EltwiseParam(float32=f32))
    a = jnp.full((2, 4), 256.0, jnp.float32)         # carried from the block before
    b = jnp.full((2, 4), 1.0, jnp.bfloat16)          # a layer's bf16 result
    kept, = sl.apply_eltwise(layer(True), None, (a, b), CTX)
    assert kept.dtype == jnp.float32 and np.all(np.asarray(kept) == 257.0)
    lost, = sl.apply_eltwise(layer(False), None, (a.astype(jnp.bfloat16), b), CTX)
    assert lost.dtype == jnp.bfloat16 and np.all(np.asarray(lost, np.float32) == 256.0)
    both, = sl.apply_eltwise(layer(True), None, (b, b), CTX)
    assert both.dtype == jnp.float32


def test_the_eight_targets_and_their_ignore_labels_by_hand():
    """A row of 12 ids, eight heads over 5 ids: head m at position i is held
    against id i + 1 + m; the positions past the row's end are not scored;
    the loss is the mean over the heads of each head's own mean."""
    ids = np.array([[3, 1, 4, 1, 0, 2, 2, 3, 4, 0, 1, 2]], np.int32)
    heads, vocab, n = 8, 5, 12
    logits = np.asarray(_x(24, (1, n, heads * vocab)), np.float64)
    targets = np.asarray(ref.head_targets(jnp.asarray(ids[0]), heads))
    for i in range(n):
        for m in range(heads):
            assert targets[i, m] == (ids[0, i + 1 + m] if i + 1 + m < n else -1)
    assert (targets >= 0).sum(0).tolist() == [11, 10, 9, 8, 7, 6, 5, 4]
    per_head = []
    for m in range(heads):
        lg = logits[0, :, m * vocab:(m + 1) * vocab]
        logp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
        scored = [i for i in range(n) if i + 1 + m < n]
        per_head.append(-np.mean([logp[i, ids[0, i + 1 + m]] for i in scored]))
    want = float(np.mean(per_head))
    p = LossParam(label_shift=1, heads=heads)
    got = base_layers._masked_softmax_loss(p, jnp.asarray(logits, jnp.float32), jnp.asarray(ids))
    assert float(got) == pytest.approx(want, rel=1e-5)
    assert float(ref.heads_loss(jnp.asarray(logits[0], jnp.float32), jnp.asarray(ids[0]),
                                heads)) == pytest.approx(want, rel=1e-5)
    # one head is the plain next-token loss
    one = base_layers._masked_softmax_loss(
        LossParam(label_shift=1), jnp.asarray(logits[..., :vocab], jnp.float32), jnp.asarray(ids))
    assert float(one) == pytest.approx(per_head[0], rel=1e-5)


def test_float32_logits_under_bfloat16():
    net = _net()
    params, ids = _params(1), _ids(1)
    with precision.policy("bfloat16"):
        blobs = net.apply(params, {"tokens": ids}, train=True)
    assert blobs["lm_head"].dtype == jnp.float32 and blobs["lm_head"].shape == (ROWS, POS, 96)
    assert blobs["final_norm"].dtype == jnp.bfloat16
    rounded = _net(fp32_logits=False)
    with precision.policy("bfloat16"):
        assert rounded.apply(params, {"tokens": ids}, train=True)["lm_head"].dtype == jnp.bfloat16
    # the unrounded accumulators carry more than bfloat16's eight bits
    lg = np.asarray(blobs["lm_head"])
    assert np.any(lg != np.asarray(lg.astype(jnp.bfloat16), np.float32))


def test_what_the_builder_refuses():
    for over in (dict(attention_class="softmax"), dict(num_key_value_heads=1),
                 dict(attention_bias=True), dict(tie_word_embeddings=True),
                 dict(rope_scaling={"type": "linear"}), dict(hidden_act="gelu")):
        with pytest.raises(ValueError, match="the file asks for something else"):
            zoo.evabyte(dict(TINY, **over), rows=1, positions=POS)


# -- one round through the trainer -------------------------------------------

def _spread_params(seed):
    return _params(seed, scale=4.0)


def test_one_round_through_the_trainer_matches_the_reference(tmp_path):
    case_ = tiny_round("evabyte", tmp_path, tau=2, rows=1, draw=_spread_params)
    state, got = program_round("evabyte", case_.make_trainer(), case_.params,
                               case_.ids)
    check_round(got, case_.want, rel=1e-3)
    _close(got["probe"][0], case_.want["probe"][0], tol=1e-4)
    assert ref.PROBE_LEAF == ("l0_attn", "phi")
    # the heads' one product is made once a step and kept whole
    from sparknet_tpu.obs import device as obs_device
    check_products_kept("evabyte", obs_device.program_report("train_round"),
                        tau=2, rows=1)
