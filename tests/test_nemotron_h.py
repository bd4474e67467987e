"""The state-space hybrid (`zoo.nemotron_h`): Mamba-2 mixers (`ops.ssd`),
grouped-query attention without a rotary turn, LatentMoE with relu^2 experts,
one mixer a layer, every operator holding a SHARE of its heads, columns or
experts -- against the benchmark's plain reference
(`benchmark/configs/nemotron3-super-tp4-ep64-tau4.reference.py`, which imports
nothing of the program and runs the scan a position at a time) at small
widths on the CPU: the scan alone against its recurrence, layer by layer, the
loss and every stored parameter's gradient, one tau-round through
`ParallelTrainer.train_round`, the controls and the broken rounds the checks
must refuse, the share arithmetic, and what the builder refuses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import MOE_P as GLM_MOE
from model_cases import _params as glm_params
from model_cases import (CTX, D, POS, ROWS, SOLVER, _ids, _per_row, _x, case,
                         check_layer, check_loss_and_every_gradient,
                         check_products_kept, check_round, compiled, load,
                         program_round, ssd_without_its_state,
                         tiny_round)
from sparknet_tpu import precision, zoo
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.spec import GQAttentionParam, Mamba2Param, MoEParam
from sparknet_tpu.ops import ssd as ssd_ops

NEMOTRON = case("nemotron_h")
ref, TINY, LAYERS, TABLE = (NEMOTRON.ref, NEMOTRON.tiny, NEMOTRON.layers,
                            NEMOTRON.table)
compare = load("benchmark/compare.py")
#: the weights' spread (`model_cases._CONFIGS`)
STD = NEMOTRON.init["std"]
#: the tiny model's limits, from CPU readings of this file's own runs (float32
#: program against the float32 reference: sound reads 1e-6 to 3e-4)
TINY_LIMITS = {"loss_gap": 1e-4, "update_gap": 2e-3, "momentum_gap": 2e-3,
               "probe_diff": 2e-3}


def _spec(config=TINY):
    return zoo.nemotron_h(config, rows=ROWS, positions=POS)


def _net():
    return compiled("nemotron_h")


MAMBA_P = _spec().layer_by_name("l0_mamba").mamba2
GQA_P = _spec().layer_by_name("l3_attn").gqa
MOE_P = _spec().layer_by_name("l1_moe").moe


#: the three mixers, each one jitted call (op-by-op dispatch compiles every
#: primitive on its own)
MAMBA = jax.jit(lambda p, x, held=None: sl.mamba2(held or MAMBA_P, p, x, CTX),
                static_argnames="held")
GQA = jax.jit(lambda p, x, held=None: sl.gqa(held or GQA_P, p, x, CTX),
              static_argnames="held")
MOE = jax.jit(lambda p, x, held=None: sl.moe(held or MOE_P, p, x, CTX),
              static_argnames="held")


def _params(seed, layer, bias_scale=1.0):
    p = NEMOTRON.params(seed)[layer]
    if "router_bias" in p:  # a bias large enough to change who is chosen
        p = dict(p, router_bias=p["router_bias"] * bias_scale)
    if "conv" in p:  # a skip and a norm that differ by head and channel
        p = dict(p, D=1.0 + 0.3 * _x(seed + 3, (4,)),
                 norm=1.0 + 0.1 * _x(seed + 5, (64,)))
    return p


# -- the scan against its recurrence -----------------------------------------

def _scan_operands(seed, n, rows=2, heads=4, hd=8, groups=2, state=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (rows, n, heads, hd)),
            jax.nn.softplus(jax.random.normal(ks[1], (rows, n, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (rows, n, groups, state)),
            jax.random.normal(ks[4], (rows, n, groups, state)))


@pytest.mark.parametrize("n,chunk", [(16, 16), (32, 16), (64, 16), (96, 8),
                                     (50, 16), (8, 16)])
def test_chunked_scan_equals_the_recurrence_and_so_does_its_gradient(n, chunk):
    """One chunk, two, four, twelve; a length that is no multiple of the
    chunk (PADDED at its end with positions whose time step is 0) and one
    shorter than a chunk."""
    args = _scan_operands(n, n)
    with jax.default_matmul_precision("highest"):
        got = ssd_ops.ssd(*args, chunk=chunk)
        want, _ = ssd_ops.ssd_recurrent(*args)
        loss = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                   argnums=(0, 1, 2, 3, 4))
        g_got = loss(lambda *a: ssd_ops.ssd(*a, chunk=chunk))(*args)
        g_want = loss(lambda *a: ssd_ops.ssd_recurrent(*a)[0])(*args)
    assert got.shape == want.shape == args[0].shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * float(jnp.max(jnp.abs(b)))


def test_the_scan_carries_its_state_from_chunk_to_chunk_and_is_causal():
    args = _scan_operands(7, 64)
    got = ssd_ops.ssd(*args, chunk=16)
    dropped = ssd_without_its_state(ssd_ops.ssd)(*args, chunk=16)
    # the first chunk has nothing to carry; every later one does
    assert np.allclose(dropped[:, :16], got[:, :16], atol=1e-6)
    assert float(jnp.max(jnp.abs(dropped[:, 16:] - got[:, 16:]))) > 0.1
    x = args[0].at[:, 40:].add(1.0)
    moved = ssd_ops.ssd(x, *args[1:], chunk=16)
    assert np.allclose(moved[:, :40], got[:, :40], atol=1e-6)
    assert not np.allclose(moved[:, 40], got[:, 40], atol=1e-3)


def test_a_strong_decay_leaves_nothing_outside_float32():
    """Time steps of 20 at A = -16: exp(-320 a position). Every exponent is a
    difference taken before the exp, so nothing overflows and the result is
    what the recurrence gives: each position all but alone."""
    x, dt, a, b, c = _scan_operands(9, 32)
    dt, a = jnp.full_like(dt, 20.0), jnp.full_like(a, -16.0)
    with jax.default_matmul_precision("highest"):
        got = ssd_ops.ssd(x, dt, a, b, c, chunk=16)
        want, _ = ssd_ops.ssd_recurrent(x, dt, a, b, c)
        grads = jax.grad(lambda x, dt: jnp.sum(ssd_ops.ssd(x, dt, a, b, c, chunk=16)),
                         argnums=(0, 1))(x, dt)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))


# -- layer by layer against the reference ------------------------------------

#: kind -> (seed -> the layer's weights, the program's layer, the reference's
#: on one row)
LAYER_TABLE = {
    "mamba2": (lambda seed: _params(seed, "l0_mamba"), MAMBA,
               lambda p, r: ref.mamba2(TABLE["l0_mamba"][1], p, r, "float32")),
    "gqa": (lambda seed: _params(seed, "l3_attn"), GQA,
            lambda p, r: ref.gqa(TABLE["l3_attn"][1], p, r, "float32")),
    "latent_moe": (lambda seed: _params(seed, "l1_moe", bias_scale=20.0),
                   lambda p, x: MOE(p, x)[0],
                   lambda p, r: ref.latent_moe(TABLE["l1_moe"][1], p, r,
                                               "float32")[0]),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["mamba2", "gqa", "latent_moe"])
def test_layer_matches_the_reference(kind, policy):
    check_layer(LAYER_TABLE, kind, policy)


def test_the_mixers_parts_are_what_the_formulas_say():
    """Mamba-2: causal; a head reads its own group (swapping the OTHER
    group's B and C changes nothing of it); the taps' bias and the skip D
    show. Attention: no rotary turn (a row shifted along the positions gives
    the shifted result where the history is the same: nothing depends on
    the position's index), and a share holds no norm. LatentMoE: two
    products a slot, no gate."""
    p, x = _params(3, "l0_mamba"), _x(23)
    got = MAMBA(p, x)
    moved = MAMBA(p, x.at[:, 20:].add(1.0))
    assert np.allclose(moved[:, :20], got[:, :20], atol=1e-6)
    assert not np.allclose(moved[:, 20], got[:, 20], atol=1e-3)
    for name in ("conv_bias", "D", "dt_bias", "A_log"):
        other = MAMBA(dict(p, **{name: p[name] + 0.5}), x)
        assert float(jnp.max(jnp.abs(other - got))) > 1e-4, name
    assert set(p) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D",
                      "norm", "out_proj"}
    # 4 held heads of 16, 2 held groups of state 16: 64 + (64 + 64) + 4
    assert p["in_proj"].shape == (64, 196) and p["conv"].shape == (128, 4)
    assert p["out_proj"].shape == (64, 64) and p["norm"].shape == (64,)
    q = _params(3, "l3_attn")
    assert set(q) == {"q", "k", "v", "o"} and q["q"].shape == (64, 32) \
        and q["k"].shape == (64, 16)
    one = _x(29, (1, POS, D))
    rolled = jnp.concatenate([one[:, :1], one[:, :-1]], axis=1)  # [x0, x0, x1, ..]
    a, b = GQA(q, one), GQA(q, rolled)
    assert np.allclose(a[:, 0], b[:, 0], atol=1e-6) and np.allclose(a[:, 0], b[:, 1], atol=1e-6)
    turned = GQAttentionParam(**{**GQA_P.__dict__, "rotary": True})
    assert not np.allclose(GQA(q, one, held=turned)[:, 1:], a[:, 1:], atol=1e-4)
    m = _params(3, "l1_moe")
    assert set(m) == {"router", "router_bias", "experts_up", "experts_down",
                      "latent_down", "latent_up", "shared_up", "shared_down"}
    assert m["experts_up"].shape == (2, 32, 48) and m["shared_up"].shape == (64, 24)
    assert sl.moe_capacity(MOE_P, ROWS * POS) == 512  # min(6, 2 held) a token, tiled


def test_other_models_layers_are_bit_equal_to_what_they_were():
    """LFM2's grouped-query attention (norms and rotary on, all heads held)
    and GLM's expert layer (SwiGLU in the stream's width), on their own tiny
    files, against the two functions written here as they stood before the
    shares, the switches and the latent."""
    spec = case("lfm2_moe").spec()
    layer = next(l for l in spec.layers if l.type == "GQAttention")
    p = layer.gqa
    assert (p.rotary, p.qk_norm, p.heads_held, p.kv_heads_held) == (True, True, None, None)
    params = sl.init_gqattention(jax.random.PRNGKey(3), layer, ((ROWS, POS, D),))
    assert list(params) == ["q", "k", "v", "q_norm", "k_norm", "o"]
    x = _x(51)

    def gqa_as_it_stood(p, params, x, ctx):
        h, kv, hd, d = p.num_heads, p.num_kv_heads, p.head_dim, x.shape[-1]
        heads_first = "rnc,chd->rhnd"
        q = sl._project(heads_first, x, params["q"].reshape(d, h, hd))
        k = sl._project(heads_first, x, params["k"].reshape(d, kv, hd))
        v = sl._project(heads_first, x, params["v"].reshape(d, kv, hd))
        q = sl.rotary(sl._rms(q, params["q_norm"] / np.sqrt(hd), p.eps), p.rope_theta, hd)
        k = sl.rotary(sl._rms(k, params["k_norm"], p.eps), p.rope_theta, hd)
        o = sl.attention_core(q, k, v, ctx)
        return sl._project("rhnd,hdm->rnm", o, params["o"].reshape(h, hd, d))

    def moe_as_it_stood(p, params, x, ctx):
        r, n, d = x.shape
        tokens = r * n
        xf = x.reshape(tokens, d)
        idx, w = sl.route(p, params, xf)
        plan, sizes, kept = sl._plan(idx, p.experts_held, sl.moe_capacity(p, tokens))
        xs = sl.rows_of_tokens(xf, plan)
        g = sl._grouped_dot(xs, params["experts_gate"], kept, ctx)
        u = sl._grouped_dot(xs, params["experts_up"], kept, ctx)
        h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
        y = sl._grouped_dot(h.astype(g.dtype), params["experts_down"], kept, ctx)
        out = sl.sum_by_token(y, w, plan) + sl._swiglu(
            xf, params["shared_gate"], params["shared_up"], params["shared_down"])
        return out.reshape(r, n, d)

    for policy in ("float32", "bfloat16"):
        with precision.policy(policy):
            assert np.array_equal(jax.jit(lambda a, b: sl.gqa(p, a, b, CTX))(params, x),
                                  jax.jit(lambda a, b: gqa_as_it_stood(p, a, b, CTX))(params, x))
            mp = glm_params(2, bias_scale=20.0)
            assert np.array_equal(
                jax.jit(lambda a, b: sl.moe(GLM_MOE, a, b, CTX)[0])(mp, x),
                jax.jit(lambda a, b: moe_as_it_stood(GLM_MOE, a, b, CTX))(mp, x))
    # and the initial values are the ones they drew
    layer = sl.LayerSpec(name="m", type="MoE", moe=GLM_MOE)
    drawn = sl.init_moe(jax.random.PRNGKey(4), layer, ((ROWS, POS, D),))
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    assert list(drawn) == ["router", "router_bias", "experts_gate", "experts_up",
                           "experts_down", "shared_gate", "shared_up", "shared_down"]
    assert np.array_equal(drawn["experts_gate"], sl._normal(ks[2], (2, D, 48), 0.02))
    assert np.array_equal(drawn["shared_down"], sl._normal(ks[7], (48, D), 0.02))


# -- the shares add up --------------------------------------------------------

def _mamba_share(p, first, heads, hd, groups, state, per, all_heads, all_groups):
    """The held slice of an uncut Mamba-2 layer's parameters: heads `first` ..
    `first + heads - 1` and their groups."""
    g0 = first // per
    inner, gs = all_heads * hd, all_groups * state
    h_cols = np.arange(first * hd, (first + heads) * hd)
    g_cols = np.arange(g0 * state, (g0 + groups) * state)
    conv = np.concatenate([h_cols, inner + g_cols, inner + gs + g_cols])
    cols = np.concatenate([h_cols, inner + conv, 2 * inner + 2 * gs
                           + np.arange(first, first + heads)])
    heads_ = np.arange(first, first + heads)
    return {"in_proj": p["in_proj"][:, cols], "conv": p["conv"][conv],
            "conv_bias": p["conv_bias"][conv], "dt_bias": p["dt_bias"][heads_],
            "A_log": p["A_log"][heads_], "D": p["D"][heads_],
            "norm": p["norm"][h_cols], "out_proj": p["out_proj"][h_cols]}


@pytest.mark.parametrize("seed", [1, 2])
def test_the_shares_add_up_to_the_uncut_layers(seed):
    """The two head shares of a Mamba-2 mixer and of the attention, the four
    column shares of the shared expert and the eight expert shares pushed
    through fc2 -- the router and fc1 counted once -- sum to the uncut
    reference's whole layers."""
    share = dict(TINY["share"], experts_held=[0, 16], mamba_heads_held=[0, 8],
                 mamba_groups_held=[0, 4], attention_heads_held=[0, 4],
                 kv_heads_held=[0, 2], shared_columns=[0, 96])
    uncut = ref.layer_table(dict(TINY, share=share))
    args = {n: a for n, _, a in uncut}
    whole = ref.init_params(seed, uncut, std=STD)
    x = _x(seed + 40)

    # Mamba-2: heads 0-3 with groups 0-1, heads 4-7 with groups 2-3
    p = whole["l0_mamba"]
    per_row = lambda fn, a: jax.jit(lambda p, x: _per_row(
        lambda r: fn(a, p, r, "float32"), x))
    want = per_row(ref.mamba2, args["l0_mamba"])(p, x)
    total = 0.0
    for first in (0, 4):
        held = Mamba2Param(**{**MAMBA_P.__dict__, "heads_held": (first, 4),
                              "groups_held": (first // 2, 2)})
        total = total + MAMBA(_mamba_share(p, first, 4, 16, 2, 16, 2, 8, 4), x,
                              held=held)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))

    # attention: query heads 0-1 with key/value head 0, 2-3 with 1
    p = whole["l3_attn"]
    want = per_row(ref.gqa, args["l3_attn"])(p, x)
    total = 0.0
    for first in (0, 1):
        q_cols, kv_cols = np.arange(first * 32, first * 32 + 32), np.arange(first * 16, first * 16 + 16)
        held = GQAttentionParam(**{**GQA_P.__dict__, "heads_held": (2 * first, 2),
                                   "kv_heads_held": (first, 1)})
        total = total + GQA({"q": p["q"][:, q_cols], "k": p["k"][:, kv_cols],
                             "v": p["v"][:, kv_cols], "o": p["o"][q_cols]}, x,
                            held=held)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))

    # LatentMoE: eight shares of two experts through fc2, four of 24 columns
    p = whole["l1_moe"]
    p = dict(p, router_bias=p["router_bias"] * 20.0)
    want = per_row(lambda *a: ref.latent_moe(*a)[0], args["l1_moe"])(p, x)
    total, landed = 0.0, 0.0
    for i in range(8):
        cols = np.arange(24 * (i % 4), 24 * (i % 4) + 24)
        mine = dict(p, experts_up=p["experts_up"][2 * i:2 * i + 2],
                    experts_down=p["experts_down"][2 * i:2 * i + 2],
                    shared_up=p["shared_up"][:, cols],
                    shared_down=p["shared_down"][cols] * (1.0 if i < 4 else 0.0))
        part, counters, _ = MOE(mine, x, held=MoEParam(**{
            **MOE_P.__dict__, "experts_held": (2 * i, 2),
            "shared_columns": (24 * (i % 4), 24)}))
        total = total + part
        landed += float(counters[0])
        assert float(counters[1]) == 0
    assert landed == ROWS * POS * 6, "every routed slot lands on exactly one share"
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))


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
        "nemotron_h", policy, NEMOTRON.params(seed), _ids(seed + 70),
        loss_tol=2e-5 if f32 else 1e-2, grad_tol=5e-5 if f32 else loose)
    seen = {name for lp in want_grads.values() for name in lp}
    assert {"in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D", "norm",
            "out_proj", "q", "o", "latent_down", "latent_up", "experts_up",
            "shared_down", "w", "scale"} <= seen


@pytest.fixture(scope="module", name="tiny_round")
def _tiny_round(tmp_path_factory):
    """(a trainer's maker, weights, ids [tau, rows, positions], the
    reference's round)."""
    case_ = tiny_round("nemotron_h", tmp_path_factory.mktemp("nemotron"), tau=2,
                       mtp_weight=0.1)
    return case_.make_trainer, case_.params, case_.ids, case_.want


def _program_round(make_trainer, params, ids):
    trainer = make_trainer()
    return trainer, program_round("nemotron_h", trainer, params, ids)[1]


def _failed(got, want):
    return [c["name"] for c in compare.first_round_checks(got, want, TINY_LIMITS)
            if not c["ok"]]


def test_one_tau_round_through_the_trainer_matches_tau_reference_steps(tiny_round):
    from sparknet_tpu.obs import device as obs_device
    make_trainer, params, ids, want = tiny_round
    trainer, got = _program_round(make_trainer, params, ids)
    check_round(got, want, rel=3e-4)
    assert _failed(got, want) == []
    assert set(want["chosen"]) == {"l1_moe", "l4_moe", "mtp1_moe"}
    assert want["chosen"]["l1_moe"].shape == (ROWS, POS, 6)
    assert set(trainer.counter_values()) == {
        "l1_moe_counters", "l4_moe_counters", "mtp1_moe_counters"}
    # the round's account of itself: two Mamba-2 layers, each scan a loop
    # over its two chunks forward, made again and backward, in two step
    # bodies; the attention blocks keep their cores' outputs (float32 here)
    report = obs_device.program_report("train_round")
    ssm = report["ssm"]
    assert set(ssm) == {"layers", "loops", "trips", "kernel_calls",
                        "carried_bytes", "instructions", "bytes"}
    assert ssm["layers"] == 2 and ssm["kernel_calls"] == 0
    assert ssm["instructions"] > 0 and ssm["bytes"] > 0
    assert obs_device.program_part("ssm")["train_round"] == ssm
    assert report["delta_rule"] == {} and report["eva"] == {}
    assert report["recompute"][sl.ATTN_CORE]["kept_bytes"] == 2 * ROWS * POS * 2 * 16 * 4
    # ... and both heads' logits and the projection into the MTP module
    check_products_kept("nemotron_h", report, tau=2)
    scopes = {op["scope"] for op in report["ops"].values()}
    for part in ("in_proj", "conv", "ssd", "gate_norm", "out_proj"):
        assert any("Mamba2/l0_mamba" in s and part in s.split("/") for s in scopes), part
    for part in ("router", "latent_down", "dispatch", "experts", "combine",
                 "latent_up", "shared"):
        assert any("MoE/l1_moe" in s and part in s.split("/") for s in scopes), part
        assert any("MoE/mtp1_moe" in s and part in s.split("/") for s in scopes), part
    assert any("GQAttention/l3_attn)/core" in s for s in scopes)
    assert any("GQAttention/mtp0_attn)/core" in s for s in scopes)
    # the rows the routing moves are the latent's
    assert trainer.net.routing_scopes() == (sl.ROUTING_SCOPES, 32)
    moves = report["routing_moves"]
    assert moves["rows_gathered"] > 0 and moves["rows_scattered"] == 0
    # ... and the single scalars it fetches or places are a buffer's rows a
    # move (a row's weight fetched, its `dw` placed), never tokens x k
    slots, room = ROWS * POS * MOE_P.num_experts_per_tok, sl.moe_capacity(
        MOE_P, ROWS * POS)
    assert moves["slot_scalar_moves"] > 0 and slots != room
    assert moves["slot_scalars_moved"] == moves["slot_scalar_moves"] * room
    # ... by gathers here, where 64 tokens x 6 slots are fewer than one tile
    # of the buffer; at 512 tokens with room for the even share the layer's
    # own rule sums by token in one scatter-add over the buffer's rows
    tight = MoEParam(**{**MOE_P.__dict__, "capacity_factor": 1.0})
    rows = sl.moe_capacity(tight, 512)
    assert sl.sum_walks_buffer(rows, 512, tight.num_experts_per_tok)

    def lone(p, x):
        with jax.named_scope("MoE/lone"):
            return jnp.sum(sl.moe(tight, p, x, CTX)[0] ** 2)

    text = jax.jit(jax.grad(lone, argnums=(0, 1))).lower(
        params["l1_moe"], _x(79, (ROWS, 256, D))).compile().as_text()
    walked = obs_device.routing_moves(obs_device.parse_hlo_ops(text),
                                      sl.ROUTING_SCOPES, 32)
    assert walked["row_scatters"] == 2 and walked["rows_scattered"] == 2 * rows
    assert walked["rows_gathered"] == 2 * rows  # no [tokens]-row gather left
    # one scalar a row by index: its weight (the dispatch's backward weighs
    # every landed row 1 and fetches nothing, since PR 52; 2 before it)
    assert (walked["slot_scalar_moves"], walked["slot_scalars_moved"]) == (
        1, rows)


@pytest.mark.parametrize("control", ["fp8", "state_dropped"])
def test_the_references_controls_fail_the_tiny_limits(tiny_round, control):
    """The reference put in the program's place: computed in the precision
    below the configuration's, and with the scan's state set to zero at
    every chunk boundary."""
    _, params, ids, want = tiny_round
    kw = dict(precision="fp8") if control == "fp8" else dict(carry_state=False)
    got = ref.round_reference(params, lambda t, w: ids[t], tau=2, solver=SOLVER,
                              layers=LAYERS, mtp_weight=0.1, **kw)
    failed = _failed(got, want)
    assert "probe_diff" in failed, failed
    assert all(c["ok"] and c["value"] == 0 for c in
               compare.first_round_checks(want, want, TINY_LIMITS))


def _swiglu_experts(u):
    u32 = u.astype(jnp.float32)
    return (jax.nn.silu(u32) * u32).astype(u.dtype)


def _bypass_latent(real):
    """`moe` with the two latent projections replaced by a plain selection
    of the stream's first channels: the experts read the stream itself."""
    def moe(p, params, x, ctx):
        pick = jnp.eye(params["latent_down"].shape[0], params["latent_down"].shape[1])
        return real(p, {**params, "latent_down": pick, "latent_up": pick.T}, x, ctx)
    return moe


@pytest.mark.parametrize("broken", ["state_unchanged", "scan_without_its_state",
                                    "experts_as_swiglu", "latent_bypassed"])
def test_a_broken_round_fails_the_tiny_limits(tiny_round, monkeypatch, broken):
    make_trainer, params, ids, want = tiny_round
    if broken == "state_unchanged":
        from sparknet_tpu.parallel.trainer import ParallelTrainer
        real = ParallelTrainer.train_round

        def lazy_round(self, state, batches, rng, **kw):
            _, loss = real(self, jax.tree.map(lambda x: x.copy(), state),
                           batches, rng, **kw)
            return state, loss

        monkeypatch.setattr(ParallelTrainer, "train_round", lazy_round)
    elif broken == "scan_without_its_state":
        monkeypatch.setattr(ssd_ops, "ssd", ssd_without_its_state(ssd_ops.ssd))
    elif broken == "experts_as_swiglu":
        monkeypatch.setattr(sl, "_relu2", _swiglu_experts)
    else:
        monkeypatch.setattr(sl, "moe", _bypass_latent(sl.moe))
    # (a spec compiles once a process; the round is traced anew a trainer)
    _, got = _program_round(make_trainer, params, ids)
    failed = _failed(got, want)
    assert failed, broken
    if broken == "state_unchanged":
        assert "update_gap" in failed and "loss_gap" not in failed
    else:
        assert "probe_diff" in failed or "momentum_gap" in failed, failed


def test_the_scan_compiles_to_one_loop_over_chunks_a_pass():
    """A lone layer at 256 positions (sixteen chunks of 16) in a
    recomputation block, forward + backward: under `ssd` the scan over
    chunks once forward, once made again and once backward, with the trip
    counts read from the text; what a trip carries holds the float32
    state."""
    from sparknet_tpu.obs import device as obs_device
    p, x = _params(1, "l0_mamba"), _x(31, (ROWS, 256, D))

    def loss(p, x):
        with jax.named_scope("tau_step"), jax.named_scope("Mamba2/l0_mamba"):
            return jnp.sum(jnp.sin(jax.checkpoint(
                lambda p, x: sl.mamba2(MAMBA_P, p, x, CTX))(p, x)))

    ops = obs_device.parse_hlo_ops(jax.jit(jax.grad(loss)).lower(p, x).compile().as_text())
    got = obs_device.ssm(ops, sl.SSD_SCOPES)
    assert got["layers"] == 1 and got["loops"] >= 3 and got["trips"] >= 3 * 16, got
    assert got["carried_bytes"] >= ROWS * 4 * 16 * 16 * 4
    assert got["instructions"] > 0 and got["bytes"] > 0 and got["kernel_calls"] == 0
    assert obs_device.ssm(ops, {}) == {}
    assert obs_device.ssm(ops, {"GQAttention": "ssd"})["loops"] == 0
    # the delta rule's account reads as it did: the same query, its own keys
    assert set(obs_device.delta_rule(ops, {"Mamba2": "ssd"})) == {
        "loops", "trips", "kernel_calls", "shape_kernel_calls", "carried_bytes",
        "instructions", "bytes", "kept_bytes"}


# -- the builder -------------------------------------------------------------

def test_zoo_follows_the_pattern_and_builds_the_mtp_module_of_layer_types():
    spec = _spec()
    mixers = [(l.name, l.type) for l in spec.layers
              if l.type in ("Mamba2", "GQAttention", "MoE")]
    assert mixers == [("l0_mamba", "Mamba2"), ("l1_moe", "MoE"), ("l2_mamba", "Mamba2"),
                      ("l3_attn", "GQAttention"), ("l4_moe", "MoE"),
                      ("mtp0_attn", "GQAttention"), ("mtp1_moe", "MoE")]
    assert not any(l.type in ("MTP", "GatedMLP", "MLAttention") for l in spec.layers)
    # one norm, one mixer, one sum a layer
    assert [l.type for l in spec.layers if l.block == "l0"] == ["RMSNorm", "Mamba2", "Eltwise"]
    assert {l.block for l in spec.layers} == {
        None, "l0", "l1", "l2", "l3", "l4", "head", "mtp", "mtp0", "mtp1", "mtp_head"}
    assert [(l.name, l.type) for l in spec.layers if l.block == "mtp"] == [
        ("mtp_embed", "Embed"), ("mtp_hnorm", "RMSNorm"), ("mtp_enorm", "RMSNorm"),
        ("mtp_cat", "Concat"), ("mtp_eh_proj", "InnerProduct")]
    assert spec.layer_by_name("mtp_embed").param_from == "embed"
    assert spec.layer_by_name("mtp_embed").embed.shift == 1
    assert spec.layer_by_name("mtp_head").param_from == "lm_head"
    assert spec.layer_by_name("mtp_hnorm").bottoms == ("x5",)  # before the final norm
    assert spec.layer_by_name("loss_mtp").loss.loss_weight == 0.1
    moe = spec.layer_by_name("l1_moe").moe
    assert (moe.n_routed_experts, moe.experts_held, moe.num_experts_per_tok,
            moe.latent_size, moe.expert_form, moe.shared_intermediate_size,
            moe.shared_columns, moe.routed_scaling_factor) == (
        16, (4, 2), 6, 32, "relu2", 96, (24, 24), 5)
    assert spec.layer_by_name("mtp1_moe").moe == moe
    m = spec.layer_by_name("l0_mamba").mamba2
    assert (m.num_heads, m.heads_held, m.n_groups, m.groups_held, m.held()) == (
        8, (4, 4), 4, (2, 2), (4, 2))
    a = spec.layer_by_name("l3_attn").gqa
    assert (a.rotary, a.qk_norm, a.heads_held, a.kv_heads_held, a.held()) == (
        False, False, (2, 2), (1, 1), (2, 1))
    head = spec.layer_by_name("lm_head")
    assert head.param_from is None and not head.inner_product.transposed  # untied
    net = _net()
    assert net.kept_makers() == {sl.ATTN_CORE: "splash_mha_fwd", sl.IP_OUT: sl.IP_OUT,
                                 sl.MOE_ROUTE: "router"}
    assert "Mamba2" not in sl.KEPT_NAMES
    assert net.attention_scopes() == ({"Mamba2": "", "GQAttention": ""}, POS)
    assert net.ssd_scopes() == {"Mamba2": "ssd"} == sl.SSD_SCOPES
    assert net.delta_scopes() == ({}, ()) and net.eva_scopes() == ({}, None)
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes(LAYERS)
    assert zoo.SEQUENCE_MODELS["nemotron_h"] is zoo.nemotron_h
    # the other builders' nets have no scan to report
    assert compiled("lfm2_moe").ssd_scopes() == {}


def test_the_programs_own_initial_values_keep_the_state_alive():
    """`init_mamba2`: time steps log-uniform in [0.001, 0.1], A in [-16, -1],
    D = 1: a head's decay a position lies in (exp(-1.6), 1), not at 1/e^2."""
    p = _net().init_params(jax.random.PRNGKey(0))["l0_mamba"]
    step = jax.nn.softplus(p["dt_bias"])
    assert 0.001 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.1 + 1e-6
    a = -jnp.exp(p["A_log"])
    assert -16 <= float(jnp.min(a)) and float(jnp.max(a)) <= -1
    assert np.array_equal(p["D"], np.ones(4)) and np.array_equal(p["norm"], np.ones(64))
    r = ref.init_params(1, LAYERS)["l0_mamba"]
    assert float(jnp.max(jnp.abs(r["conv"]))) <= 0.5 < 2 * float(jnp.max(jnp.abs(p["conv"])))
    step = jax.nn.softplus(r["dt_bias"])
    assert 0.001 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.1 + 1e-6
    assert float(jnp.min(r["A_log"])) >= 0 and float(jnp.max(r["A_log"])) <= np.log(16)


@pytest.mark.parametrize("change,match", [
    ({"share": {**TINY["share"], "experts_held": [4, 4]}}, "disagree"),
    ({"share": {**TINY["share"], "vocab_rows": [0, 128]}}, "disagree"),
    ({"share": {**TINY["share"], "shared_columns": [80, 24]}}, "disagree"),
    ({"share": {**TINY["share"], "mamba_heads_held": [3, 4]}}, "not the heads of"),
    ({"share": {**TINY["share"], "kv_heads_held": [0, 1]}}, "not the query heads of"),
    ({"hybrid_override_pattern": "MEM-E"}, "no other letter is built"),
    ({"hybrid_override_pattern": "MEM*"}, "does not name the mixer"),
    ({"mtp_hybrid_override_pattern": "*-"}, "no other letter is built"),
    ({"mlp_hidden_act": "silu"}, "asks for something else"),
    ({"use_conv_bias": False}, "asks for something else"),
    ({"residual_in_fp32": True}, "asks for something else"),
    ({"tie_word_embeddings": True}, "asks for something else"),
    ({"n_group": 4}, "asks for something else"),
    ({"expand": 4}, "asks for something else"),
])
def test_zoo_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        _spec(dict(TINY, **change))


def test_an_unknown_expert_form_is_refused():
    layer = sl.LayerSpec(name="m", type="MoE", moe=MoEParam(
        **{**MOE_P.__dict__, "expert_form": "geglu"}))
    with pytest.raises(ValueError, match="expert_form 'geglu' is not built"):
        sl.init_moe(jax.random.PRNGKey(0), layer, ((ROWS, POS, D),))
