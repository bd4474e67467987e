"""The expert layer (`seq_layers.moe`) and its routing plans, at GLM's tiny
file against the benchmark's plain reference
(`benchmark/configs/glm47-flash-ep8-tau4.reference.py`): the share arithmetic
(the parts all the shares give add up to the uncut layer), drops and
counters, the pair `rows_of_tokens` / `sum_by_token` against the dense
formula in both forms of the weighted sum, tight buffers, and the router's
per-slot scalars that travel by no index.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (CTX, D, MOE_P, POS, ROWS, _close, _params, _per_row,
                         _x, benchmark_expert_layers, case, compiled)
from sparknet_tpu.model import seq_layers as sl
from sparknet_tpu.model.spec import MoEParam

GLM = case("glm4_moe_lite")
ref, TINY = GLM.ref, GLM.tiny
MOE = GLM.table["l1_moe"][1]


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


def _slot_side(plan, tokens, k):
    """(`slot_row`, `slot_ok`) [tokens, k] as numpy: the plan's own where it
    holds a slot side (the k gathers), else read back from its rows -- a slot
    found room where a landed row names it, and that row is its row."""
    if "slot_row" in plan:
        return np.asarray(plan["slot_row"]), np.asarray(plan["slot_ok"])
    ok, row_slot = np.asarray(plan["row_ok"]), np.asarray(plan["row_slot"])
    slot_row, slot_ok = np.zeros(tokens * k, np.int32), np.zeros(tokens * k, bool)
    slot_row[row_slot[ok]], slot_ok[row_slot[ok]] = np.flatnonzero(ok), True
    return slot_row.reshape(tokens, k), slot_ok.reshape(tokens, k)


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
    # the plan holds the side its sums read: no slot side, and no second
    # sort, where they walk the buffer
    assert sorted(plan) == sorted(("tok", "row_slot", "row_ok") + (
        ("slot_row", "slot_ok") if form == "gathers" else ()))
    assert np.array_equal(_slot_side(plan, tokens, k)[1], keep)
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
        factor, positions, form, capsys):
    """The gradients of the whole layer -- the router's (through `dw`), the
    experts' (through `dy`) and the input's (through `dxf` and the router) --
    when the buffer is too small and slots are dropped: the reference's, with
    the dropped slots' weights zeroed in it. A bias sends most tokens to the
    two held experts, so that much more lands than finds room (2,048 or 4,096
    tokens: the buffer is whole tiles of the grouped product, 512 to 2,048
    rows), and the layer's rule takes the weighted sums over the buffer's
    rows at the shorter buffers and as k gathers at the longer. A block under
    the layer's policy keeps the side of the plan that form reads: the ids,
    their logits and the group sizes either way, with the three arrays by
    buffer row where the sums walk the buffer (the two by slot are read by
    nothing there, and their sort is made in neither pass) and all five
    where the gathers run."""
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
    block = jax.checkpoint(
        lambda p, x: sl.moe(tight, p, x, CTX)[0],
        policy=jax.checkpoint_policies.save_only_these_names(sl.MOE_ROUTE))
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda p, x: jnp.sum(block(p, x) ** 2), p, x)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out.splitlines()
            if f"named '{sl.MOE_ROUTE}'" in line]
    slots, by_row = f"[{ROWS * positions},2]", f"[{room}]"
    assert sorted(kept) == sorted(
        ["i32" + slots, "i32[2]", "i32" + by_row, "i32" + by_row,
         "bool" + by_row] + (
            [] if form == "buffer" else ["i32" + slots, "bool" + slots]))


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


def _router_case(p):
    """(a router's weights, 300 tokens of width 48, a cotangent for the
    weights, the bias as numpy) with exact ties among the scores -- two
    pairs of columns with one weight vector and one bias, within and across
    groups -- and a token whose score at column 3 overflows to exactly 1."""
    tokens, d, k, experts = 300, 48, p.num_experts_per_tok, p.n_routed_experts
    rng = np.random.default_rng(experts + k)
    router = rng.standard_normal((d, experts)).astype(np.float32)
    bias = (0.1 * rng.standard_normal(experts)).astype(np.float32)
    for a, b in ((1, 5), (experts - 2, 7)):
        router[:, a], bias[a] = router[:, b], bias[b]
    params = {"router": jnp.asarray(router), "router_bias": jnp.asarray(bias)}
    xf = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    xf = xf.at[0].set(40.0 * jnp.sign(params["router"][:, 3]))
    return (params, xf,
            jnp.asarray(rng.standard_normal((tokens, k)), jnp.float32), bias)


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
    params, xf, c, bias = _router_case(p)

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


def _route_as_it_was(p, params, xf):
    """`route` before its backward pass read the chosen logits alone: the
    sigmoid over all the columns, THEN the chosen columns of the scores by
    `take_along_axis` (whose backward reads every column's score)."""
    z = jnp.dot(xf.astype(jnp.float32), params["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    if p.score_func == "softmax_topk":
        _, idx = jax.lax.top_k(z, p.num_experts_per_tok)
        return idx.astype(jnp.int32), jax.nn.softmax(
            jnp.take_along_axis(z, idx, axis=-1), axis=-1)
    s = jax.nn.sigmoid(z)
    choice = s + jax.lax.stop_gradient(params["router_bias"])
    if p.n_group > 1:
        grouped = choice.reshape(choice.shape[0], p.n_group, -1)
        _, best = jax.lax.top_k(jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1),
                                p.topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(p.n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            choice.shape)
    _, idx = jax.lax.top_k(choice, p.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if p.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + p.norm_topk_eps)
    return idx.astype(jnp.int32), w * p.routed_scaling_factor


@pytest.mark.parametrize("config,score_func,n_group", [
    ("nemotron3-super-tp4-ep64-tau4", "sigmoid", 1),
    ("ling3-flash-ep64-tau4", "sigmoid", 8),
    ("smallthinker-21b-ep4-tau4", "softmax_topk", 1)])
def test_the_sigmoid_after_the_selection_is_the_one_before_it_to_the_bit(
        config, score_func, n_group):
    """`route()` at three configurations' own routers (300 tokens of width
    48, exact ties among the scores, one score that overflows to 1): the
    chosen experts, the weights and the gradients of a weighted sum of the
    weights with respect to the tokens and to the router's matrix equal, BIT
    FOR BIT, those of the form it replaces, written out above -- the sigmoid
    of the chosen logits is the chosen sigmoid (one elementwise function of
    the same float32), and `dz[t, idx[t, j]] = dw[t, j] w (1 - w)` is the
    same products on the same numbers whichever side of the selection makes
    them. What the backward pass reads differs: every column's score there,
    the ids and k logits a token here, both named `moe_route`."""
    p = benchmark_expert_layers(config)[0][0]
    assert (sl._score_func(p), p.n_group) == (score_func, n_group)
    params, xf, c, _ = _router_case(p)

    def both(route):
        def weighed(params, xf):
            idx, w = route(p, params, xf)
            return jnp.sum(w * c), (idx, w)
        return jax.value_and_grad(weighed, argnums=(0, 1), has_aux=True)

    got, want = both(sl.route)(params, xf), both(_route_as_it_was)(params, xf)
    (_, (idx, w)), (dparams, dxf) = got
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    z = jnp.dot(xf, params["router"], precision="highest")
    assert float(jax.nn.sigmoid(z)[0, 3]) == 1.0 or score_func == "softmax_topk"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(_bits(a), _bits(b))
    assert np.any(np.asarray(dparams["router"])) and np.any(np.asarray(dxf))
    assert not np.any(np.asarray(dparams["router_bias"]))
    jaxpr = str(jax.make_jaxpr(both(sl.route))(params, xf))
    assert jaxpr.count("name=" + sl.MOE_ROUTE) == 2, "the ids and their logits"


@functools.cache
def _recomputed_routing(kept: bool):
    """{layer type: the primitives, by routing scope, that the tiny GLM
    net's step makes again in that type's blocks} as its blocks are built
    (`kept`) or with `moe_route` struck from their policies."""
    from sparknet_tpu.model import net as net_mod
    from sparknet_tpu.obs import device as obs_device
    net = compiled("glm4_moe_lite")
    assert {l.type for l in net.spec.layers if l.block} >= {"MoE", "MTP"}
    loss = net.loss_fn("loss")

    def grad(p, ids):  # (a fresh function a trace: no policy in jax's key)
        with jax.named_scope(obs_device.STEP_SCOPE):
            return jax.value_and_grad(
                lambda p: loss(p, {"tokens": ids}, None)[0])(p)
    names = net_mod._kept_names
    with pytest.MonkeyPatch.context() as patch:
        if not kept:
            patch.setattr(net_mod, "_kept_names", lambda layers: tuple(
                n for n in names(layers) if n != sl.MOE_ROUTE))
        text = jax.jit(grad).lower(
            jax.eval_shape(net.init_params, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((ROWS, POS), jnp.int32)).compile().as_text()
    again = {}
    for op_name in set(re.findall(r'op_name="([^"]*)"', text)):
        at = obs_device.scope_of(op_name)
        under = [s for s in sl.ROUTING_SCOPES if s in at["scope"].split("/")]
        if at["recomputed"] and under:
            again.setdefault(at["layer_type"], {}).setdefault(
                under[0], set()).add(op_name.rsplit("/", 1)[-1])
    return again


@pytest.mark.parametrize("kind", ["MoE", "MTP"])
def test_a_block_that_keeps_the_routing_makes_none_of_it_again(kind):
    """The compiled gradient of GLM's tiny net (on the CPU; every
    instruction's `op_name`, inside a fusion or out): what an expert block
    -- a decoder's, and the MTP module's, whose policy names the attention
    core too -- makes again under `router` holds no product, no `top_k` and
    no sort nor the select over the experts' columns, and under `dispatch`
    no sort: the k-wide weights from the kept logits, and the row gather. With `moe_route` struck
    from the policies all of them are there a second time."""
    sorts = {"sort", "top_k"}
    bare, built = _recomputed_routing(False)[kind], _recomputed_routing(True)[kind]
    assert {"dot_general", "top_k", "eq"} <= bare["router"]
    assert "sort" in bare["dispatch"]
    assert not built["router"] & (sorts | {"dot_general", "eq"}), built
    assert not built["dispatch"] & sorts, built
    assert "gather" in built["dispatch"], "the rows are fetched again"
    assert built["router"] and built["router"] < bare["router"]


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
        slot_row, slot_ok = _slot_side(plan, tokens, k)
        by_slot = jnp.where(slot_ok, jnp.take(dw_row, slot_row), 0.0)
        assert dw.shape == (tokens, k) and dw.dtype == jnp.float32
        assert np.array_equal(_bits(dw), _bits(by_slot))
        assert np.count_nonzero(np.asarray(dw)) == kept
        assert not np.any(np.asarray(dy, np.float32)[~ok])

