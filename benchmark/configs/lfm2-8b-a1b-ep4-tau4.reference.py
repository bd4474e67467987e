"""Plain reference for the `lfm2-8b-a1b-ep4-tau4` configuration.

LFM2-8B-A1B (`lfm2_moe`: huggingface.co/LiquidAI/LFM2-8B-A1B config.json) as
ONE CHIP'S SHARE of a four-chip expert-parallel deployment, written out in
straightforward `jax.numpy`: float32, matmul precision `highest`, no kernels,
no cache. It imports nothing of the program and takes nothing the program
made: the benchmark makes the weights (`init_params`, from the
configuration's `weights_seed`) and the token ids, and hands both sides the
same.

The model, per row of token ids t_0 .. t_{P-1} (x is [P, d], d = 2048; RMSNorm
eps `norm_eps`; no biases; pre-norm residual blocks):

  x = E[t]                                   E the held vocabulary rows
  per layer:  h = x + Op(RMSNorm(x));  x' = h + FF(RMSNorm(h))
    Op = ShortConv where layer_types[i] == "conv":
          [B | C | z] = u W_in (d -> 3d); s = B * z;
          c_t = sum_{j=0..2} w[:, j] * s_{t-2+j}   (depthwise, causal, 3 taps a
          channel, zeros before position 0, no bias); out = (C * c) W_out.
          No nonlinearity.
    Op = GQAttention where "full_attention":
          q = u W_q -> 32 heads x 64; k, v = u W_k, u W_v -> 8 heads x 64;
          q, k each through an RMS norm over the 64 dims of a head (one scale
          vector of 64 each, shared by the heads); rotary over all 64 dims,
          theta 1e6, contiguous halves (x[i], x[i+32]); scores q.k / 8, causal
          softmax; query heads 4g .. 4g+3 read key/value head g;
          out = concat(heads) W_o.
    FF    leading dense layers: (silu(x W_g) * x W_u) W_d, width 7,168
          expert layers: s = sigmoid(x W_r) over ALL 32 published experts
          (float32); chosen = the top 4 of s + b (b a buffer, neither trained
          nor decayed); w = s[chosen] / (sum s[chosen] + 1e-6), x
          routed_scaling_factor; y = sum over the chosen experts THIS CHIP
          HOLDS of w_e SwiGLU_e(x), width 1,792. No shared expert. What the
          absent experts would add is left out, as in the program.
  logits = RMSNorm(x_last) E^T               the SAME E: a tied head
  L = mean_i CE(logits_i, t_{i+1})

Left out here and in the program alike (`changed_from_source` in the
configuration file): the balance update of b and any auxiliary balance loss,
dropout, document masks (one document a row). The head size, the per-head
norms, the order [B | C | z], the rotary pairing, the tied head and the
initialisation are `assumed` there.

To fit a chip at the published widths the gradient is taken one row at a
time and summed, every block is recomputed in the backward pass
(`jax.checkpoint`) and the attention scores are made one block of queries at
a time; none of that changes a number beyond float32's summation order.

`precision` other than "float32" is the CONTROL (see `LIMITS`): the same
mathematics with both operands of every matmul (the router's excepted: it is
float32 on both sides by the model's own rule) and the cotangent of its
output rounded per tensor to fp8 e4m3, the step below the configuration's
bfloat16. The gates and the taps are no matmul and stay float32.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "lfm2-8b-a1b-ep4-tau4.json")) as _f:
    CONFIG = json.load(_f)

#: queries a block in the attention core (scores [heads, block, P] float32)
ATTN_BLOCK = 512
#: runs the queries go in, each against the keys up to its end (4: 62.5 % of
#: the score square is computed; more runs compile longer)
ATTN_GROUPS = 4

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`, the full rate) against
#: `round_reference`. Each limit stands with the v5e readings it was set from
#: (PR 31's chip runs at the cell's own size: eight seeds through
#: `benchmark/token_control.py` and seven benchmark runs; PERF.md section 2
#: repeats them): "sound" is the program over those fifteen (the weights are
#: the configuration's, so the readings hardly move), "control" the fp8
#: control over two seeds.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum: the number
    # that tells the precisions apart, and the one limit the lower-precision
    # control has to fail. Sound 0.0804-0.0825 (twice the other sequence
    # model's: nine blocks lie above the probe, not five), control
    # 0.2552-0.2557: 1.8 times the one, 0.59 of the other.
    "probe_diff": 0.15,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round, and the same over the parameters'
    # change across the round. Precision hardly moves either at this depth:
    # sound 0.00122-0.00505 (momentum) and 0.00196-0.00429 (change), the
    # worst leaf each time a norm's scale or a router, whose gradients are
    # small and whose reading swings fourfold from seed to seed; control
    # 0.00628-0.00896 and 0.00663-0.00717, 1.24 times the largest sound
    # reading. A limit between the two would refuse a sound run on a fresh
    # seed, so both stand between the sound readings and what a broken round
    # reads, with the more room above the readings: a round that returns its
    # state unchanged reads 1.0, a step's rows left out a quarter of the
    # round's gradient, a convolution that drops a tap 0.07-0.10 at a test's
    # size. Four times
    # the largest sound reading.
    "momentum_gap": 0.02,
    "update_gap": 0.02,
    # |program round loss - reference round loss|. Precision hardly moves it:
    # sound 2.2e-5-3.06e-4, control 1.9e-4 on one seed and 1.97e-3 on the
    # other, so no limit lies between the two. The other sequence cell's
    # 3.5e-4 would leave the largest sound reading 1.14 times of room, and
    # the comparison has no way to leave a number out: 1e-3, three times the
    # largest sound reading. It guards the loss's own arithmetic (the mean
    # over the positions that have a target, the norm before the head, the
    # head's matrix), not the precision. PERF.md section 7 asks for the
    # repair.
    "loss_gap": 1.0e-3,
    # the worst expert layer's share of routed slots whose expert differs
    # between the program's forward pass (bf16 stream) and this file's
    # (float32), the router float32 on both sides: sound 0.0210-0.0228 (the
    # last expert layer, always; it grows with depth from the first's
    # 0.0077-0.0083). No control reads it; three times the sound reading,
    # held against a router that reads a coarser stream, or another bias,
    # than the model's.
    "routing_diff_share": 0.065,
}
#: the step below the configuration's bfloat16
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the leading dense
#: layer's down projection. Its gradient carries the whole backward pass
#: through the eight blocks above it (six convolutions, two attentions, eight
#: expert layers) and the tied head; the tied matrix's own is dominated by
#: the head's forward pass.
PROBE_LEAF = ("l0_mlp", "down")


# -- the layer table ---------------------------------------------------------

def layer_table(config: dict) -> tuple:
    """(name, kind, args) of every layer of the table, in execution order;
    the names are the program's (`zoo.lfm2_moe`). Kinds `mlp`, `moe` and
    `head` carry the keys `benchmark/lm_flops.py` reads; `shortconv` and
    `gqa` those `benchmark/hybrid_lm_flops.py` reads. The head holds no
    parameter of its own (`tied`: the layer whose matrix it reads)."""
    c, share = config, config["share"]
    d, eps = c["hidden_size"], c["norm_eps"]
    heads = c["num_attention_heads"]
    attn = dict(d=d, heads=heads, kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim") or d // heads,
                theta=float(c["rope_theta"]), eps=eps)
    conv = dict(d=d, taps=c["conv_L_cache"])
    moe = dict(d=d, width=c["moe_intermediate_size"],
               routed=share["num_experts"],
               first=share["experts_held"][0], held=share["experts_held"][1],
               k=c["num_experts_per_tok"], shared=0,
               scale=c["routed_scaling_factor"], norm=c["norm_topk_prob"],
               norm_eps=1e-6)
    norm = dict(d=d, eps=eps)
    vocab = share["vocab_rows"][1]
    kinds = c["layer_types"]
    assert len(kinds) == c["num_hidden_layers"], "one operator a layer"
    table = [("embed", "embed", dict(vocab=vocab, d=d))]
    for i, kind in enumerate(kinds):
        table += [(f"l{i}_op_norm", "rmsnorm", norm),
                  (f"l{i}_conv", "shortconv", conv) if kind == "conv"
                  else (f"l{i}_attn", "gqa", attn),
                  (f"l{i}_mlp_norm", "rmsnorm", norm)]
        table.append((f"l{i}_mlp", "mlp", dict(d=d, width=c["intermediate_size"]))
                     if i < c["num_dense_layers"]
                     else (f"l{i}_moe", "moe", moe))
    table += [("final_norm", "rmsnorm", norm),
              ("lm_head", "head", dict(d=d, vocab=vocab, tied="embed"))]
    return tuple(table)


LAYERS = layer_table(CONFIG)


def param_shapes(layers=LAYERS) -> dict:
    """{layer: {parameter: shape}}: what this chip holds."""
    shapes = {}
    for name, kind, a in layers:
        d = a["d"]
        if kind == "embed":
            shapes[name] = {"w": (a["vocab"], d)}
        elif kind == "rmsnorm":
            shapes[name] = {"scale": (d,)}
        elif kind == "shortconv":
            shapes[name] = {"in_proj": (d, 3 * d), "conv": (d, a["taps"]),
                            "out_proj": (d, d)}
        elif kind == "gqa":
            q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
            shapes[name] = {"q": (d, q), "k": (d, kv), "v": (d, kv),
                            "q_norm": (a["head_dim"],),
                            "k_norm": (a["head_dim"],), "o": (q, d)}
        elif kind == "mlp":
            shapes[name] = {"gate": (d, a["width"]), "up": (d, a["width"]),
                            "down": (a["width"], d)}
        elif kind == "moe":
            w = a["width"]
            shapes[name] = {"router": (d, a["routed"]),
                            "router_bias": (a["routed"],),
                            "experts_gate": (a["held"], d, w),
                            "experts_up": (a["held"], d, w),
                            "experts_down": (a["held"], w, d)}
        # kind "head": the embedding's matrix, counted there
    return shapes


def n_params(layers=LAYERS) -> int:
    return sum(int(np.prod(s)) for lp in param_shapes(layers).values()
               for s in lp.values())


def init_params(weights_seed: int, layers=LAYERS, std: float = 0.02) -> dict:
    """The benchmark's weights, one jitted call on the device: normal(0,
    `std`) for every matrix, for the convolutions' taps and for the router's
    selection bias, ones for every norm's scale. From the configuration's
    `weights_seed`, NOT from the run's seed: which experts a random router
    favours is a property of the draw (configuration file, `assumed`)."""
    shapes = param_shapes(layers)

    @jax.jit
    def make(key):
        out, i = {}, 0
        for name, lp in shapes.items():
            out[name] = {}
            for pn, sh in lp.items():
                i += 1
                out[name][pn] = (
                    jnp.ones(sh, jnp.float32)
                    if pn.endswith("norm") or pn == "scale" else
                    std * jax.random.normal(jax.random.fold_in(key, i), sh,
                                            jnp.float32))
        return out

    return make(jax.random.PRNGKey(weights_seed % (2 ** 31)))


# -- the lower-precision control ---------------------------------------------

def _quantize(x, precision: str):
    if precision != "fp8":
        raise ValueError(f"unknown control precision {precision!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_to(x, precision: str):
    """An operand of a forward matmul, rounded; its gradient passes through."""
    return x if precision == "float32" else _quantize(x, precision)


_round_to.defvjp(lambda x, precision: (_round_to(x, precision), None),
                 lambda precision, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_grad(y, precision: str):
    """A matmul's output: untouched forward, its cotangent (an operand of
    both backward matmuls) rounded on the way back."""
    return y


_round_grad.defvjp(
    lambda y, precision: (y, None),
    lambda precision, _, g: (g if precision == "float32"
                             else _quantize(g, precision),))


def _mm(spec: str, a, b, precision: str):
    """einsum(spec, a, b), the one matmul of this file."""
    return _round_grad(jnp.einsum(spec, _round_to(a, precision),
                                  _round_to(b, precision)), precision)


# -- forward -----------------------------------------------------------------

def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x [P, heads, d], position = index along axis 0; pairs (x[i],
    x[i + d/2]), frequency theta^(-2i/d)."""
    d, n = x.shape[-1], x.shape[0]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(n)[:, None] * inv[None, :], jnp.float32)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def causal_attention(q, k, v, precision, block=ATTN_BLOCK, groups=ATTN_GROUPS):
    """q, k, v [P, heads, d] -> [P, heads, d]: the exact causal softmax of
    q.k / sqrt(d), `block` queries at a time, the scores made again in the
    backward pass. The queries go in `groups` runs, each against the keys up
    to its own end, so most of the masked half of the score square is never
    computed."""
    n, dk = q.shape[0], q.shape[-1]
    block = min(block, n)
    groups = min(groups, n // block)
    assert n % (block * groups) == 0, (n, block, groups)

    @jax.checkpoint
    def one(start, qb, kb, vb):
        s = _mm("qhd,khd->hqk", qb, kb, precision) / np.sqrt(dk)
        qpos = start + jnp.arange(block)
        s = jnp.where(qpos[None, :, None] >= jnp.arange(kb.shape[0])[None, None, :],
                      s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb, precision)

    out, run = [], n // groups
    for end in range(run, n + 1, run):
        starts = jnp.arange(end - run, end, block)
        qs = q[end - run:end].reshape((run // block, block) + q.shape[1:])
        o = lax.map(lambda a: one(a[0], a[1], k[:end], v[:end]), (starts, qs))
        out.append(o.reshape((run,) + o.shape[2:]))
    return jnp.concatenate(out)


def gqa(a, p, x, precision):
    n, h, kv, hd = x.shape[0], a["heads"], a["kv_heads"], a["head_dim"]
    q = _mm("pd,df->pf", x, p["q"], precision).reshape(n, h, hd)
    k = _mm("pd,df->pf", x, p["k"], precision).reshape(n, kv, hd)
    v = _mm("pd,df->pf", x, p["v"], precision).reshape(n, kv, hd)
    q = rotary(rmsnorm(q, p["q_norm"], a["eps"]), a["theta"])
    k = rotary(rmsnorm(k, p["k_norm"], a["eps"]), a["theta"])
    # query heads g*(h/kv) .. read key/value head g: every query head its copy
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
    o = causal_attention(q, k, v, precision)
    return _mm("pf,fd->pd", o.reshape(n, h * hd), p["o"], precision)


def shortconv(a, p, x, precision):
    n, taps = x.shape[0], a["taps"]
    b, c, z = jnp.split(_mm("pd,df->pf", x, p["in_proj"], precision), 3, axis=-1)
    # zeros before position 0: s[t + taps - 1] is position t's B * z
    s = jnp.concatenate([jnp.zeros((taps - 1, b.shape[1]), b.dtype), b * z])
    conv = sum(p["conv"][:, j] * s[j:j + n] for j in range(taps))
    return _mm("pd,de->pe", c * conv, p["out_proj"], precision)


def swiglu(x, gate, up, down, precision):
    return _mm("pw,wd->pd", jax.nn.silu(_mm("pd,dw->pw", x, gate, precision))
               * _mm("pd,dw->pw", x, up, precision), down, precision)


def route(a, p, x):
    """(chosen experts [P, k], their weights [P, k]): float32 always."""
    s = jax.nn.sigmoid(jnp.einsum("pd,de->pe", x, p["router"]))
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]), a["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if a["norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + a["norm_eps"])
    return idx, w * a["scale"]


def moe(a, p, x, precision):
    """This chip's part of the expert layer's result: every held expert over
    every position, weighted by the router's weight where the position chose
    it and by 0 where it did not (four times the products the routed slots
    need: plain, and exact whatever the load). Returns (y, chosen experts)."""
    idx, w = route(a, p, x)
    y = jnp.zeros_like(x)
    for e in range(a["held"]):
        w_e = jnp.sum(jnp.where(idx == a["first"] + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(
            x, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e],
            precision)
    return y, idx


OPS = {"shortconv": shortconv, "gqa": gqa}


def _blocks(layers):
    """[(operator norm, operator, mlp norm, mlp) entries of one decoder
    block], from the table."""
    body = [e for e in layers if e[1] in ("rmsnorm", "shortconv", "gqa", "mlp",
                                          "moe") and e[0] != "final_norm"]
    return [body[i:i + 4] for i in range(0, len(body), 4)]


def row_loss(params, ids, *, layers=LAYERS, precision="float32"):
    """One row's (loss, parts): the mean over the positions that have a
    target of CE(next token); parts = the experts every expert layer chose."""
    table = {name: (kind, a) for name, kind, a in layers}
    x = params["embed"]["w"][ids]
    chosen = {}

    def block(x, p_on, p_op, p_mn, p_ml, entries):
        (_, _, a_n), (_, op, a_op), _, (_, kind, a_ml) = entries
        x = x + OPS[op](a_op, p_op, rmsnorm(x, p_on["scale"], a_n["eps"]),
                        precision)
        h = rmsnorm(x, p_mn["scale"], a_n["eps"])
        if kind == "mlp":
            return x + swiglu(h, p_ml["gate"], p_ml["up"], p_ml["down"],
                              precision), None
        y, idx = moe(a_ml, p_ml, h, precision)
        return x + y, idx

    for entries in _blocks(layers):
        x, idx = jax.checkpoint(functools.partial(block, entries=entries))(
            x, *(params[e[0]] for e in entries))
        if idx is not None:
            chosen[entries[3][0]] = idx

    def ce(h, scale, table_w, eps):
        # the tied head: the embedding's own matrix, transposed
        logits = _mm("pd,vd->pv", rmsnorm(h, scale, eps), table_w, precision)
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))

    eps = table["final_norm"][1]["eps"]
    tied = table["lm_head"][1]["tied"]
    loss = jax.checkpoint(lambda h, s, w: ce(h, s, w, eps))(
        x, params["final_norm"]["scale"], params[tied]["w"])
    return loss, {"chosen": chosen}


# -- Caffe SGD ---------------------------------------------------------------

def multipliers(pname: str) -> tuple:
    """(lr_mult, decay_mult) by parameter name: norms' scales are not
    decayed; the router's selection bias is a buffer, neither trained nor
    decayed."""
    if pname == "router_bias":
        return 0.0, 0.0
    if pname.endswith("norm") or pname == "scale":
        return 1.0, 0.0
    return 1.0, 1.0


def learning_rate(solver: dict, it):
    if solver["lr_policy"] == "fixed":
        return jnp.asarray(solver["base_lr"], jnp.float32)
    raise ValueError(f"lr_policy {solver['lr_policy']!r} is not in this reference")


# One step of V <- mu V + lr lr_mult (g + wd decay_mult W); W <- W - V, with g
# the mean of the rows' gradients, taken so that a chip holds W, V and ONE
# row's gradient: V is decayed first, every row's gradient goes straight into
# it, W takes it last. The sum is the rule's, in another order.

def _leafwise(fn, *trees):
    return {name: {pn: fn(pn, *(t[name][pn] for t in trees)) for pn in lp}
            for name, lp in trees[0].items()}


@functools.partial(jax.jit, static_argnames=("solver_items",), donate_argnums=(1,))
def _decay_momentum(params, momentum, it, *, solver_items):
    solver = dict(solver_items)
    rate = learning_rate(solver, it.astype(jnp.float32))
    return _leafwise(
        lambda pn, w, v: solver["momentum"] * v + rate * multipliers(pn)[0] * (
            solver["weight_decay"] * multipliers(pn)[1]) * w, params, momentum)


@functools.partial(jax.jit, static_argnames=("statics", "solver_items", "rows"),
                   donate_argnums=(1,))
def _add_row_gradient(params, momentum, ids, it, *, statics, solver_items, rows):
    """(one row's loss, the experts its expert layers chose, `momentum` +
    lr lr_mult g / rows), g that row's gradient; `momentum` is consumed."""
    table_key, precision = statics
    with jax.default_matmul_precision("highest"):
        (value, parts), g = jax.value_and_grad(row_loss, has_aux=True)(
            params, ids, layers=_TABLES[table_key], precision=precision)
    rate = learning_rate(dict(solver_items), it.astype(jnp.float32))
    return value, parts["chosen"], _leafwise(
        lambda pn, v, g: v + (rate * multipliers(pn)[0] / rows) * g, momentum, g)


_apply_momentum = jax.jit(lambda params, momentum: jax.tree.map(
    jnp.subtract, params, momentum), donate_argnums=(0,))


#: layer tables by their JSON text: a table holds dicts, so the jitted
#: functions take the text as their static argument and look the table up
_TABLES: dict = {}


def _table_key(layers) -> str:
    key = json.dumps(layers, sort_keys=True)
    _TABLES[key] = layers
    return key


def worker_round(params, rows, *, tau, solver, layers=LAYERS,
                 precision="float32", device=None):
    """tau local steps from `params` (consumed) with zero momentum; `rows(t)`
    gives step t's ids [rows, P]. Returns (params, momentum, [tau losses],
    {expert layer: the experts step 0's positions chose, [rows, P, k]})."""
    put = functools.partial(jax.device_put, device=device)
    p = put(params)
    m = jax.tree.map(jnp.zeros_like, p)
    it = put(jnp.zeros((), jnp.int32))
    statics = (_table_key(layers), precision)
    solver_items = tuple(sorted(solver.items()))
    losses, first_chosen = [], []
    for t in range(tau):
        ids, value = put(rows(t)), 0.0
        m = _decay_momentum(p, m, it, solver_items=solver_items)
        for r in range(ids.shape[0]):
            v, chosen, m = _add_row_gradient(
                p, m, ids[r], it, statics=statics, solver_items=solver_items,
                rows=int(ids.shape[0]))
            value = value + v / ids.shape[0]
            if t == 0:
                first_chosen.append(chosen)
        p, it = _apply_momentum(p, m), it + 1
        losses.append(value)
    return p, m, losses, {k: np.stack([np.asarray(c[k]) for c in first_chosen])
                          for k in first_chosen[0]}


def round_reference(params0, rows, round_key=None, *, tau, solver,
                    n_workers=1, precision="float32", devices=None,
                    layers=LAYERS, mtp_weight=None):
    """What one round of this configuration should produce: per-leaf norms of
    the momentum and of the parameters' change, the loss, the probe leaf's
    momentum, and the experts step 0's positions chose. `rows(t, w)` gives
    worker w's ids of step t. One worker (the deployment's four chips are ONE
    tau-averaging worker, and this is one chip's share of it), so the
    boundary average is the identity. `params0` may be a function that makes
    the weights: at the published widths a second copy held through the round
    does not fit the chip. `mtp_weight` is the token driver's keyword for a
    model with a second head: accepted, and nothing here reads it."""
    assert n_workers == 1, "this configuration is one worker"
    del mtp_weight
    device = (devices or jax.devices())[0]
    make = params0 if callable(params0) else (
        lambda: jax.tree.map(jnp.array, params0))
    p, m, losses, chosen = worker_round(
        make(), lambda t: rows(t, 0), tau=tau, solver=solver, layers=layers,
        precision=precision, device=device)
    upd = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(p, make())
    mom = jax.jit(lambda a: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), a))(m)
    flat = lambda tree: {f"{ln}/{pn}": float(x) for ln, lp in tree.items()
                         for pn, x in lp.items()}
    return {"loss": float(np.mean([float(v) for v in losses])),
            "update_norms": flat(upd), "momentum_norms": [flat(mom)],
            "probe": [np.asarray(m[PROBE_LEAF[0]][PROBE_LEAF[1]])],
            # of step 0, from the benchmark's weights: what the routing
            # comparison holds the program's forward pass against
            "chosen": chosen}
