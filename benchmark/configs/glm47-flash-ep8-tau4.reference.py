"""Plain reference for the `glm47-flash-ep8-tau4` configuration.

GLM-4.7-Flash (`glm4_moe_lite`: huggingface.co/zai-org/GLM-4.7-Flash
config.json) as ONE CHIP'S SHARE of an eight-chip expert-parallel deployment,
written out in straightforward `jax.numpy`: float32, matmul precision
`highest`, no kernels, no cache. It imports nothing of the program and takes
nothing the program made: the benchmark makes the weights (`init_params`, from
the configuration's `weights_seed`) and the token ids, and hands both sides
the same.

The model, per row of token ids t_0 .. t_{P-1} (x is [P, d]; RMSNorm eps from
the config; pre-norm residual blocks):

  x = E[t]                                   E the held vocabulary rows
  per layer:  x += MLA(RMSNorm(x));  x += MLP(RMSNorm(x))
    MLA   c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads [q_nope | q_rope]
          [c_kv | k_rope] = x W_kva; c_kv = RMSNorm(c_kv)
          c_kv W_kvb -> heads [k_nope | v]; rotary on q_rope and on the ONE
          k_rope all heads share; scores q.k / sqrt(nope + rope), causal
          softmax, P v, concat(heads) W_o
    MLP   leading dense layers: (silu(x W_g) * x W_u) W_d
          expert layers: s = sigmoid(x W_r) over ALL published experts; the
          top k of s + b; w = s / sum(chosen s) * scale; y = sum over the
          chosen experts THIS CHIP HOLDS of w_e expert_e(x) + shared(x).
          What the absent experts would add is left out, as in the program.
  logits = RMSNorm(x) W_out                  over the held vocabulary rows
  MTP   h' = [RMSNorm_h(x_i) ; RMSNorm_e(E[t_{i+1}])] W_eh, one expert
        block on h', a norm of its own, the SAME W_out -> predicts t_{i+2}
  L = mean_i CE(logits_i, t_{i+1}) + lambda * mean_i CE(mtp_i, t_{i+2})

Left out here and in the program alike (`changed_from_source` in the
configuration file): the balance update of b and any auxiliary balance loss,
dropout, document masks. The rotary pairing, lambda and the initialisation are
`assumed` there.

To fit a chip at the published widths the gradient is taken one row at a
time and summed, every block is recomputed in the backward pass
(`jax.checkpoint`) and the attention scores are made one block of queries at
a time; none of that changes a number beyond float32's summation order.

`precision` other than "float32" is the CONTROL (see `LIMITS`): the same
mathematics with both operands of every matmul (the router's excepted: it is
float32 on both sides by the model's own rule) and the cotangent of its
output rounded per tensor to fp8 e4m3, the step below the configuration's
bfloat16.
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
with open(os.path.join(HERE, "glm47-flash-ep8-tau4.json")) as _f:
    CONFIG = json.load(_f)

#: queries a block in the attention core (scores [heads, block, P] float32)
ATTN_BLOCK = 512
#: runs the queries go in, each against the keys up to its end (4: 62.5 % of
#: the score square is computed; more runs compile longer)
ATTN_GROUPS = 4

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`, the full rate) against
#: `round_reference`. Each limit stands with the v5e readings it was set from
#: (PR 27's chip runs at the cell's own size, `benchmark/token_control.py`;
#: PERF.md section 2 repeats them): "sound" is the program over twelve seeds
#: (the weights are the configuration's, so the readings hardly move),
#: "control" the fp8 control over two. Every limit lies between the two.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum: the number
    # the lower-precision control fails by most. Sound 0.0428-0.0452, control
    # 0.2018-0.2033: twice the one, under half the other.
    "probe_diff": 0.09,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round. Sound 0.00234-0.00251, control
    # 0.00687-0.00937. Held against a step's rows or a loss head left out.
    "momentum_gap": 0.0042,
    # the same over the parameters' change across the round; held against a
    # round that returns its state unchanged (gap 1.0). Sound 0.00233-0.00265,
    # control 0.00754-0.00987.
    "update_gap": 0.0045,
    # |program round loss - reference round loss|; held against the MTP loss
    # left out (0.3 x ln 19360 = 2.96). Sound 6e-6-1.7e-4, control
    # 5.3e-4-6.9e-4.
    "loss_gap": 3.5e-4,
    # the worst expert layer's share of routed slots whose expert differs
    # between the program's forward pass (bf16 stream) and this file's
    # (float32), the router float32 on both sides: sound 0.0134-0.0157 (the
    # last expert layer, always; the first reads 0.0082-0.0088). No control
    # reads it; three times the sound reading, held against a router that
    # reads a coarser stream, or another bias, than the model's.
    "routing_diff_share": 0.045,
}
#: the step below the configuration's bfloat16
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the leading dense
#: layer's down projection. Its gradient carries the whole backward pass
#: through five blocks of attention and experts and both heads; the output
#: matrix's own would carry the forward pass alone.
PROBE_LEAF = ("l0_mlp", "down")


# -- the layer table ---------------------------------------------------------

def layer_table(config: dict) -> tuple:
    """(name, kind, args) of every layer that holds parameters, in execution
    order; the names are the program's (`zoo.glm4_moe_lite`)."""
    c, share = config, config["share"]
    d = c["hidden_size"]
    attn = dict(d=d, heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
                kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
                theta=float(c["rope_theta"]), eps=c["rms_norm_eps"])
    moe = dict(d=d, width=c["moe_intermediate_size"],
               routed=share["n_routed_experts"],
               first=share["experts_held"][0], held=share["experts_held"][1],
               k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
               scale=c["routed_scaling_factor"], norm=c["norm_topk_prob"])
    norm = dict(d=d, eps=c["rms_norm_eps"])
    vocab = share["vocab_rows"][1]
    table = [("embed", "embed", dict(vocab=vocab, d=d))]
    for i in range(c["num_hidden_layers"]):
        table += [(f"l{i}_attn_norm", "rmsnorm", norm),
                  (f"l{i}_attn", "mla", attn),
                  (f"l{i}_mlp_norm", "rmsnorm", norm)]
        table.append((f"l{i}_mlp", "mlp", dict(d=d, width=c["intermediate_size"]))
                     if i < c["first_k_dense_replace"]
                     else (f"l{i}_moe", "moe", moe))
    table += [("final_norm", "rmsnorm", norm),
              ("lm_head", "head", dict(d=d, vocab=vocab))]
    if c.get("num_nextn_predict_layers", 0):
        table.append(("mtp", "mtp", dict(attn=attn, moe=moe, eps=norm["eps"], d=d)))
    return tuple(table)


LAYERS = layer_table(CONFIG)


def _mla_shapes(a):
    return {"q_a": (a["d"], a["q_rank"]), "q_a_norm": (a["q_rank"],),
            "q_b": (a["q_rank"], a["heads"] * (a["nope"] + a["rope"])),
            "kv_a": (a["d"], a["kv_rank"] + a["rope"]),
            "kv_a_norm": (a["kv_rank"],),
            "kv_b": (a["kv_rank"], a["heads"] * (a["nope"] + a["v"])),
            "o": (a["heads"] * a["v"], a["d"])}


def _moe_shapes(a):
    d, w, sw = a["d"], a["width"], a["width"] * a["shared"]
    out = {"router": (d, a["routed"]), "router_bias": (a["routed"],),
           "experts_gate": (a["held"], d, w), "experts_up": (a["held"], d, w),
           "experts_down": (a["held"], w, d)}
    if a["shared"]:
        out.update(shared_gate=(d, sw), shared_up=(d, sw), shared_down=(sw, d))
    return out


def param_shapes(layers=LAYERS) -> dict:
    """{layer: {parameter: shape}}: what this chip holds."""
    shapes = {}
    for name, kind, a in layers:
        if kind == "embed":
            shapes[name] = {"w": (a["vocab"], a["d"])}
        elif kind == "head":
            shapes[name] = {"w": (a["d"], a["vocab"])}
        elif kind == "rmsnorm":
            shapes[name] = {"scale": (a["d"],)}
        elif kind == "mla":
            shapes[name] = _mla_shapes(a)
        elif kind == "mlp":
            shapes[name] = {"gate": (a["d"], a["width"]),
                            "up": (a["d"], a["width"]),
                            "down": (a["width"], a["d"])}
        elif kind == "moe":
            shapes[name] = _moe_shapes(a)
        elif kind == "mtp":
            d = a["d"]
            shapes[name] = {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                            "attn_norm": (d,), **_mla_shapes(a["attn"]),
                            "mlp_norm": (d,), **_moe_shapes(a["moe"]),
                            "norm": (d,)}
    return shapes


def n_params(layers=LAYERS) -> int:
    return sum(int(np.prod(s)) for lp in param_shapes(layers).values()
               for s in lp.values())


def init_params(weights_seed: int, layers=LAYERS, std: float = 0.02) -> dict:
    """The benchmark's weights, one jitted call on the device: normal(0,
    `std`) for every matrix and for the router's selection bias, ones for
    every norm's scale. From the configuration's `weights_seed`, NOT from
    the run's seed: which experts a random router favours is a property of
    the draw (configuration file, `assumed`)."""
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
    """x [P, ..., d], position = index along axis 0; pairs (x[2i], x[2i+1]),
    frequency theta^(-2i/d); written out half-split (rotated pairs side by
    side would give every dot product the same value)."""
    d, n = x.shape[-1], x.shape[0]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(n)[:, None] * inv[None, :], jnp.float32)
    ang = ang.reshape((n,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def causal_attention(q, k, v, precision, block=ATTN_BLOCK, groups=ATTN_GROUPS):
    """q, k [P, heads, dk], v [P, heads, dv] -> [P, heads, dv]: the exact
    causal softmax, `block` queries at a time, the scores made again in the
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


def mla(a, p, x, precision):
    n, h, nope = x.shape[0], a["heads"], a["nope"]
    c_q = rmsnorm(_mm("pd,dr->pr", x, p["q_a"], precision), p["q_a_norm"], a["eps"])
    q = _mm("pr,rf->pf", c_q, p["q_b"], precision).reshape(n, h, nope + a["rope"])
    kv_a = _mm("pd,dr->pr", x, p["kv_a"], precision)
    c_kv = rmsnorm(kv_a[:, :a["kv_rank"]], p["kv_a_norm"], a["eps"])
    kv = _mm("pr,rf->pf", c_kv, p["kv_b"], precision).reshape(n, h, nope + a["v"])
    k_rope = rotary(kv_a[:, a["kv_rank"]:], a["theta"])          # [P, rope]
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], a["theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope[:, None, :], (n, h, a["rope"]))], -1)
    o = causal_attention(q, k, kv[..., nope:], precision)
    return _mm("pf,fd->pd", o.reshape(n, h * a["v"]), p["o"], precision)


def swiglu(x, gate, up, down, precision):
    return _mm("pw,wd->pd", jax.nn.silu(_mm("pd,dw->pw", x, gate, precision))
               * _mm("pd,dw->pw", x, up, precision), down, precision)


def route(a, p, x):
    """(chosen experts [P, k], their weights [P, k]): float32 always."""
    s = jax.nn.sigmoid(jnp.einsum("pd,de->pe", x, p["router"]))
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]), a["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if a["norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * a["scale"]


def moe(a, p, x, precision):
    """This chip's part of the expert layer's result, the shared expert with
    it: every held expert over every position, weighted by the router's
    weight where the position chose it and by 0 where it did not (eight
    times the products the routed slots need: plain, and exact whatever the
    load). Returns (y, chosen experts)."""
    idx, w = route(a, p, x)
    y = jnp.zeros_like(x)
    for e in range(a["held"]):
        w_e = jnp.sum(jnp.where(idx == a["first"] + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(
            x, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e],
            precision)
    if a["shared"]:
        y = y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                       precision)
    return y, idx


def _blocks(params, layers):
    """[(attention norm, attention, mlp norm, mlp) entries of one decoder
    block], from the table."""
    body = [e for e in layers if e[1] in ("rmsnorm", "mla", "mlp", "moe")
            and e[0] != "final_norm"]
    return [body[i:i + 4] for i in range(0, len(body), 4)]


def row_loss(params, ids, *, layers=LAYERS, mtp_weight=0.3,
             precision="float32"):
    """One row's (loss, parts): loss = CE(next) + mtp_weight * CE_mtp(second
    next), each a mean over the positions that have a target; parts = the
    two terms and the experts every expert layer chose."""
    table = {name: (kind, a) for name, kind, a in layers}
    x = params["embed"]["w"][ids]
    chosen = {}

    def block(x, p_an, p_at, p_mn, p_ml, entries):
        (_, _, a_n), (_, _, a_at), _, (name, kind, a_ml) = entries
        x = x + mla(a_at, p_at, rmsnorm(x, p_an["scale"], a_n["eps"]), precision)
        h = rmsnorm(x, p_mn["scale"], a_n["eps"])
        if kind == "mlp":
            return x + swiglu(h, p_ml["gate"], p_ml["up"], p_ml["down"],
                              precision), None
        y, idx = moe(a_ml, p_ml, h, precision)
        return x + y, idx

    for entries in _blocks(params, layers):
        x, idx = jax.checkpoint(functools.partial(block, entries=entries))(
            x, *(params[e[0]] for e in entries))
        if idx is not None:
            chosen[entries[3][0]] = idx

    def ce(h, scale, eps, shift):
        logits = _mm("pd,dv->pv", rmsnorm(h, scale, eps),
                     params["lm_head"]["w"], precision)
        logp = jax.nn.log_softmax(logits[:-shift], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[shift:, None], axis=-1))

    eps = table["final_norm"][1]["eps"]
    loss_next = jax.checkpoint(lambda h, s: ce(h, s, eps, 1))(
        x, params["final_norm"]["scale"])
    loss_mtp = jnp.zeros((), jnp.float32)
    if "mtp" in table:
        a, p = table["mtp"][1], params["mtp"]

        def mtp(h, p, emb):
            e = emb[jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])]
            z = _mm("pf,fd->pd", jnp.concatenate(
                [rmsnorm(h, p["hnorm"], a["eps"]),
                 rmsnorm(e, p["enorm"], a["eps"])], -1), p["eh_proj"], precision)
            z = z + mla(a["attn"], p, rmsnorm(z, p["attn_norm"], a["eps"]),
                        precision)
            y, idx = moe(a["moe"], p, rmsnorm(z, p["mlp_norm"], a["eps"]),
                         precision)
            return z + y, idx

        z, chosen["mtp"] = jax.checkpoint(mtp)(x, p, params["embed"]["w"])
        loss_mtp = jax.checkpoint(lambda h, s: ce(h, s, a["eps"], 2))(
            z, p["norm"])
    return loss_next + mtp_weight * loss_mtp, {
        "loss_next": loss_next, "loss_mtp": loss_mtp, "chosen": chosen}


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
    table_key, mtp_weight, precision = statics
    with jax.default_matmul_precision("highest"):
        (value, parts), g = jax.value_and_grad(row_loss, has_aux=True)(
            params, ids, layers=_TABLES[table_key], mtp_weight=mtp_weight,
            precision=precision)
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


def worker_round(params, rows, *, tau, solver, layers=LAYERS, mtp_weight=0.3,
                 precision="float32", device=None):
    """tau local steps from `params` (consumed) with zero momentum; `rows(t)`
    gives step t's ids [rows, P]. Returns (params, momentum, [tau losses],
    {expert layer: the experts step 0's positions chose, [rows, P, k]})."""
    put = functools.partial(jax.device_put, device=device)
    p = put(params)
    m = jax.tree.map(jnp.zeros_like, p)
    it = put(jnp.zeros((), jnp.int32))
    statics = (_table_key(layers), float(mtp_weight), precision)
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
    momentum, and the experts step 0's positions chose. `rows(t, w)` gives worker w's ids of step t. One worker
    (the deployment's eight chips are ONE tau-averaging worker, and this is
    one chip's share of it), so the boundary average is the identity.
    `params0` may be a function that makes the weights: at the published
    widths a second copy held through the round does not fit the chip."""
    assert n_workers == 1, "this configuration is one worker"
    if mtp_weight is None:
        mtp_weight = CONFIG["share"].get("mtp_loss_weight", 0.3)
    device = (devices or jax.devices())[0]
    make = params0 if callable(params0) else (
        lambda: jax.tree.map(jnp.array, params0))
    p, m, losses, chosen = worker_round(make(), lambda t: rows(t, 0), tau=tau,
                                solver=solver, layers=layers,
                                mtp_weight=mtp_weight, precision=precision,
                                device=device)
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
