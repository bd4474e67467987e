"""Plain reference for the `evabyte-l4-tau4` configuration.

EvaByte (huggingface.co/EvaByte/EvaByte config.json: a dense byte-level
decoder whose attention is EVA -- Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", arXiv:2302.04542 -- as simplified for causal
byte-level modelling), four of its 32 layers whole on one chip, written out
in straightforward `jax.numpy`: float32, matmul precision `highest`, no
kernels. It imports nothing of the program and takes nothing the program
made: the benchmark makes the weights (`init_params`, from the
configuration's `weights_seed`) and the byte ids, and hands both sides the
same.

The model, per row of byte ids t_0 .. t_{P-1} (x is [P, d], d = 4096; H = 32
heads of 128; s = 128^-1/2; W = window_size = 2048; C = chunk_size = 16; P a
multiple of W; RMSNorm eps 1e-5 with scale (1 + g); no biases):

  x = E[t]
  per layer:
    u = RMSNorm(x); q, k, v = u W_q, u W_k, u W_v, a head at a time; rotary
        over the whole head on q and k (theta 1e5, pairs (j, j + 64))
    chunk c = positions cC .. cC + C - 1, in window floor(cC / W):
        a_{c,j} = softmax over j in c of (s phi . k_j)     phi [128] a head
        v~_c = sum_j a_{c,j} v_j;  k~_c = (1/C) sum_j k_j + mu   mu [128] a head
    position i, in window w(i) = floor(i / W), under ONE softmax:
        o_i = [ sum_{j <= i, w(j) = w(i)} e^{s q_i.k_j} v_j
                + sum_{c: floor(cC/W) < w(i)} e^{s q_i.k~_c} v~_c ]
              / [ the same sums without v ]
        computed A WINDOW AT A TIME: window w's queries against its own
        keys (causal) and the summaries of the chunks of windows 0 .. w - 1
    x <- x + o W_o;  x <- x + (silu(r W_g) * r W_u) W_d, r = RMSNorm(x)
  logits = RMSNorm(x_last) W_head, read as [P, 8, 320]
  L = mean over m = 0 .. 7 of mean_{i < P - 1 - m} CE(logits_{i,m}, t_{i+1+m})

Left out here and in the program alike (`changed_from_source` in the
configuration file): dropout, document masks (one document a row). What the
published keys do not settle is `assumed` there.

To fit a chip at the published widths every block is recomputed in the
backward pass (`jax.checkpoint`), a window's scores are made one block of
queries at a time and the SwiGLU one block of positions at a time; none of
that changes a number beyond float32's summation order.

`precision` other than "float32" is the CONTROL (see `LIMITS`): the same
mathematics with both operands of every matmul and the cotangent of its output
rounded per tensor to fp8 e4m3, the step below the configuration's bfloat16.
The summaries are no matmul (products and sums a position at a time) and stay
float32. `summaries=False` is the SECOND control: the round with the summary
columns masked out, every window reading its own keys alone.
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
with open(os.path.join(HERE, "evabyte-l4-tau4.json")) as _f:
    CONFIG = json.load(_f)

#: queries a block in the attention core (scores [heads, block, keys] float32)
ATTN_BLOCK = 512
#: positions a block in the SwiGLU ([block, 11008] float32 three times)
MLP_BLOCK = 4096

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`, the full rate) against
#: `round_reference`. Each limit stands with the v5e readings it was set from
#: (PR 40's chip runs at the cell's own size: `benchmark/byte_control.py` and
#: every benchmark run; PERF.md section 2 repeats them and names the seeds):
#: "sound" is the program as configured over eleven seeds (4000000001,
#: 4000000011-14, 4000000021-26; the weights are the configuration's, so the
#: readings hardly move with the ids), "fp8" the lower-precision control and
#: "blind" the round with the summary columns masked out, two seeds each
#: (4000000011, 4000000014). Both controls fail every one of the four.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum, a leaf only
    # the summaries reach: a program that drops them reads 1 (what is left
    # of its momentum there is the weight decay's part), and the lower
    # precision shows here first. Sound 0.0139-0.0159, fp8 0.195 and 0.217,
    # blind 0.996 and 0.997: 3.1 times the largest sound reading, a quarter
    # of the smallest control's.
    "probe_diff": 0.05,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round, and the same over the parameters'
    # change across the round. Sound 0.00044-0.00099 (momentum) and
    # 0.00045-0.00082 (change), always a SwiGLU matrix or an attention
    # output; fp8 0.0119-0.0150 and 0.0127-0.0182; blind 0.64-0.66 and
    # 0.49-0.51; a round that returns its state unchanged 1.0. Four and five
    # times the largest sound reading, a third of the smallest control's.
    "momentum_gap": 0.004,
    "update_gap": 0.004,
    # |program round loss - reference round loss|: sound 8e-6-1.03e-4, fp8
    # 6.5e-4 and 8.5e-4, blind 0.062 and 0.063. It guards the loss's own
    # arithmetic (eight heads, each a mean over the positions it scores, the
    # norm before the head, the head's matrix) and, here, the precision too:
    # 3.9 times the largest sound reading, 0.62 of the smallest control's
    # (nearer the control's side, because fresh seeds read higher on the
    # sound side and the other three limits hold the precision as well).
    "loss_gap": 4.0e-4,
}
#: the step below the configuration's bfloat16
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the first layer's
#: phi, which only the chunk summaries' weights read. Its gradient carries
#: the backward pass through the four blocks and the eight heads, and is
#: zero in a program whose queries read no summary.
PROBE_LEAF = ("l0_attn", "phi")


# -- the layer table ---------------------------------------------------------

def layer_table(config: dict) -> tuple:
    """(name, kind, args) of every layer of the table, in execution order;
    the names are the program's (`zoo.evabyte`). Kinds `mlp` and `head` carry
    the keys `benchmark/lm_flops.py` reads (`head`'s `vocab` is all eight
    heads' columns); `eva` those `benchmark/eva_lm_flops.py` reads."""
    c = config
    d, eps, heads = c["hidden_size"], c["rms_norm_eps"], c["num_attention_heads"]
    attn = dict(d=d, heads=heads, head_dim=d // heads, window=c["window_size"],
                chunk=c["chunk_size"], theta=float(c["rope_theta"]))
    norm = dict(d=d, eps=eps, offset=bool(c["norm_add_unit_offset"]))
    table = [("embed", "embed", dict(vocab=c["vocab_size"], d=d))]
    for i in range(c["num_hidden_layers"]):
        table += [(f"l{i}_attn_norm", "rmsnorm", norm),
                  (f"l{i}_attn", "eva", attn),
                  (f"l{i}_mlp_norm", "rmsnorm", norm),
                  (f"l{i}_mlp", "mlp", dict(d=d, width=c["intermediate_size"]))]
    table += [("final_norm", "rmsnorm", norm),
              ("lm_head", "head", dict(d=d, heads=c["num_pred_heads"],
                                       vocab=c["num_pred_heads"] * c["vocab_size"]))]
    return tuple(table)


LAYERS = layer_table(CONFIG)


def param_shapes(layers=LAYERS) -> dict:
    """{layer: {parameter: shape}}: what this chip holds."""
    shapes = {}
    for name, kind, a in layers:
        d = a["d"]
        if kind == "embed":
            shapes[name] = {"w": (a["vocab"], d)}
        elif kind == "head":
            shapes[name] = {"w": (d, a["vocab"])}
        elif kind == "rmsnorm":
            shapes[name] = {"scale": (d,)}
        elif kind == "eva":
            h, f = a["heads"], a["heads"] * a["head_dim"]
            shapes[name] = {"q": (d, f), "k": (d, f), "v": (d, f),
                            "mu": (h, a["head_dim"]), "phi": (h, a["head_dim"]),
                            "o": (f, d)}
        elif kind == "mlp":
            shapes[name] = {"gate": (d, a["width"]), "up": (d, a["width"]),
                            "down": (a["width"], d)}
    return shapes


def n_params(layers=LAYERS) -> int:
    return sum(int(np.prod(s)) for lp in param_shapes(layers).values()
               for s in lp.values())


def init_params(weights_seed: int, layers=LAYERS,
                std: float = CONFIG["init_std"]) -> dict:
    """The benchmark's weights, one jitted call on the device: normal(0,
    `std`) for every matrix, the embedding, mu and phi; zeros for every
    norm's stored scale (the scale is 1 + g). From the configuration's
    `weights_seed`, not from the run's seed (configuration file, `assumed`)."""
    shapes = param_shapes(layers)

    @jax.jit
    def make(key):
        out, i = {}, 0
        for name, lp in shapes.items():
            out[name] = {}
            for pn, sh in lp.items():
                i += 1
                out[name][pn] = (
                    jnp.zeros(sh, jnp.float32) if pn == "scale" else
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

def rmsnorm(x, g, eps, offset: bool):
    scale = 1.0 + g if offset else g
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x [P, heads, d], position = index along axis 0; pairs (x[j],
    x[j + d/2]), frequency theta^(-2j/d)."""
    d, n = x.shape[-1], x.shape[0]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(n)[:, None] * inv[None, :], jnp.float32)
    ang = ang[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def chunk_summaries(k, v, mu, phi, chunk: int):
    """(k~ [chunks, heads, d], v~ [chunks, heads, d]) of k, v [P, heads, d]:
    a position at a time, no matmul."""
    n, h, d = k.shape
    kc, vc = k.reshape(n // chunk, chunk, h, d), v.reshape(n // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.sum(kc * phi, axis=-1) / np.sqrt(d), axis=1)
    return jnp.mean(kc, axis=1) + mu, jnp.sum(a[..., None] * vc, axis=1)


def window_attention(q, k, v, k_s, v_s, precision, block=ATTN_BLOCK):
    """One window: q, k, v [W, heads, d] its own, k_s, v_s [S, heads, d] the
    summaries of the chunks before it (S may be 0). One softmax over the
    window's causal keys and all S summaries, `block` queries at a time."""
    w, d = q.shape[0], q.shape[-1]
    block = min(block, w)
    assert w % block == 0, (w, block)
    keys, values = jnp.concatenate([k_s, k]), jnp.concatenate([v_s, v])
    n_s = k_s.shape[0]

    @jax.checkpoint
    def one(start, qb):
        s = _mm("qhd,khd->hqk", qb, keys, precision) / np.sqrt(d)
        qpos = start + jnp.arange(block)
        kpos = jnp.arange(keys.shape[0]) - n_s  # summaries sit before 0
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), values, precision)

    o = lax.map(lambda a: one(a[0], a[1]),
                (jnp.arange(0, w, block), q.reshape((w // block, block) + q.shape[1:])))
    return o.reshape((w,) + o.shape[2:])


def eva(a, p, x, precision, summaries: bool = True):
    n, h, hd = x.shape[0], a["heads"], a["head_dim"]
    w = min(a["window"], n)
    assert n % w == 0 and w % a["chunk"] == 0, (n, w, a["chunk"])
    heads = lambda name: _mm("pd,df->pf", x, p[name], precision).reshape(n, h, hd)
    q, k, v = rotary(heads("q"), a["theta"]), rotary(heads("k"), a["theta"]), heads("v")
    k_s, v_s = chunk_summaries(k, v, p["mu"], p["phi"], a["chunk"])
    per = w // a["chunk"]  # chunks a window
    out = []
    for i in range(n // w):
        seen = i * per if summaries else 0
        out.append(window_attention(
            q[i * w:(i + 1) * w], k[i * w:(i + 1) * w], v[i * w:(i + 1) * w],
            k_s[:seen], v_s[:seen], precision))
    o = jnp.concatenate(out)
    return _mm("pf,fd->pd", o.reshape(n, h * hd), p["o"], precision)


def swiglu(x, gate, up, down, precision, block=MLP_BLOCK):
    block = min(block, x.shape[0])
    assert x.shape[0] % block == 0, (x.shape, block)
    one = jax.checkpoint(lambda xb: _mm(
        "pw,wd->pd", jax.nn.silu(_mm("pd,dw->pw", xb, gate, precision))
        * _mm("pd,dw->pw", xb, up, precision), down, precision))
    return lax.map(one, x.reshape(-1, block, x.shape[1])).reshape(x.shape)


def head_targets(ids, heads: int):
    """[P, heads] int32: head m's target at position i is t_{i+1+m}; -1
    where that lies beyond the row."""
    n = ids.shape[0]
    at = jnp.arange(n)[:, None] + 1 + jnp.arange(heads)[None, :]
    return jnp.where(at < n, ids[jnp.minimum(at, n - 1)], -1)


def heads_loss(logits, ids, heads: int):
    """logits [P, heads x V]: the mean over the heads of each head's mean
    cross-entropy over the positions it scores."""
    n = ids.shape[0]
    logp = jax.nn.log_softmax(logits.reshape(n, heads, -1), axis=-1)
    target = head_targets(ids, heads)
    scored = target >= 0
    nll = -jnp.take_along_axis(logp, jnp.maximum(target, 0)[..., None], axis=-1)[..., 0]
    return jnp.mean(jnp.sum(jnp.where(scored, nll, 0.0), axis=0)
                    / jnp.sum(scored, axis=0))


def _blocks(layers):
    """[(attention norm, attention, mlp norm, mlp) entries of one decoder
    block], from the table."""
    body = [e for e in layers if e[1] in ("rmsnorm", "eva", "mlp")
            and e[0] != "final_norm"]
    return [body[i:i + 4] for i in range(0, len(body), 4)]


def row_loss(params, ids, *, layers=LAYERS, precision="float32",
             summaries: bool = True):
    """One row's loss."""
    table = {name: (kind, a) for name, kind, a in layers}
    x = params["embed"]["w"][ids]

    def block(x, p_an, p_at, p_mn, p_ml, entries):
        (_, _, a_n), (_, _, a_at), _, _ = entries
        x = x + eva(a_at, p_at, rmsnorm(x, p_an["scale"], a_n["eps"], a_n["offset"]),
                    precision, summaries)
        return x + swiglu(rmsnorm(x, p_mn["scale"], a_n["eps"], a_n["offset"]),
                          p_ml["gate"], p_ml["up"], p_ml["down"], precision)

    for entries in _blocks(layers):
        x = jax.checkpoint(functools.partial(block, entries=entries))(
            x, *(params[e[0]] for e in entries))

    a_n, a_h = table["final_norm"][1], table["lm_head"][1]
    return jax.checkpoint(lambda h, g, w: heads_loss(
        _mm("pd,dv->pv", rmsnorm(h, g, a_n["eps"], a_n["offset"]), w, precision),
        ids, a_h["heads"]))(x, params["final_norm"]["scale"], params["lm_head"]["w"])


# -- Caffe SGD ---------------------------------------------------------------

def multipliers(pname: str) -> tuple:
    """(lr_mult, decay_mult) by parameter name: norms' scales are not
    decayed."""
    return (1.0, 0.0) if pname == "scale" else (1.0, 1.0)


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
    """(one row's loss, `momentum` + lr lr_mult g / rows), g that row's
    gradient; `momentum` is consumed."""
    table_key, precision, summaries = statics
    with jax.default_matmul_precision("highest"):
        value, g = jax.value_and_grad(row_loss)(
            params, ids, layers=_TABLES[table_key], precision=precision,
            summaries=summaries)
    rate = learning_rate(dict(solver_items), it.astype(jnp.float32))
    return value, _leafwise(
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
                 precision="float32", summaries=True, device=None):
    """tau local steps from `params` (consumed) with zero momentum; `rows(t)`
    gives step t's ids [rows, P]. Returns (params, momentum, [tau losses])."""
    put = functools.partial(jax.device_put, device=device)
    p = put(params)
    m = jax.tree.map(jnp.zeros_like, p)
    it = put(jnp.zeros((), jnp.int32))
    statics = (_table_key(layers), precision, bool(summaries))
    solver_items = tuple(sorted(solver.items()))
    losses = []
    for t in range(tau):
        ids, value = put(rows(t)), 0.0
        m = _decay_momentum(p, m, it, solver_items=solver_items)
        for r in range(ids.shape[0]):
            v, m = _add_row_gradient(
                p, m, ids[r], it, statics=statics, solver_items=solver_items,
                rows=int(ids.shape[0]))
            value = value + v / ids.shape[0]
        p, it = _apply_momentum(p, m), it + 1
        losses.append(value)
    return p, m, losses


def round_reference(params0, rows, round_key=None, *, tau, solver,
                    n_workers=1, precision="float32", devices=None,
                    layers=LAYERS, mtp_weight=None, summaries=True):
    """What one round of this configuration should produce: per-leaf norms of
    the momentum and of the parameters' change, the loss, and the probe
    leaf's momentum. `rows(t, w)` gives worker w's ids of step t. One worker
    (the deployment's eight chips are ONE tau-averaging worker, and this is
    one chip's stage of it), so the boundary average is the identity.
    `params0` may be a function that makes the weights: at the published
    widths a second copy held through the round does not fit the chip.
    `mtp_weight` is the token driver's keyword for a model with a second
    head: accepted, and nothing here reads it. "chosen" is empty: no layer
    routes."""
    assert n_workers == 1, "this configuration is one worker"
    del mtp_weight
    device = (devices or jax.devices())[0]
    make = params0 if callable(params0) else (
        lambda: jax.tree.map(jnp.array, params0))
    p, m, losses = worker_round(
        make(), lambda t: rows(t, 0), tau=tau, solver=solver, layers=layers,
        precision=precision, summaries=summaries, device=device)
    upd = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(p, make())
    mom = jax.jit(lambda a: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), a))(m)
    flat = lambda tree: {f"{ln}/{pn}": float(x) for ln, lp in tree.items()
                         for pn, x in lp.items()}
    return {"loss": float(np.mean([float(v) for v in losses])),
            "update_norms": flat(upd), "momentum_norms": [flat(mom)],
            "probe": [np.asarray(m[PROBE_LEAF[0]][PROBE_LEAF[1]])],
            "chosen": {}}
