"""Plain reference for the `smallthinker-21b-ep4-tau4` configuration.

SmallThinker-21BA3B (`smallthinker`: huggingface.co/PowerInfer/
SmallThinker-21BA3B-Instruct config.json) as ONE CHIP'S SHARE of a four-chip
expert-parallel deployment, written out in straightforward `jax.numpy`:
float32, matmul precision `highest`, no kernels, no cache. It imports nothing
of the program and takes nothing the program made: the benchmark makes the
weights (`init_params`, from the configuration's `weights_seed`) and the
token ids, and hands both sides the same.

The model, per row of token ids t_0 .. t_{P-1} (x is [P, d], d = 2560; RMSNorm
eps `rms_norm_eps`, scale only; no biases). Every layer i:

  n = RMSNorm(x; w_in)
  r = n                              the ROUTER'S input: taken before attention
  q, k, v = n W_q, n W_k, n W_v      28 heads x 128; 4 x 128; 4 x 128; no
                                     norm over a head
  where rope_layout[i]:  q, k through the rotary turn over the whole head of
        128, theta 1.5e6, contiguous halves (x[j], x[j + 64])
  query p reads key j where j <= p, and where sliding_window_layout[i] also
        p - j < sliding_window_size (4,096: itself and the 4,095 before it)
  a = softmax(q k^T / sqrt(128) over those keys) v; query heads 7g .. 7g + 6
        read key/value head g
  h = x + a W_o
  m = RMSNorm(h; w_post)
  z = r W_r                          2560 -> 64, float32
  chosen = the top 6 of z;  w = softmax(z[chosen])   over the six alone
  y = sum over the chosen experts THIS CHIP HOLDS of
        w_e (relu(m W_gate_e) * (m W_up_e)) W_down_e        width 768
  x' = h + y

Layout 0 is a global layer WITHOUT a rotary turn, layout 1 a sliding layer
with one. No dense layer, no shared expert. What the absent experts would add
is left out, as in the program.

  logits = RMSNorm(x_last) W_head           an untied head over the held rows
  L = mean_i CE(logits_i, t_{i+1})

Left out here and in the program alike (`changed_from_source` in the
configuration file): a second level of experts (`described_as` names
"primary+secondary"; the config has no key for one), any auxiliary balance
loss, dropout, document masks (one document a row). The router's place, the
softmax over the chosen logits, the rotary pairing and the initialisation are
`assumed` there.

To fit a chip at the published widths the gradient is taken one row at a
time and summed, every block is recomputed in the backward pass
(`jax.checkpoint`), every expert again inside it, the attention scores are
made one block of queries at a time -- a sliding layer's block against the
keys its window reaches alone -- and the head's logits one run of positions
at a time; none of that changes a number beyond float32's summation order.

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
with open(os.path.join(HERE, "smallthinker-21b-ep4-tau4.json")) as _f:
    CONFIG = json.load(_f)

#: queries a block in the attention core (scores [heads, block, keys] float32)
ATTN_BLOCK = 512
#: runs a global layer's queries go in, each against the keys up to its end
#: (4: 62.5 % of the score square is computed; more runs compile longer)
ATTN_GROUPS = 4
#: positions a run of the head's logits ([run, vocabulary] float32)
HEAD_BLOCK = 2048
#: the embedding table's spread: the configuration's `embed_init_std`, its
#: own choice and not the family's initialiser, which draws the table at the
#: other matrices' 0.02. At 0.02 a token's embedding is a sixtieth of what
#: the first global layer adds to it -- the running mean of v over the row so
#: far, ONE direction a row -- and the routers of the layers above send the
#: whole row (a step is one row) to the same few experts: a layer's landed
#: slots then swing between 500 and 57,000 of an even 24,576 with the ids'
#: seed and from round to round (configuration file, `assumed`; PERF.md
#: section 4)
EMBED_STD = CONFIG["embed_init_std"]

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`, the full rate) against
#: `round_reference`. Each limit stands with the v5e readings it was set from
#: (PR 46's chip runs at the cell's own size, calls 4 to 6; PERF.md section 2
#: repeats them): "sound" is the program over twenty-two seeds (twelve
#: through the control's loop, ten benchmark runs; the weights are the
#: configuration's, so the readings hardly move), "control" the fp8 control
#: over five of them, and two faults planted in the program on one seed
#: (call 6): the router fed the experts' input, and the sliding layers
#: handed no mask. Every limit lies between its sound and its faulty
#: readings; the control fails the first four on every seed.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum: the number
    # that tells the precisions apart. Sound 0.01282-0.01423, control
    # 0.09002-0.09446 (router fault 0.102, no mask 0.427): 2.8 times the one,
    # 0.44 of the other.
    "probe_diff": 0.04,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round, and the same over the parameters'
    # change across the round. Sound 0.00029-0.00077 (momentum) and
    # 0.00036-0.00097 (change), the worst leaf a projection of an attention
    # or a router; control 0.00543-0.00880 and 0.00557-0.00994 (router fault
    # 0.0101 and 0.0138, no mask 0.123 and 0.125): precision moves both
    # sixfold or more, so each stands between, 2.6 and 3.1 times its largest
    # sound reading (0.37 and 0.54 of its smallest control reading). A round
    # that returns its state unchanged reads 1.0.
    "momentum_gap": 0.002,
    "update_gap": 0.003,
    # |program round loss - reference round loss|: sound 1e-6-8.1e-5 (the
    # other twenty-one under 8.0e-5), control 2.5e-4-5.1e-4 over five seeds:
    # here precision moves the loss too, three times or more, so the limit
    # stands between, 1.85 times the largest sound reading and 0.6 of the
    # smallest control's. Neither planted fault moves it past the limit
    # (5.5e-5 and 1.49e-4): it guards the loss's own arithmetic and the
    # precision, the other limits the layers.
    "loss_gap": 1.5e-4,
    # the worst expert layer's share of routed slots whose expert differs
    # between the program's forward pass (bf16 stream) and this file's
    # (float32), the router float32 on both sides: sound 0.00516-0.00580 (the
    # last layer, always; it grows with depth from the first's 0.0020). The
    # router fed the experts' input reads 0.105 (0.039 in the first layer
    # already), the sliding layers without their mask 0.036: 2.9 times the
    # largest sound reading, 0.16 and 0.48 of the faults'.
    "routing_diff_share": 0.017,
}
#: the step below the configuration's bfloat16
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the first layer's
#: output projection. Its gradient carries the whole backward pass through
#: the four expert layers, the three sliding cores above it and the head
PROBE_LEAF = ("l0_attn", "o")


# -- the layer table ---------------------------------------------------------

def layer_table(config: dict) -> tuple:
    """(name, kind, args) of every layer of the table, in execution order;
    the names are the program's (`zoo.smallthinker`). Kinds `moe` and `head`
    carry the keys `benchmark/lm_flops.py` reads; `gqa` those
    `benchmark/window_lm_flops.py` reads (`window` None: every key)."""
    c, share = config, config["share"]
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    depth = c["num_hidden_layers"]
    assert len(c["sliding_window_layout"]) == len(c["rope_layout"]) == depth, \
        "one entry of each layout a layer"
    attn = lambda i: dict(
        d=d, heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], theta=float(c["rope_theta"]),
        rotary=bool(c["rope_layout"][i]),
        window=c["sliding_window_size"] if c["sliding_window_layout"][i]
        else None)
    moe = dict(d=d, width=c["moe_ffn_hidden_size"],
               routed=share["moe_num_primary_experts"],
               first=share["experts_held"][0], held=share["experts_held"][1],
               k=c["moe_num_active_primary_experts"], shared=0)
    norm = dict(d=d, eps=eps)
    vocab = share["vocab_rows"][1]
    table = [("embed", "embed", dict(vocab=vocab, d=d))]
    for i in range(depth):
        table += [(f"l{i}_op_norm", "rmsnorm", norm),
                  (f"l{i}_attn", "gqa", attn(i)),
                  (f"l{i}_mlp_norm", "rmsnorm", norm),
                  (f"l{i}_moe", "moe", moe)]
    table += [("final_norm", "rmsnorm", norm),
              ("lm_head", "head", dict(d=d, vocab=vocab))]
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
        elif kind == "gqa":
            q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
            shapes[name] = {"q": (d, q), "k": (d, kv), "v": (d, kv),
                            "o": (q, d)}
        elif kind == "moe":
            w = a["width"]
            shapes[name] = {"router": (d, a["routed"]),
                            "experts_gate": (a["held"], d, w),
                            "experts_up": (a["held"], d, w),
                            "experts_down": (a["held"], w, d)}
        elif kind == "head":
            shapes[name] = {"w": (d, a["vocab"])}
    return shapes


def n_params(layers=LAYERS) -> int:
    return sum(int(np.prod(s)) for lp in param_shapes(layers).values()
               for s in lp.values())


def init_params(weights_seed: int, layers=LAYERS, std: float = 0.02,
                embed_std: float = EMBED_STD) -> dict:
    """The benchmark's weights, one jitted call on the device: normal(0,
    `std`) for every matrix but the embedding table (`embed_std`), ones for
    every norm's scale. From the
    configuration's `weights_seed`, NOT from the run's seed: which experts a
    random router favours is a property of the draw (configuration file,
    `assumed`)."""
    shapes = param_shapes(layers)
    embeds = {name for name, kind, _ in layers if kind == "embed"}

    @jax.jit
    def make(key):
        out, i = {}, 0
        for name, lp in shapes.items():
            out[name] = {}
            for pn, sh in lp.items():
                i += 1
                out[name][pn] = (
                    jnp.ones(sh, jnp.float32) if pn == "scale" else
                    (embed_std if name in embeds else std)
                    * jax.random.normal(jax.random.fold_in(key, i), sh,
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


def _softmax_block(qb, kb, vb, qpos, kpos, window, precision):
    """One block of queries against the keys handed to it: the softmax of
    q.k / sqrt(d) over the keys j <= p, and p - j < window where there is a
    window; every query holds itself, so no row is empty."""
    s = _mm("qhd,khd->hqk", qb, kb, precision) / np.sqrt(qb.shape[-1])
    ok = kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok = ok & (qpos[:, None] - kpos[None, :] < window)
    s = jnp.where(ok[None], s, -jnp.inf)
    return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb, precision)


def attention(q, k, v, window, precision, block=ATTN_BLOCK, groups=ATTN_GROUPS):
    """q, k, v [P, heads, d] -> [P, heads, d]: the exact softmax attention
    under the layer's mask, `block` queries at a time, the scores made again
    in the backward pass. Without a window (or under one as long as the
    row) the queries go in `groups` runs, each against the keys up to its
    own end, so most of the masked half of the score square is never
    computed; under a window a block reads the `window` - 1 + `block` keys
    that end with its own last one, and no other."""
    n = q.shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)
    starts = jnp.arange(0, n, block)
    qs = q.reshape((n // block, block) + q.shape[1:])
    if window is not None and window < n:
        span = min(n, window - 1 + block)

        @jax.checkpoint
        def one(start, qb):
            lo = jnp.clip(start + block - span, 0, n - span)
            kb, vb = (lax.dynamic_slice_in_dim(t, lo, span) for t in (k, v))
            return _softmax_block(qb, kb, vb, start + jnp.arange(block),
                                  lo + jnp.arange(span), window, precision)

        o = lax.map(lambda a: one(*a), (starts, qs))
        return o.reshape((n,) + o.shape[2:])

    groups = min(groups, n // block)
    assert n % (block * groups) == 0, (n, block, groups)

    @jax.checkpoint
    def one(start, qb, kb, vb):
        return _softmax_block(qb, kb, vb, start + jnp.arange(block),
                              jnp.arange(kb.shape[0]), None, precision)

    out, run = [], n // groups
    for end in range(run, n + 1, run):
        sel = slice((end - run) // block, end // block)
        o = lax.map(lambda a: one(a[0], a[1], k[:end], v[:end]),
                    (starts[sel], qs[sel]))
        out.append(o.reshape((run,) + o.shape[2:]))
    return jnp.concatenate(out)


def gqa(a, p, x, precision):
    n, h, kv, hd = x.shape[0], a["heads"], a["kv_heads"], a["head_dim"]
    q = _mm("pd,df->pf", x, p["q"], precision).reshape(n, h, hd)
    k = _mm("pd,df->pf", x, p["k"], precision).reshape(n, kv, hd)
    v = _mm("pd,df->pf", x, p["v"], precision).reshape(n, kv, hd)
    if a["rotary"]:
        q, k = rotary(q, a["theta"]), rotary(k, a["theta"])
    # query heads g*(h/kv) .. read key/value head g: every query head its copy
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
    o = attention(q, k, v, a["window"], precision)
    return _mm("pf,fd->pd", o.reshape(n, h * hd), p["o"], precision)


def reglu(x, gate, up, down, precision):
    return _mm("pw,wd->pd", jax.nn.relu(_mm("pd,dw->pw", x, gate, precision))
               * _mm("pd,dw->pw", x, up, precision), down, precision)


def route(a, p, r):
    """(chosen experts [P, k], their weights [P, k]) from the ROUTER'S input
    `r`: float32 always; the softmax is over the chosen logits alone."""
    z = jnp.einsum("pd,de->pe", r, p["router"])
    _, idx = lax.top_k(z, a["k"])
    return idx, jax.nn.softmax(jnp.take_along_axis(z, idx, axis=-1), axis=-1)


def moe(a, p, x, r, precision):
    """This chip's part of the expert layer's result for the experts' input
    `x`, routed on `r`: every held expert over every position, weighted by
    the router's weight where the position chose it and by 0 where it did
    not (four times the products the routed slots need: plain, and exact
    whatever the load), an expert at a time and made again in the backward
    pass. Returns (y, chosen experts)."""
    idx, w = route(a, p, r)
    one = jax.checkpoint(lambda x, w_e, g, u, d: w_e[:, None] * reglu(
        x, g, u, d, precision))
    y = jnp.zeros_like(x)
    for e in range(a["held"]):
        w_e = jnp.sum(jnp.where(idx == a["first"] + e, w, 0.0), axis=-1)
        y = y + one(x, w_e, p["experts_gate"][e], p["experts_up"][e],
                    p["experts_down"][e])
    return y, idx


def _blocks(layers):
    """[(attention norm, attention, expert norm, experts) entries of one
    decoder block], from the table."""
    body = [e for e in layers if e[1] in ("rmsnorm", "gqa", "moe")
            and e[0] != "final_norm"]
    return [body[i:i + 4] for i in range(0, len(body), 4)]


def row_loss(params, ids, *, layers=LAYERS, precision="float32"):
    """One row's (loss, parts): the mean over the positions that have a
    target of CE(next token); parts = the experts every expert layer chose."""
    table = {name: (kind, a) for name, kind, a in layers}
    x = params["embed"]["w"][ids]
    chosen = {}

    def block(x, p_on, p_at, p_mn, p_ml, entries):
        (_, _, a_n), (_, _, a_at), _, (_, _, a_ml) = entries
        n = rmsnorm(x, p_on["scale"], a_n["eps"])
        h = x + gqa(a_at, p_at, n, precision)
        y, idx = moe(a_ml, p_ml, rmsnorm(h, p_mn["scale"], a_n["eps"]), n,
                     precision)
        return h + y, idx

    for entries in _blocks(layers):
        x, idx = jax.checkpoint(functools.partial(block, entries=entries))(
            x, *(params[e[0]] for e in entries))
        chosen[entries[3][0]] = idx

    eps = table["final_norm"][1]["eps"]
    targets = len(ids) - 1          # the last position has none
    run = min(HEAD_BLOCK, len(ids))
    assert len(ids) % run == 0, (len(ids), run)

    @jax.checkpoint
    def ce_sum(h, t, ok, scale, w):
        # a run of positions: the sum of their cross-entropies
        logits = _mm("pd,dv->pv", rmsnorm(h, scale, eps), w, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.where(
            ok, jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0], 0.0))

    nxt = jnp.concatenate([ids[1:], ids[:1]])  # the last one's is masked out
    has = jnp.arange(len(ids)) < targets
    shaped = lambda t: t.reshape((len(ids) // run, run) + t.shape[1:])
    sums = lax.map(lambda a: ce_sum(a[0], a[1], a[2],
                                    params["final_norm"]["scale"],
                                    params["lm_head"]["w"]),
                   (shaped(x), shaped(nxt), shaped(has)))
    return jnp.sum(sums) / targets, {"chosen": chosen}


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
