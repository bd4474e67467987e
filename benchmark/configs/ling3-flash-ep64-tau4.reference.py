"""Plain reference for the `ling3-flash-ep64-tau4` configuration.

Ling-3.0-flash's language model (huggingface.co/inclusionAI/Ling-3.0-flash-VL
config.json; the vision tower and any multi-token-prediction module are left
out: the published keys give neither) as ONE CHIP'S SHARE of a sixty-four-chip
expert-parallel deployment, written out in straightforward `jax.numpy`:
float32, matmul precision `highest`, no kernels, no chunks. It imports
nothing of the program and takes nothing the program made: the benchmark
makes the weights (`init_params`, from the configuration's `weights_seed`)
and the token ids, and hands both sides the same.

The model, per row of token ids t_0 .. t_{P-1} (x is [P, d], d = 2560; RMSNorm
eps `rms_norm_eps`; no biases; pre-norm residual blocks):

  x = E[t]                                   E the held vocabulary rows
  per layer:  h = x + Op(RMSNorm(x));  x' = h + FF(RMSNorm(h))
    Op = KDA (Kimi Delta Attention, arXiv:2510.26692) where the PUBLISHED
         layer index j has (j + 1) % layer_group_size != 0; u the normed x:
          q, k, v = SiLU(conv4(u W_q)), SiLU(conv4(u W_k)), SiLU(conv4(u W_v))
            32 heads x 128 each; conv4 depthwise, causal, 4 taps a channel,
            zeros before position 0, no bias
          q_h <- q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(128);  k_h likewise, x 1
          g_h = -5 sigmoid(exp(A_h) (u W_a + b)_h)   a vector of 128 a head
          beta_h = sigmoid(u w_beta,h)               a scalar a head
          S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T q_t          S [128, 128] a head, S_0 = 0: THE RECURRENCE,
                                   A POSITION AT A TIME (`delta_recurrence`)
          out = concat_h[sigmoid(u w_r,h) RMSNorm_h(o_h)] W_o   (one scale of
                                   128 shared by the heads)
    Op = latent attention where (j + 1) % layer_group_size == 0:
          q = u W_q directly -> 32 heads x (128 + 64), no latent, no norm;
          c = RMSNorm(u W_kv_a[:, :512]); [k_nope | v] = c W_kv_b; one rotary
          key u W_kv_a[:, 512:] shared by the heads; rotary over the 64 last
          dims, theta 6e6, pairs (x[2i], x[2i+1]); scores q.k / sqrt(192),
          causal softmax; out = concat_h[sigmoid(u w_r,h) o_h] W_o
    FF    leading dense layers: (silu(x W_g) * x W_u) W_d, width 6,144
          expert layers: s = sigmoid(x W_r) over ALL 512 published experts
          (float32); the 512 in 8 groups of 64; a group's score the sum of
          its two largest entries of s + b (b a buffer, neither trained nor
          decayed); the 4 best groups kept; chosen = the 8 largest entries
          of s + b among their 256; w = s[chosen] / (sum s[chosen] + 1e-20)
          x 2.5; y = sum over the chosen experts THIS CHIP HOLDS of
          w_e SwiGLU_e(x) + SwiGLU_shared(x), both of width 768. What the
          absent experts would add is left out, as in the program.
  logits = RMSNorm(x_last) W_head            untied
  L = mean_i CE(logits_i, t_{i+1})

Left out here and in the program alike (`changed_from_source` in the
configuration file): the vision tower and its patch tokens, any
multi-token-prediction module, the balance update of b, dropout, document
masks (one document a row), the swiglu clamp (its limit is 0, off, at every
layer held). What the published keys do not settle is `assumed` there.

To fit a chip at the published widths the gradient is taken one row at a
time and summed, every block is recomputed in the backward pass
(`jax.checkpoint`), the attention scores are made one block of queries at a
time and the recurrence goes in checkpointed blocks of positions (8,192
states of a layer would be 17 GB); none of that changes a number beyond
float32's summation order.

`precision` other than "float32" is the CONTROL (see `LIMITS`): the same
mathematics with both operands of every matmul (the router's excepted: it is
float32 on both sides by the model's own rule) and the cotangent of its
output rounded per tensor to fp8 e4m3, the step below the configuration's
bfloat16; the rule's operands q, k and v are rounded so where they enter the
recurrence and its result's cotangent where it leaves. The decays, the
state, the taps and the gates are no matmul and stay float32.
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
with open(os.path.join(HERE, "ling3-flash-ep64-tau4.json")) as _f:
    CONFIG = json.load(_f)

#: queries a block in the attention core (scores [heads, block, P] float32)
ATTN_BLOCK = 512
#: runs the queries go in, each against the keys up to its end (4: 62.5 % of
#: the score square is computed; more runs compile longer)
ATTN_GROUPS = 4
#: positions a checkpointed block of the recurrence: the backward pass keeps
#: one state a block and makes a block's states again
DELTA_BLOCK = 128

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`, the full rate) against
#: `round_reference`. Each limit stands with the v5e readings it was set from
#: (PR 33's chip runs at the cell's own size: eight seeds through
#: `benchmark/token_control.py`, 3300000041-48, and every benchmark run;
#: PERF.md section 2 repeats them): "sound" is the program over those (the
#: weights are the configuration's, so most readings hardly move), "control"
#: the fp8 control over two seeds.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum: the number
    # that tells the precisions apart, and the one limit the lower-precision
    # control has to fail. Sound 0.0358-0.0368, control 0.2377 on both
    # seeds: 2.4 times the one, 0.38 of the other.
    "probe_diff": 0.09,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round, and the same over the parameters'
    # change across the round. Most seeds read 0.0009-0.0018 on both, but
    # the worst leaf is a small one -- the first delta-rule layer's output
    # norm or `A_log`, an expert's gate -- and one seed in ten read 0.0061
    # (change) and 0.0043 (momentum) on `l0_kda/o_norm`; the control reads
    # 0.0169-0.0202 (change) and 0.0061-0.0123 (momentum): 2.8 and 1.4 times
    # the largest sound reading. A limit between the two would refuse a
    # sound run on a fresh seed, so both stand where the hybrid cell's do,
    # between the sound readings and what a broken round reads (a round that
    # returns its state unchanged 1.0; the decay left out or a tap dropped
    # 0.05-0.2 at a test's size), 3.3 and 4.6 times the largest sound
    # reading; `probe_diff` is the limit that holds the precision.
    "momentum_gap": 0.02,
    "update_gap": 0.02,
    # |program round loss - reference round loss|. Precision hardly moves it:
    # sound 7e-6-2.6e-4, control 1.9e-4-7.7e-4 over four readings, so no
    # limit lies between the two; the hybrid cell's accepted 1e-3 leaves the
    # largest sound reading 3.8 times of room (the other sequence cell's
    # 3.5e-4 would leave it 1.3). It guards the loss's own arithmetic (the
    # mean over the positions that have a target, the norm before the head,
    # the head's matrix), not the precision.
    "loss_gap": 1.0e-3,
    # the worst expert layer's share of routed slots whose expert differs
    # between the program's forward pass (bf16 stream) and this file's
    # (float32), the router float32 on both sides: sound 0.0293-0.0305 (the
    # last expert layer, always; it grows with depth from the first's
    # 0.019). No control reads it; three times the sound reading, held
    # against a router that reads a coarser stream, another bias, or other
    # groups than the model's.
    "routing_diff_share": 0.09,
}
#: the step below the configuration's bfloat16
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the leading dense
#: layer's down projection. Its gradient carries the backward pass through
#: the six blocks above it (five delta-rule scans, the latent attention, six
#: expert layers) and the head.
PROBE_LEAF = ("l0_mlp", "down")


# -- the layer table ---------------------------------------------------------

def layer_table(config: dict) -> tuple:
    """(name, kind, args) of every layer of the table, in execution order;
    the names are the program's (`zoo.ling3_flash`). Kinds `mla`, `mlp`,
    `moe` and `head` carry the keys `benchmark/lm_flops.py` reads; `kda`
    those `benchmark/linear_lm_flops.py` reads. Layer i here is published
    layer `first_layer` + i."""
    c, share = config, config["share"]
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    heads = c["num_attention_heads"]
    attn = dict(d=d, heads=heads, q_rank=0, kv_rank=c["kv_lora_rank"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                v=c["v_head_dim"], theta=float(c["rope_theta"]), eps=eps)
    linear = dict(d=d, heads=heads, head_dim=c["head_dim"],
                  taps=c["short_conv_kernel_size"],
                  lower=float(c["kda_lower_bound"]), eps=eps)
    moe = dict(d=d, width=c["moe_intermediate_size"],
               routed=share["num_experts"],
               first=share["experts_held"][0], held=share["experts_held"][1],
               k=c["num_experts_per_tok"],
               shared=c["moe_shared_expert_intermediate_size"]
               // c["moe_intermediate_size"],
               scale=c["routed_scaling_factor"], norm=c["norm_topk_prob"],
               groups=c["n_group"], groups_kept=c["topk_group"])
    norm = dict(d=d, eps=eps)
    vocab = share["vocab_rows"][1]
    table = [("embed", "embed", dict(vocab=vocab, d=d))]
    for i in range(c["num_hidden_layers"]):
        latent = (share.get("first_layer", 0) + i + 1) % c["layer_group_size"] == 0
        table += [(f"l{i}_op_norm", "rmsnorm", norm),
                  (f"l{i}_attn", "mla", attn) if latent
                  else (f"l{i}_kda", "kda", linear),
                  (f"l{i}_mlp_norm", "rmsnorm", norm)]
        table.append((f"l{i}_mlp", "mlp", dict(d=d, width=c["intermediate_size"]))
                     if i < c["first_k_dense_replace"]
                     else (f"l{i}_moe", "moe", moe))
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
        elif kind == "head":
            shapes[name] = {"w": (d, a["vocab"])}
        elif kind == "rmsnorm":
            shapes[name] = {"scale": (d,)}
        elif kind == "kda":
            h, f = a["heads"], a["heads"] * a["head_dim"]
            shapes[name] = {
                "q": (d, f), "k": (d, f), "v": (d, f),
                "q_conv": (f, a["taps"]), "k_conv": (f, a["taps"]),
                "v_conv": (f, a["taps"]), "a": (d, f), "dt_bias": (f,),
                "A_log": (h,), "beta": (d, h), "out_gate": (d, h),
                "o_norm": (a["head_dim"],), "o": (f, d)}
        elif kind == "mla":
            h = a["heads"]
            shapes[name] = {
                "q": (d, h * (a["nope"] + a["rope"])),
                "kv_a": (d, a["kv_rank"] + a["rope"]),
                "kv_a_norm": (a["kv_rank"],),
                "kv_b": (a["kv_rank"], h * (a["nope"] + a["v"])),
                "out_gate": (d, h), "o": (h * a["v"], d)}
        elif kind == "mlp":
            shapes[name] = {"gate": (d, a["width"]), "up": (d, a["width"]),
                            "down": (a["width"], d)}
        elif kind == "moe":
            w, sw = a["width"], a["width"] * a["shared"]
            shapes[name] = {"router": (d, a["routed"]),
                            "router_bias": (a["routed"],),
                            "experts_gate": (a["held"], d, w),
                            "experts_up": (a["held"], d, w),
                            "experts_down": (a["held"], w, d)}
            if a["shared"]:
                shapes[name].update(shared_gate=(d, sw), shared_up=(d, sw),
                                    shared_down=(sw, d))
    return shapes


def n_params(layers=LAYERS) -> int:
    return sum(int(np.prod(s)) for lp in param_shapes(layers).values()
               for s in lp.values())


def init_params(weights_seed: int, layers=LAYERS, std: float = 0.02) -> dict:
    """The benchmark's weights, one jitted call on the device: normal(0,
    `std`) for every matrix, for the convolutions' taps, for the decay's bias
    `dt_bias` and for the router's selection bias; zeros for `A_log`; ones
    for every norm's scale. From the configuration's `weights_seed`, NOT from
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
                    jnp.zeros(sh, jnp.float32) if pn == "A_log" else
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
    causal softmax of q.k / sqrt(dk), `block` queries at a time, the scores
    made again in the backward pass. The queries go in `groups` runs, each
    against the keys up to its own end, so most of the masked half of the
    score square is never computed."""
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


def head_gate(x, w_gate, precision):
    """sigmoid(x w_h): one scalar a head and position, [P, heads, 1]."""
    return jax.nn.sigmoid(_mm("pd,dh->ph", x, w_gate, precision))[:, :, None]


def mla(a, p, x, precision):
    n, h, nope = x.shape[0], a["heads"], a["nope"]
    q = _mm("pd,df->pf", x, p["q"], precision).reshape(n, h, nope + a["rope"])
    kv_a = _mm("pd,dr->pr", x, p["kv_a"], precision)
    c_kv = rmsnorm(kv_a[:, :a["kv_rank"]], p["kv_a_norm"], a["eps"])
    kv = _mm("pr,rf->pf", c_kv, p["kv_b"], precision).reshape(n, h, nope + a["v"])
    k_rope = rotary(kv_a[:, a["kv_rank"]:], a["theta"])          # [P, rope]
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], a["theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope[:, None, :], (n, h, a["rope"]))], -1)
    o = causal_attention(q, k, kv[..., nope:], precision) \
        * head_gate(x, p["out_gate"], precision)
    return _mm("pf,fd->pd", o.reshape(n, h * a["v"]), p["o"], precision)


def delta_recurrence(q, k, v, g, beta, block=DELTA_BLOCK):
    """o [P, heads, dv] of the gated delta rule, A POSITION AT A TIME: q, k
    [P, heads, dk], v [P, heads, dv], g [P, heads, dk] the log-decay, beta
    [P, heads]; S [heads, dk, dv] starts at zero. The positions go in
    checkpointed blocks: the backward pass keeps one state a block."""
    n, h, dk = q.shape
    block = min(block, n)
    assert n % block == 0, (n, block)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    run = jax.checkpoint(lambda s, xs: lax.scan(step, s, xs))
    blocks = tuple(t.reshape((n // block, block) + t.shape[1:])
                   for t in (q, k, v, g, beta))
    _, o = lax.scan(run, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), blocks)
    return o.reshape((n,) + o.shape[2:])


def kda(a, p, x, precision):
    n, h, hd, taps = x.shape[0], a["heads"], a["head_dim"], a["taps"]

    def branch(name):
        y = _mm("pd,df->pf", x, p[name], precision)
        # zeros before position 0: s[t + taps - 1] is position t's projection
        s = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1]), y.dtype), y])
        conv = sum(p[name + "_conv"][:, j] * s[j:j + n] for j in range(taps))
        return jax.nn.silu(conv).reshape(n, h, hd)

    q, k, v = branch("q"), branch("k"), branch("v")
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / np.sqrt(hd), unit(k)
    pre = (_mm("pd,df->pf", x, p["a"], precision) + p["dt_bias"]).reshape(n, h, hd)
    g = a["lower"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[None, :, None] * pre)
    beta = jax.nn.sigmoid(_mm("pd,dh->ph", x, p["beta"], precision))
    o = _round_grad(delta_recurrence(
        _round_to(q, precision), _round_to(k, precision),
        _round_to(v, precision), g, beta), precision)
    o = rmsnorm(o, p["o_norm"], a["eps"]) * head_gate(x, p["out_gate"], precision)
    return _mm("pf,fd->pd", o.reshape(n, h * hd), p["o"], precision)


def swiglu(x, gate, up, down, precision):
    return _mm("pw,wd->pd", jax.nn.silu(_mm("pd,dw->pw", x, gate, precision))
               * _mm("pd,dw->pw", x, up, precision), down, precision)


def route(a, p, x):
    """(chosen experts [P, k], their weights [P, k]): float32 always. The
    choice is group-limited: the routed experts in `groups` equal groups, a
    group's score the sum of its two largest entries of score + bias, the
    `groups_kept` best groups kept (ties to the lower index), the top k
    taken among the entries of those groups."""
    s = jax.nn.sigmoid(jnp.einsum("pd,de->pe", x, p["router"]))
    choice = s + lax.stop_gradient(p["router_bias"])
    groups, size = a["groups"], a["routed"] // a["groups"]
    if groups > 1:
        of_group = [choice[:, i * size:(i + 1) * size] for i in range(groups)]
        score = jnp.stack([jnp.sum(jnp.sort(c, axis=-1)[:, -2:], axis=-1)
                           for c in of_group], axis=-1)          # [P, groups]
        _, best = lax.top_k(score, a["groups_kept"])
        choice = jnp.concatenate(
            [jnp.where(jnp.any(best == i, axis=-1)[:, None], c, -jnp.inf)
             for i, c in enumerate(of_group)], axis=-1)
    _, idx = lax.top_k(choice, a["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if a["norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * a["scale"]


def moe(a, p, x, precision):
    """This chip's part of the expert layer's result, the shared expert with
    it: every held expert over every position, weighted by the router's
    weight where the position chose it and by 0 where it did not (sixty-four
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


OPS = {"kda": kda, "mla": mla}


def _blocks(layers):
    """[(operator norm, operator, mlp norm, mlp) entries of one decoder
    block], from the table."""
    body = [e for e in layers if e[1] in ("rmsnorm", "kda", "mla", "mlp", "moe")
            and e[0] != "final_norm"]
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

    def ce(h, scale, w, eps):
        logits = _mm("pd,dv->pv", rmsnorm(h, scale, eps), w, precision)
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))

    eps = table["final_norm"][1]["eps"]
    loss = jax.checkpoint(lambda h, s, w: ce(h, s, w, eps))(
        x, params["final_norm"]["scale"], params["lm_head"]["w"])
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
    worker w's ids of step t. One worker (the deployment's sixty-four chips are
    ONE tau-averaging worker, and this is one chip's share of it), so the
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
