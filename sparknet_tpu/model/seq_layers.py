"""The sequence-model layer set: what a decoder, sparse-expert or dense, is
built from.

Embed, RMSNorm (its scale w, or 1 + w), three kinds of softmax attention --
MLAttention (multi-head latent attention, its queries through a latent or
projected directly), GQAttention (grouped-query attention with or without
per-head q/k norms and a rotary turn, over every key or over a sliding
window of a query's last keys) and EVAttention (EVA: an exact causal window beside learned summaries
of the chunks before it, under one softmax; `ops.eva`) -- KDAttention
(a linear attention: one matrix state a head, updated by the gated delta
rule, `ops.delta_rule`), Mamba2 (a state-space mixer: one matrix state a
head under a scalar decay, `ops.ssd`, of whose heads this chip may hold a
share, as GQAttention may of its own), ShortConv (a gated short convolution:
the operator a hybrid decoder sets between its attention layers), GatedMLP
(SwiGLU), MoE (routed experts of which this chip holds a share, SwiGLU,
ReGLU or relu^2, in the stream's width or in a latent narrower than it, with
or without a shared one, chosen among all or among the best groups by sigmoid
scores or by a softmax over the chosen logits, the router fed the experts'
input or a tensor of its own), MTP (a
multi-token-prediction module) and Eltwise (the residual sum, in float32
where the model carries its stream so). Same three
functions a layer type as `layers.py` (`init_`, `apply_`, `infer_`);
registered there in `LAYER_IMPLS`. Activations are `[rows, positions, d]`;
matrices are stored (in, out) in float32 and cast by the precision policy at
use. (A tied head is `layers.py`'s InnerProduct, `transposed`, on the
embedding's own table.)

Every layer's parameters carry names of their own (`q_a`, `kv_a_norm`,
`q_norm`, `conv`, `router_bias`, ...): `param_defaults` gives the per-name
lr/decay multipliers the solver applies where the spec gives none.

The two places where a plain formulation would not fit a chip at 8k positions
go through kernels jax ships: the attention core through
splash attention (its backward keeps nothing of size positions^2), the
experts' products through megablox's grouped matmul (only the rows routed to
an expert meet its weights). Off the chip, at test sizes and under
`ops_interpret`, both take the exact path: `ops.attention.attention` and
`lax.ragged_dot`.

What the attention core reads is laid out where that is free, on the weights
(`mla`, `gqa`): views of the stored matrices whose products come out heads
first, rotary columns half-split, q scaled, so no activation is sliced at a
stride, transposed or scaled between a projection and the core. Both kinds
of attention hand the one `attention_core` the kernel's own operands; where
the key/value heads are fewer than the query heads the kernel does the
grouping. The core is causal over as many keys as queries unless its layer
hands it a mask: a value the layer derives from its own parameters (never a
run's option), a function of (query positions, key columns) the kernel
works out tile by tile, skipping the tiles it empties; the key columns may
then be more than the queries (`eva`: a row's keys and one summary a chunk
behind them; `gqa` under a `window`: as many keys as queries, a query's last
`window` of them).

A layer may name a value that is dear to compute again and cheap to keep
(`KEPT_NAMES`, by layer type): the recomputation block such a layer stands in
(`LayerSpec.block`, `CompiledNet.apply`) keeps the named values for the
backward pass beside the block's inputs and computes the rest again. The
attention core names its output and, on the kernel path, its softmax
statistics (the row-wise log-sum-exp): all the kernel's backward needs
besides q, k and v, so its forward kernel runs once a step. The dense
SwiGLU (`GatedMLP`) names its two input products `x W_gate` and `x W_up`
(bf16 under the bf16 policy: 360.7 MB each a layer-step at EvaByte's
16,384 x 11,008), so its block's backward makes no product twice; the
gated `h` between them and the down projection is one elementwise pass
from the two and is made again, and the expert layer's shared expert, which
runs the same arithmetic at a fraction of the width inside a block whose
memory the routed experts decide, names nothing. In training the layer also
sets its input behind an `optimization_barrier`, so that the norm before it
is a value the backward pass reads and not one its products make again
(`apply_gatedmlp`). An `InnerProduct` that stands in a block (a head, with
its norm and loss) names its result, the logits: its block makes the
model's largest product once a step (`layers.apply_innerproduct`). An
expert layer (`moe`) names what its backward pass reads of its routing --
the chosen experts, their raw scores, the plan's index arrays -- a few MB a
layer-step: its block makes no score product, sort or select again.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import precision
from ..ops import attention as attention_ops
from ..ops import delta_rule, eva as eva_ops, kda_shape, ssd as ssd_ops
from .spec import (EVAttentionParam, GQAttentionParam, KDAttentionParam,
                   LayerSpec, MLAttentionParam, Mamba2Param, MoEParam,
                   ParamSpec)

Params = Dict[str, jnp.ndarray]

#: splash attention's tiles (queries, keys a block, keys a product; positions
#: must divide by the largest) and the grouped matmul's (rows, contraction,
#: columns), one set for every layer that runs them. Chosen on a v5e as the
#: fastest of those tried at latent attention's [2, 20, 8192, 256] and the
#: experts' [65536 x 2048] x [8, 2048, 1536] (PERF.md section 6, PR 27), and
#: read again at grouped-query attention's [2, 32 q / 8 kv, 8192, 64] and
#: [32768 x 2048] x [8, 2048, 1792] (PR 31, forward + backward): the core
#: 34.6 ms at these blocks with the 64-wide head as it is and 35.5 zero-padded
#: to a lane row (32.3 at (1024, 1024, 512), which latent attention has not
#: been read at); the three grouped products 14.1 ms at these tiles, which
#: overhang 1,792 (megablox masks the overhang), against 19.7 at tiles of 256
#: that divide it, 40.9 at 128 and 12.2 at (512, 1024, 1024). Under a mask the
#: key columns need not be as many as the queries, but both must be whole
#: tiles of the largest (EVA's 16,384 + 1,024 columns are 17 tiles of keys);
#: a key length that is no multiple of it takes the exact path
ATTN_BLOCKS = (512, 1024, 512)
GMM_TILING = (512, 512, 512)
#: what adding one buffer row into its token's float32 row costs the chip, in
#: rows fetched by a gather: the expert layer's weighted sum by token walks
#: the buffer (a scatter-add of its rows) where SCATTER_ROW_COST x rows <
#: k x tokens, and fetches every token's row k times elsewhere
#: (`sum_walks_buffer`). Read on a v5e with the lone layer at the four
#: sequence cells' shapes (16,384 tokens, forward + backward in a
#: recomputation block, bf16, ms a call on the device, gathers | buffer;
#: PERF.md section 6, PR 43): k x tokens / rows = 16 (Nemotron-3-Super, k = 22
#: in a latent of 1,024) 52.15 | 44.70; 4 (GLM-4.7-Flash, 2,048 wide) 25.26 |
#: 25.03 and 2 (LFM2-8B-A1B) 31.16 | 31.27, both ties; 32 (Ling-3.0-flash, k =
#: 8, 2,560 wide) 26.51 | 25.01. So 4 keeps the buffer form to where it wins.
#: SCATTER_COLUMNS: the scatter-add runs a slab of that many columns at a
#: time. The TPU compiler's scatter (it sorts the indices, fetches the float32
#: updates in that order and adds them sorted) falls off a cliff at whole rows
#: of 2,560 floats: 4,096 rows into [16384, 2560] alone take 7.09 ms whole,
#: 1.28 in slabs of 1,280, 0.79 in slabs of 512 (2,048 wide: 1.44 whole), and
#: Ling's layer 36.61 | 26.01 | 25.01 (24.72 at 128); Nemotron's 1,024-wide
#: rows lose a little to it (44.70 whole, 45.28 at 512, 46.68 at 128)
SCATTER_ROW_COST = 4
SCATTER_COLUMNS = 512
#: the counters an expert layer returns beside its result, in this order
MOE_COUNTERS = ("slots_landed", "slots_dropped", "expert_tokens_max",
                "expert_tokens_min")
#: the counter a Mamba-2 mixer returns beside its result where its rows hold
#: several documents: the boundaries a step, as the scan's and the taps' cut
#: saw them (the positions whose document differs from the one before's)
SSD_COUNTERS = ("doc_boundaries",)
#: the name (`jax.ad_checkpoint.checkpoint_name`) on the attention core's
#: output and softmax statistics, that on a Kimi Delta Attention layer's
#: result, and that on the dense SwiGLU's two input products `x W_gate` and
#: `x W_up` (also the named scope the two run under: how a compiled
#: program's text tells them from the layer's third product)
ATTN_CORE = "attn_core"
KDA_OUT = "kda_out"
MLP_PRE = "mlp_pre"
#: that on the result of an `InnerProduct` that stands in a recomputation
#: block (`layers.apply_innerproduct`: a head's logits), and the named scope
#: its product runs under there
IP_OUT = "ip_out"
#: that on what an expert layer's backward pass reads of its routing: the
#: chosen experts, their raw scores (`route`), the plan's index arrays that
#: its weighted sums read and the grouped products' group sizes (`moe`)
MOE_ROUTE = "moe_route"


def param_defaults(pname: str) -> ParamSpec:
    """lr_mult / decay_mult of a parameter the spec says nothing about, by
    its name: norms' scales are not decayed; the router's selection bias is
    a buffer the gradient does not train (and is not decayed)."""
    if pname == "router_bias":
        return ParamSpec(lr_mult=0.0, decay_mult=0.0)
    if pname.endswith("norm") or pname == "scale":
        return ParamSpec(lr_mult=1.0, decay_mult=0.0)
    return ParamSpec()


def use_kernels(ctx) -> bool:
    return not ctx.interpret and jax.default_backend() == "tpu"


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _dot(x, w):
    """x [..., k] @ w [k, n] under the precision policy."""
    return jnp.dot(precision.cast_in(x), precision.cast_in(w),
                   precision=precision.matmul_precision(),
                   preferred_element_type=precision.preferred_out())


def _rms(x, scale, eps):
    """RMSNorm in float32, returned in the compute dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(precision.compute_dtype())


def _gated(g, u, w_down):
    """silu(g) u W_down: the product in float32, `h` in g's dtype."""
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return _dot(h.astype(g.dtype), w_down)


def _swiglu(x, w_gate, w_up, w_down):
    return _gated(_dot(x, w_gate), _dot(x, w_up), w_down)


def _relu2(u):
    """relu(u)^2: the square in float32, the result in u's dtype."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(u.dtype)


def _relu2_mlp(x, w_up, w_down):
    return _dot(_relu2(_dot(x, w_up)), w_down)


# -- Embed -------------------------------------------------------------------

def infer_embed(layer: LayerSpec, in_shapes):
    return (tuple(in_shapes[0]) + (layer.embed.dim,),)


def init_embed(key, layer: LayerSpec, in_shapes) -> Params:
    p = layer.embed
    return {"w": _normal(key, (p.num_embeddings, p.dim), p.std)}


def shifted(ids, k: int, fill=0):
    """ids[:, i + k] at position i, `fill` where the row has ended."""
    if k == 0:
        return ids
    return jnp.concatenate(
        [ids[:, k:], jnp.full((ids.shape[0], k), fill, ids.dtype)], axis=1)


def apply_embed(layer: LayerSpec, params: Params, inputs, ctx):
    (ids,) = inputs
    ids = shifted(ids.astype(jnp.int32), layer.embed.shift)
    x = jnp.take(params["w"], ids, axis=0)
    if layer.embed.multiplier != 1.0:
        x = x * layer.embed.multiplier
    return (precision.cast_in(x),)


# -- RMSNorm -----------------------------------------------------------------

def infer_same(layer: LayerSpec, in_shapes):
    return (in_shapes[0],)


def init_rmsnorm(key, layer: LayerSpec, in_shapes) -> Params:
    fill = jnp.zeros if layer.rmsnorm.unit_offset else jnp.ones
    return {"scale": fill((in_shapes[0][-1],), jnp.float32)}


def apply_rmsnorm(layer: LayerSpec, params: Params, inputs, ctx):
    p, scale = layer.rmsnorm, params["scale"]
    return (_rms(inputs[0], 1.0 + scale if p.unit_offset else scale, p.eps),)


# -- Eltwise -----------------------------------------------------------------

def apply_eltwise(layer: LayerSpec, params, inputs, ctx):
    """Caffe's Eltwise SUM, with its `coeff`s: the residual add (in
    float32, and left so, where the layer says `float32`), and the sum of
    the weighted losses."""
    p = layer.eltwise
    if p is not None and p.operation != "SUM":
        raise ValueError(f"layer {layer.name!r}: Eltwise operation "
                         f"{p.operation!r} is not built (SUM is)")
    if p is not None and p.float32:
        inputs = tuple(x.astype(jnp.float32) for x in inputs)
    coeff = p.coeff if p and p.coeff else (1.0,) * len(inputs)
    assert len(coeff) == len(inputs), (layer.name, coeff)
    return (sum(x if c == 1.0 else c * x for c, x in zip(coeff, inputs)),)


# -- GatedMLP ----------------------------------------------------------------

def init_gatedmlp(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.gated_mlp, in_shapes[0][-1]
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": _normal(kg, (d, p.intermediate_size), p.std),
            "up": _normal(ku, (d, p.intermediate_size), p.std),
            "down": _normal(kd, (p.intermediate_size, d), p.std)}


def apply_gatedmlp(layer: LayerSpec, params: Params, inputs, ctx):
    # `_swiglu` with its two input products named (KEPT_NAMES): the block
    # keeps g and u, and makes h = silu(g) u again from them, elementwise.
    # In training the layer's input is pinned as a value of its own: a block
    # that makes neither product again has two readers of the norm before
    # it left in its backward pass, the weight gradients' products, and the
    # TPU compiler then makes the norm again INSIDE each of them, from the
    # float32 stream, tile by tile (+10.6 ms a layer-step at EvaByte's
    # shape, over half of what the kept products save), and fuses the
    # norm's own backward into the product that makes its cotangent (+4.7).
    # Behind the barrier the norm's result is written once, 134 MB, and
    # read (PERF.md section 6, PR 41: round 3,677 -> 3,434 ms)
    x = lax.optimization_barrier(inputs[0]) if ctx.train else inputs[0]
    with jax.named_scope(MLP_PRE):
        g, u = (checkpoint_name(_dot(x, params[w]), MLP_PRE)
                for w in ("gate", "up"))
    return (_gated(g, u, params["down"]),)


# -- MLAttention -------------------------------------------------------------

def init_mla(key, p: MLAttentionParam, d: int) -> Params:
    # (a layer without the gate draws the five keys it always drew)
    ks = jax.random.split(key, 6 if p.output_gate else 5)
    qk = p.qk_nope_head_dim + p.qk_rope_head_dim
    queries = {
        "q_a": _normal(ks[0], (d, p.q_lora_rank), p.std),
        "q_a_norm": jnp.ones((p.q_lora_rank,), jnp.float32),
        "q_b": _normal(ks[1], (p.q_lora_rank, p.num_heads * qk), p.std),
    } if p.q_lora_rank else {"q": _normal(ks[0], (d, p.num_heads * qk), p.std)}
    gate = {"out_gate": _normal(ks[5], (d, p.num_heads), p.std)} \
        if p.output_gate else {}
    return {
        **queries,
        "kv_a": _normal(ks[2], (d, p.kv_lora_rank + p.qk_rope_head_dim), p.std),
        "kv_a_norm": jnp.ones((p.kv_lora_rank,), jnp.float32),
        "kv_b": _normal(ks[3], (p.kv_lora_rank, p.num_heads * (
            p.qk_nope_head_dim + p.v_head_dim)), p.std),
        **gate,
        "o": _normal(ks[4], (p.num_heads * p.v_head_dim, d), p.std)}


def init_mlattention(key, layer: LayerSpec, in_shapes) -> Params:
    return init_mla(key, layer.mla, in_shapes[0][-1])


def half_split(w):
    """The last axis's interleaved pairs (w[2i], w[2i+1]) laid out evens
    first, then odds: a static permutation, applied to a weight's columns
    so that no activation is ever sliced at a stride."""
    d = w.shape[-1]
    return jnp.swapaxes(w.reshape(w.shape[:-1] + (d // 2, 2)),
                        -1, -2).reshape(w.shape)


def rotary(x, theta: float, rope: int):
    """Rotary position embedding over the last `rope` lanes of x
    [..., positions, d], laid out half-split: pair i is (x[d - rope + i],
    x[d - rope/2 + i]), turned by position * theta^(-2i/rope), and stays
    where it was; the lanes before pass. The public DeepSeek-V3-family code
    pairs (x[2i], x[2i+1]): `half_split` of the columns of the weight that
    makes x turns the one into the other, and no dot product of two vectors
    permuted alike can tell. Written over the whole width, x * c + (x with
    the halves exchanged) * s, contiguous slices alone: no gather forward,
    no scatter backward, and on a v5e 0.8 ms a block-step faster than
    slice, turn, concatenate (PERF.md section 6, PR 30)."""
    d, n, half = x.shape[-1], x.shape[-2], rope // 2
    inv = 1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
    ang = jnp.asarray(np.arange(n)[:, None] * inv[None, :], jnp.float32)
    cos, sin, one = jnp.cos(ang), jnp.sin(ang), jnp.ones((n, d - rope))
    c = jnp.concatenate([one, cos, cos], axis=-1)
    s = jnp.concatenate([jnp.zeros_like(one), -sin, sin], axis=-1)
    exchanged = jnp.concatenate([x[..., :d - rope], x[..., d - half:],
                                 x[..., d - rope:d - half]], axis=-1)
    return (x.astype(jnp.float32) * c
            + exchanged.astype(jnp.float32) * s).astype(x.dtype)


@functools.lru_cache(maxsize=8)
def _splash(heads: int, positions: int, mask=None, interpret: bool = False):
    """The kernel for `heads` heads of `positions` queries: causal over as
    many keys (`mask` None), or under a mask of its own -- a hashable
    function of (query positions, key columns) with a `shape` (queries, key
    columns), `ops.eva.WindowSummaryMask` -- which the kernel works out
    tile by tile from the positions, reading no table, and whose empty tiles
    it never visits."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    bq, bkv, bkv_compute = ATTN_BLOCKS
    sizes = sk.BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv_compute,
                          block_q_dkv=bq, block_kv_dkv=bkv,
                          block_kv_dkv_compute=bkv_compute,
                          use_fused_bwd_kernel=True)  # dq with dk, dv: one pass
    if mask is None:
        one = sm.CausalMask((positions, positions))
    else:
        class Computed(sm._ComputableMask):
            __eq__ = lambda a, b: (isinstance(b, Computed)
                                   and a.mask_function == b.mask_function)
            __hash__ = lambda a: hash(a.mask_function)
        one = Computed(mask.shape, mask)
    with jax.ensure_compile_time_eval():  # the mask tables are constants
        # the kernel names its output and log-sum-exp itself
        return sk.make_splash_mha(sm.MultiHeadMask([one] * heads),
                                  block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1,
                                  residual_checkpoint_name=ATTN_CORE,
                                  interpret=interpret)


def _blocks_visited(heads: int, positions: int, mask=None) -> int:
    """The key blocks the forward kernel's tables send it to, all query
    blocks together (the kernel is built once a shape and mask: `_splash`)."""
    return int(np.count_nonzero(np.asarray(
        _splash(heads, positions, mask).fwd_mask_info.block_mask)))


def attention_core(q, k, v, ctx, mask=None, docs=None):
    """Causal softmax(q k^T) v over q [rows, heads, positions, d] and k, v
    [rows, key/value heads, positions, d], heads first as the kernel reads
    and writes them; q comes scaled (its layer folds 1/sqrt(d) into a
    weight). Where the key/value heads are fewer, query heads g*n ..
    g*n + n - 1 read key/value head g (the kernel's own grouping). With a
    `mask` (`_splash`: a value the layer derives from its own parameters)
    k and v hold `mask.shape[1]` key columns, more or fewer than the
    queries, and a query reads those the mask grants it. The kernel where
    it applies (queries and key columns whole tiles, head sizes a half or
    whole lane rows); else the exact path, which materialises the
    scores. With `docs` (the rows' document ids [rows, positions], as many
    keys as queries) a query reads the keys of its own document alone: the
    kernel is handed them as its segment ids and masks inside the tiles its
    tables send it to -- the tiles it visits are the mask's, wherever the
    documents fall --; the exact path takes them as a bias."""
    n, group = q.shape[2], q.shape[1] // k.shape[1]
    if (use_kernels(ctx) and n % max(ATTN_BLOCKS) == 0
            and k.shape[2] % max(ATTN_BLOCKS) == 0
            and q.shape[-1] % 64 == 0 and v.shape[-1] % 64 == 0
            and q.dtype == jnp.bfloat16):
        kernel = jax.vmap(_splash(q.shape[1], n, mask))
        if docs is None:
            return kernel(q, k, v)
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk)
        return kernel(q, k, v, sk.SegmentIds(q=docs, kv=docs))
    # the exact path's positions-first, every query head with its own copy
    swap = lambda x: jnp.swapaxes(x, 1, 2)
    spread = lambda x: swap(x if group == 1 else jnp.repeat(x, group, axis=1))
    bias = None if mask is None else jnp.where(mask.dense(), 0.0, -jnp.inf)
    if docs is not None:
        same = jnp.where(docs[:, None, :, None] == docs[:, None, None, :],
                         0.0, -jnp.inf)                       # [rows, 1, q, k]
        bias = same if bias is None else same + bias
    return checkpoint_name(swap(attention_ops.attention(
        swap(q), spread(k), spread(v), causal=mask is None, bias=bias,
        scale=1.0)), ATTN_CORE)


def _project(spec: str, x, w):
    """einsum of an activation with a view of a weight, under the
    precision policy."""
    return jnp.einsum(spec, precision.cast_in(x), precision.cast_in(w),
                      precision=precision.matmul_precision(),
                      preferred_element_type=precision.preferred_out())


def mla(p: MLAttentionParam, params: Params, x, ctx):
    """Multi-head latent attention. The layout of q, k and v is decided on
    the weights, where it is free: views of the stored matrices (published
    shapes and column order), made each step, whose products come out heads
    first, [rows, heads, positions, d], the rotary columns half-split and q
    scaled -- what the core reads. v goes from its product to the core
    untouched, q through the rotary turn alone, k through the concatenation
    that sets the one shared rotary key beside every head's keys, and the
    output projection contracts (heads, d) of the core's result as it
    lies. Without a query latent (`q_lora_rank` 0 or None) the one matrix `q`
    takes `q_b`'s place and reads x itself, with no norm before it."""
    h, nope, rope = p.num_heads, p.qk_nope_head_dim, p.qk_rope_head_dim
    rank = p.kv_lora_rank
    w_q = params["q_b" if p.q_lora_rank else "q"].reshape(-1, h, nope + rope)
    w_q = jnp.concatenate([w_q[..., :nope], half_split(w_q[..., nope:])],
                          axis=-1) / np.sqrt(nope + rope)
    w_kv = params["kv_b"].reshape(rank, h, nope + p.v_head_dim)
    w_k_rope = half_split(params["kv_a"][:, rank:])[:, None, :]  # one "head"
    w_o = params["o"].reshape(h, p.v_head_dim, -1)

    c_q = _rms(_dot(x, params["q_a"]), params["q_a_norm"], p.eps) \
        if p.q_lora_rank else x
    c_kv = _rms(_dot(x, params["kv_a"][:, :rank]), params["kv_a_norm"], p.eps)
    heads_first = "rnc,chd->rhnd"
    q = rotary(_project(heads_first, c_q, w_q), p.rope_theta, rope)
    k_rope = rotary(_project(heads_first, x, w_k_rope), p.rope_theta, rope)
    k_nope = _project(heads_first, c_kv, w_kv[..., :nope])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope,))],
        axis=-1)
    v = _project(heads_first, c_kv, w_kv[..., nope:])
    with jax.named_scope("core"):
        o = attention_core(q, k, v, ctx)
    if p.output_gate:
        o = _head_gate(o, x, params["out_gate"])
    return _project("rhnd,hdm->rnm", o, w_o)


def _head_gate(o, x, w_gate):
    """o [rows, heads, positions, d] scaled by sigmoid(x w_h): one scalar a
    head and position (float32), the result in the compute dtype."""
    gate = jax.nn.sigmoid(_project("rnc,ch->rhn", x, w_gate).astype(jnp.float32))
    return (o.astype(jnp.float32) * gate[..., None]).astype(
        precision.compute_dtype())


def apply_mlattention(layer: LayerSpec, params: Params, inputs, ctx):
    return (mla(layer.mla, params, inputs[0], ctx),)


# -- GQAttention -------------------------------------------------------------

def init_gqattention(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.gqa, in_shapes[0][-1]
    ks = jax.random.split(key, 4)
    h, kv = (n * p.head_dim for n in p.held())
    norms = {"q_norm": jnp.ones((p.head_dim,), jnp.float32),
             "k_norm": jnp.ones((p.head_dim,), jnp.float32)} if p.qk_norm else {}
    return {"q": _normal(ks[0], (d, h), p.std),
            "k": _normal(ks[1], (d, kv), p.std),
            "v": _normal(ks[2], (d, kv), p.std), **norms,
            "o": _normal(ks[3], (h, d), p.std)}


def gqa(p: GQAttentionParam, params: Params, x, ctx, docs=None):
    """Grouped-query attention, laid out as `mla` lays its own out: the
    products of x with views of the stored matrices come out heads first,
    q and k go through their per-head norm (1/sqrt(d) folded into q's scale
    vector) and the rotary turn over the whole head, v from its product into
    the core, and the output projection contracts (heads, d) of the core's
    result as it lies. Without the norms 1/sqrt(d) rides on the view of q's
    weight; without the rotary turn q and k go from product (or norm) to
    core. The heads are those the layer holds (`GQAttentionParam.held`): a
    share's result is its part of the sum over all heads. A layer with a
    `window` shorter than the row hands the core a sliding mask
    (`ops.attention.SlidingWindowMask`); one at least as long as the row is
    plain causal attention, as a layer without one. `score_scale` stands
    where 1/sqrt(d) would; `docs` (document ids [rows, positions]) go to the
    core."""
    (h, kv), hd, d = p.held(), p.head_dim, x.shape[-1]
    heads_first = "rnc,chd->rhnd"
    w_q = params["q"].reshape(d, h, hd)
    scaled = (lambda t: t / np.sqrt(hd)) if p.score_scale is None \
        else (lambda t: t * p.score_scale)
    q = _project(heads_first, x, w_q if p.qk_norm else scaled(w_q))
    k = _project(heads_first, x, params["k"].reshape(d, kv, hd))
    v = _project(heads_first, x, params["v"].reshape(d, kv, hd))

    def shaped(t, scale):
        if p.qk_norm:
            t = _rms(t, scale(), p.eps)
        return rotary(t, p.rope_theta, hd) if p.rotary else t

    q = shaped(q, lambda: scaled(params["q_norm"]))
    k = shaped(k, lambda: params["k_norm"])
    with jax.named_scope("core"):
        o = attention_core(q, k, v, ctx, gqa_mask(p, x.shape[1]), docs)
    return _project("rhnd,hdm->rnm", o, params["o"].reshape(h, hd, d))


def gqa_mask(p: GQAttentionParam, positions: int):
    """The mask a grouped-query layer hands its core at `positions` a row:
    None (causal over every key) without a window, or under one that reaches
    back over the whole row."""
    if p.window is None or p.window >= positions:
        return None
    return attention_ops.SlidingWindowMask(positions, p.window)


def gqa_core_blocks(p: GQAttentionParam, positions: int) -> Dict[str, int]:
    """What one grouped-query layer's core visits at `positions` a row:
    {"blocks_visited": the key blocks the forward kernel's tables send it to
    under the layer's mask, all query blocks together, "blocks_causal": those
    a causal mask over every key sends it to} -- 0 and 0 where the row is no
    whole tiles (the exact path)."""
    if positions % max(ATTN_BLOCKS):
        return {"blocks_visited": 0, "blocks_causal": 0}
    heads = p.held()[0]
    return {"blocks_visited": _blocks_visited(heads, positions,
                                              gqa_mask(p, positions)),
            "blocks_causal": _blocks_visited(heads, positions, None)}


def apply_gqattention(layer: LayerSpec, params: Params, inputs, ctx):
    return (gqa(layer.gqa, params, inputs[0], ctx, *inputs[1:]),)


# -- EVAttention -------------------------------------------------------------

def init_evattention(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.eva, in_shapes[0][-1]
    ks = jax.random.split(key, 6)
    hd = p.num_heads * p.head_dim
    return {"q": _normal(ks[0], (d, hd), p.std),
            "k": _normal(ks[1], (d, hd), p.std),
            "v": _normal(ks[2], (d, hd), p.std),
            "mu": _normal(ks[3], (p.num_heads, p.head_dim), p.std),
            "phi": _normal(ks[4], (p.num_heads, p.head_dim), p.std),
            "o": _normal(ks[5], (hd, d), p.std)}


def eva(p: EVAttentionParam, params: Params, x, ctx):
    """EVA attention, laid out as `gqa` lays its own out (as many key/value
    heads as query heads, no q/k norm: 1/sqrt(d) rides on the view of q's
    weight): q and k through the rotary turn over the whole head, v from its
    product. A row longer than a window gets, behind its keys and values,
    one summary of every chunk (`summaries`: `ops.eva.chunk_summaries`, of
    the turned keys), and the one core call (`core`) reads keys and
    summaries under `ops.eva.WindowSummaryMask`: one softmax, one
    normaliser, the gradients reaching mu and phi through the
    concatenation. A row no longer than a window has no chunk behind it
    and is plain causal attention."""
    h, hd, d, n = p.num_heads, p.head_dim, x.shape[-1], x.shape[1]
    heads_first = "rnc,chd->rhnd"
    view = lambda name: params[name].reshape(d, h, hd)
    q = rotary(_project(heads_first, x, view("q") / np.sqrt(hd)),
               p.rope_theta, hd)
    k = rotary(_project(heads_first, x, view("k")), p.rope_theta, hd)
    v = _project(heads_first, x, view("v"))
    mask = None
    if n > p.window_size:
        mask = eva_ops.WindowSummaryMask(n, p.window_size, p.chunk_size)
        with jax.named_scope("summaries"):
            k_s, v_s = eva_ops.chunk_summaries(k, v, params["mu"],
                                               params["phi"], p.chunk_size)
            k = jnp.concatenate([k, k_s], axis=2)
            v = jnp.concatenate([v, v_s], axis=2)
    with jax.named_scope("core"):
        o = attention_core(q, k, v, ctx, mask)
    return _project("rhnd,hdm->rnm", o, params["o"].reshape(h, hd, d))


def eva_core_blocks(p: EVAttentionParam, positions: int) -> Dict[str, int]:
    """What one EVA layer's core is given at `positions` a row:
    {"keys_per_query": the key columns behind a query row (the positions and
    one summary a chunk; the positions alone within one window),
    "blocks_visited": the key blocks the forward kernel's tables send it to,
    all query blocks together, "blocks": the key blocks there are} -- 0 and
    0 where the shape is not the kernel's."""
    if positions <= p.window_size:
        return {"keys_per_query": positions, "blocks_visited": 0, "blocks": 0}
    mask = eva_ops.WindowSummaryMask(positions, p.window_size, p.chunk_size)
    n, n_kv = mask.shape
    out = {"keys_per_query": n_kv, "blocks_visited": 0, "blocks": 0}
    if n % max(ATTN_BLOCKS) == 0 and n_kv % max(ATTN_BLOCKS) == 0:
        out.update(blocks_visited=_blocks_visited(p.num_heads, n, mask),
                   blocks=(n // ATTN_BLOCKS[0]) * (n_kv // ATTN_BLOCKS[1]))
    return out


def apply_evattention(layer: LayerSpec, params: Params, inputs, ctx):
    return (eva(layer.eva, params, inputs[0], ctx),)


# -- Mamba2 ------------------------------------------------------------------

def init_mamba2(key, layer: LayerSpec, in_shapes) -> Params:
    """Matrices normal(0, std); the taps and their bias uniform in
    +-1/sqrt(taps) (a depthwise Conv1d's default), the time steps' bias,
    `A_log` and `D` as Mamba-2 publishes them (`Mamba2Param`): with small
    taps x, B and C all but vanish, and at zeros every head would forget
    within two positions and the state between chunks would carry nothing."""
    p, d = layer.mamba2, in_shapes[0][-1]
    (h, g), n_state = p.held(), p.state_size
    inner, conv = h * p.head_dim, h * p.head_dim + 2 * g * n_state
    ks, bound = jax.random.split(key, 6), 1.0 / np.sqrt(p.taps)
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(ks[3], (h,)) * (np.log(p.dt_max) - np.log(p.dt_min))
        + np.log(p.dt_min)), p.dt_floor)
    return {"in_proj": _normal(ks[0], (d, inner + conv + h), p.std),
            "conv": jax.random.uniform(ks[1], (conv, p.taps), minval=-bound,
                                       maxval=bound),
            "conv_bias": jax.random.uniform(ks[2], (conv,), minval=-bound,
                                            maxval=bound),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "A_log": jnp.log(jax.random.uniform(ks[4], (h,), minval=1.0,
                                                maxval=16.0)),
            "D": jnp.ones((h,), jnp.float32),
            "norm": jnp.ones((inner,), jnp.float32),
            "out_proj": _normal(ks[5], (inner, d), p.std)}


def mamba2(p: Mamba2Param, params: Params, u, ctx, docs=None):
    """A Mamba-2 mixer over the heads and groups the layer holds
    (`Mamba2Param.held`): one product to gate, x, B, C and time steps; the
    taps, their bias and SiLU over x, B and C in float32 (`conv`); the scan
    with its skip (`ssd`: `ops.ssd`, the time steps' softplus and the decay
    float32; on the TPU at the published head and state widths the scan is
    `ops.pallas_ssd`'s kernel pair, the skip beside it in `jnp`); the gate
    and the norm a group in float32 (`gate_norm`: the gate first); the
    product back. Both rows at once, nothing named for the block to keep: the
    backward pass of a block makes the layer again once and holds, for one
    layer at a time, the float32 state every chunk started from (134 MB at
    the cell's shape; the `jnp` form's [128, 128] squares a chunk, 268 MB an
    array, where that form runs: PERF.md section 6, PR 42 and 48).

    With `docs` (the rows' document ids [rows, positions]) the taps read
    zeros before a document's first position and the scan's state is zero
    there (`ops.ssd.document_runs`: the one reading of the ids both cuts are
    made from), and the layer returns (its result, `SSD_COUNTERS`: the
    boundaries those runs hold, all rows together)."""
    (h, g), hd, n_state = p.held(), p.head_dim, p.state_size
    (r, n, _), inner = u.shape, h * hd
    f32 = lambda t: t.astype(jnp.float32)
    with jax.named_scope("in_proj"):
        zxbcdt = _dot(u, params["in_proj"])
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-h], zxbcdt[..., -h:])
    cut = {} if docs is None else {"runs": ssd_ops.document_runs(docs)}
    with jax.named_scope("conv"):
        xbc = jax.nn.silu(causal_taps(f32(xbc), params["conv"], **cut)
                          + params["conv_bias"]).astype(zxbcdt.dtype)
    x = xbc[..., :inner].reshape(r, n, h, hd)
    b, c = (t.reshape(r, n, g, n_state)
            for t in jnp.split(xbc[..., inner:], 2, axis=-1))
    with jax.named_scope("ssd"):
        # (the keyword only where it says something: an accepted benchmark
        # test swaps `ssd` for a twin that takes the six operands alone)
        y = ssd_ops.ssd(x, jax.nn.softplus(f32(dt) + params["dt_bias"]),
                        -jnp.exp(params["A_log"]), b, c, p.chunk_size, **cut,
                        **({"interpret": True} if ctx.interpret else {}))
        y = y + params["D"][:, None] * f32(x)
    with jax.named_scope("gate_norm"):
        y = (y.reshape(r, n, inner) * jax.nn.silu(f32(z))).reshape(r, n, g, -1)
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + p.eps)
        y = (y.reshape(r, n, inner) * params["norm"]).astype(zxbcdt.dtype)
    with jax.named_scope("out_proj"):
        out = _dot(y, params["out_proj"])
    if docs is None:
        return out
    return out, jnp.sum(cut["runs"][:, -1]).astype(jnp.float32)[None]


def infer_mamba2(layer: LayerSpec, in_shapes):
    return (in_shapes[0],) + ((len(SSD_COUNTERS),),) * (len(in_shapes) - 1)


def apply_mamba2(layer: LayerSpec, params: Params, inputs, ctx):
    out = mamba2(layer.mamba2, params, inputs[0], ctx, *inputs[1:])
    return out if len(inputs) > 1 else (out,)


# -- ShortConv ---------------------------------------------------------------

def init_shortconv(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.shortconv, in_shapes[0][-1]
    k_in, k_conv, k_out = jax.random.split(key, 3)
    return {"in_proj": _normal(k_in, (d, 3 * d), p.std),
            "conv": _normal(k_conv, (d, p.taps), p.std),
            "out_proj": _normal(k_out, (d, d), p.std)}


def causal_taps(s, w, runs=None):
    """c[.., t, :] = sum_j w[.., j] * s[.., t - (taps - 1) + j, :] over s
    [rows, positions, d] with w [d, taps], or s [rows, heads, positions, d]
    with w [heads, d, taps]: a depthwise causal convolution, zeros before
    position 0, as shifted sums (a pad and a contiguous slice each). With
    `runs` [rows, positions] (`ops.ssd.document_runs`; s [rows, positions,
    d]) zeros before a DOCUMENT's first position: a tap reads the position
    `back` behind where that one is of the same run."""
    taps, n = w.shape[-1], s.shape[-2]
    # a head's taps stand over the positions' axis
    tap = (lambda j: w[:, j]) if w.ndim == 2 else (lambda j: w[:, None, :, j])
    ahead = ((0, 0),) * (s.ndim - 2)
    out = s * tap(taps - 1)
    for j in range(taps - 1):
        back = taps - 1 - j
        behind = jnp.pad(s, ahead + ((back, 0), (0, 0)))[..., :n, :]
        if runs is not None:
            behind = jnp.where((jnp.pad(runs, ((0, 0), (back, 0)),
                                        constant_values=-1)[:, :n] == runs)[..., None],
                               behind, 0.0)
        out = out + behind * tap(j)
    return out


def apply_shortconv(layer: LayerSpec, params: Params, inputs, ctx):
    """[B | C | z] = x W_in; (C * taps(B * z)) W_out. The gates and the
    taps (`mix`) run in float32 between the two products."""
    (x,) = inputs
    with jax.named_scope("in_proj"):
        bcz = _dot(x, params["in_proj"])
    with jax.named_scope("mix"):
        b, c, z = (t.astype(jnp.float32) for t in jnp.split(bcz, 3, axis=-1))
        y = (c * causal_taps(b * z, params["conv"])).astype(bcz.dtype)
    with jax.named_scope("out_proj"):
        return (_dot(y, params["out_proj"]),)


# -- KDAttention -------------------------------------------------------------

def init_kdattention(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.kda, in_shapes[0][-1]
    ks = jax.random.split(key, 11)
    hd = p.num_heads * p.head_dim
    return {"q": _normal(ks[0], (d, hd), p.std),
            "k": _normal(ks[1], (d, hd), p.std),
            "v": _normal(ks[2], (d, hd), p.std),
            "q_conv": _normal(ks[3], (hd, p.taps), p.std),
            "k_conv": _normal(ks[4], (hd, p.taps), p.std),
            "v_conv": _normal(ks[5], (hd, p.taps), p.std),
            "a": _normal(ks[6], (d, hd), p.std),
            "dt_bias": _normal(ks[7], (hd,), p.std),
            "A_log": jnp.zeros((p.num_heads,), jnp.float32),
            "beta": _normal(ks[8], (d, p.num_heads), p.std),
            "out_gate": _normal(ks[9], (d, p.num_heads), p.std),
            "o_norm": jnp.ones((p.head_dim,), jnp.float32),
            "o": _normal(ks[10], (hd, d), p.std)}


def kda(p: KDAttentionParam, params: Params, x, ctx):
    """Kimi Delta Attention, laid out as `mla` and `gqa` lay theirs out: the
    products of x with views of the stored matrices come out heads first,
    [rows, heads, positions, d]; `ops.kda_shape.shape` takes the projections
    to what the rule reads (three convolutions over the positions of that
    layout, SiLU after, the L2 norms, the decay and the writing strength,
    all float32: one Pallas kernel forward and one backward where the shape
    is theirs, `causal_taps` and plain `jnp` under a checkpoint elsewhere);
    the rule itself is `ops.delta_rule.gated_delta_rule` (two kernel pairs
    where the shape is theirs, the chunk stage's and the walk's over a row's
    chunks with the state in VMEM; `jnp` and a `lax.scan` elsewhere); its
    result is normed a head, scaled by the head-wise gate and contracted with `o` as
    it lies.

    ONE ROW AT A TIME, in a checkpointed `lax.map`: what the backward pass of
    a row holds together (the five projections, the rule's inputs and
    segment states, the gate's) is 2.4 GB for one row where two rows at once
    took 4.4 when the layer was some thirty float32 passes between its
    products (compiled for a v5e, PERF.md section 6, PR 33); a row's values
    are made again when its turn comes. Of the two elementwise stages the
    first keeps its inputs alone (the kernels' `custom_vjp`; the `jnp` form's
    checkpoint), the second (the norm and gate of the result) is a
    checkpoint of its own."""
    h, hd, d = p.num_heads, p.head_dim, x.shape[-1]
    heads_first = "rnc,chd->rhnd"
    view = lambda name: params[name].reshape(d, h, hd)

    def rows(x):
        with jax.named_scope("in_proj"):
            q, k, v = (_project(heads_first, x, view(n)) for n in "qkv")
        with jax.named_scope("gates"):
            a = _project(heads_first, x, view("a"))
            b = _project("rnc,ch->rhn", x, params["beta"])
        q, k, v, g, beta = kda_shape.shape(
            q, k, v, a, b,
            [params[n + "_conv"].reshape(h, hd, p.taps) for n in "qkv"],
            params["dt_bias"].reshape(h, hd), params["A_log"], p.lower_bound,
            conv=causal_taps, interpret=ctx.interpret)
        with jax.named_scope("delta"):
            o = delta_rule.gated_delta_rule(q, k, v, g, beta,
                                            interpret=ctx.interpret)
        with jax.named_scope("out_gate"):
            o = jax.checkpoint(lambda o, x: _head_gate(
                _rms(o, params["o_norm"], p.eps), x, params["out_gate"]))(o, x)
        with jax.named_scope("out_proj"):
            return _project("rhnd,hdm->rnm", o, params["o"].reshape(h, hd, d))

    # the block keeps the layer's result (KEPT_NAMES): the rows' checkpoints
    # need nothing of the forward pass but its inputs, so with the result at
    # hand the block's backward pass never runs the layer forward a second
    # time before the rows' own recomputation does
    return checkpoint_name(lax.map(
        jax.checkpoint(lambda row: rows(row[None])[0]), x), KDA_OUT)


def apply_kdattention(layer: LayerSpec, params: Params, inputs, ctx):
    return (kda(layer.kda, params, inputs[0], ctx),)


# -- MoE ---------------------------------------------------------------------

def moe_capacity(p: MoEParam, tokens: int, tile: int = GMM_TILING[0]) -> int:
    """Rows of the buffer the held experts' slots are gathered into."""
    k_here = min(p.num_experts_per_tok, p.experts_held[1])
    rows = tokens * k_here  # every slot that can land here
    if p.capacity_factor is not None:
        even = tokens * p.num_experts_per_tok * p.experts_held[1] \
            / p.n_routed_experts
        rows = min(rows, int(np.ceil(p.capacity_factor * even)))
    return -(-rows // tile) * tile


#: expert form -> the gate's activation, for the forms of three products a
#: slot, down(act(gate x) up x)
GATED_FORMS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def _expert_form(p: MoEParam) -> str:
    if p.expert_form not in (*GATED_FORMS, "relu2"):
        raise ValueError(f"expert_form {p.expert_form!r} is not built "
                         f"(swiglu, reglu and relu2 are)")
    if p.expert_form == "reglu" and p.shared_width():
        raise ValueError("a shared expert beside reglu experts is not built")
    return p.expert_form


def _score_func(p: MoEParam) -> str:
    if p.score_func not in ("sigmoid", "softmax_topk"):
        raise ValueError(f"score_func {p.score_func!r} is not built "
                         f"(sigmoid and softmax_topk are)")
    return p.score_func


def init_moe_params(key, p: MoEParam, d: int) -> Params:
    ks = jax.random.split(key, 8)
    held, w, ws = p.experts_held[1], p.intermediate_size, p.shared_width()
    gated, dl = _expert_form(p) in GATED_FORMS, p.latent_size or d
    out = {"router": _normal(ks[0], (d, p.n_routed_experts), p.std)}
    if _score_func(p) == "sigmoid":
        out["router_bias"] = _normal(ks[1], (p.n_routed_experts,), p.std)
    if gated:
        out["experts_gate"] = _normal(ks[2], (held, dl, w), p.std)
    out.update(experts_up=_normal(ks[3], (held, dl, w), p.std),
               experts_down=_normal(ks[4], (held, w, dl), p.std))
    if p.latent_size:
        k_down, k_up = jax.random.split(jax.random.fold_in(key, 8))
        out.update(latent_down=_normal(k_down, (d, dl), p.std),
                   latent_up=_normal(k_up, (dl, d), p.std))
    if ws and gated:
        out["shared_gate"] = _normal(ks[5], (d, ws), p.std)
    if ws:
        out.update(shared_up=_normal(ks[6], (d, ws), p.std),
                   shared_down=_normal(ks[7], (ws, d), p.std))
    return out


def init_moe(key, layer: LayerSpec, in_shapes) -> Params:
    return init_moe_params(key, layer.moe, in_shapes[0][-1])


# No per-slot scalar of the expert layer travels by an index over tokens x k:
# a TPU fetches or places single elements one at a time, 7-10 ns each. The
# chosen scores by `take_along_axis` -- forward, made again for the backward
# pass, and the transpose's scatter -- were 3.68 + 3.68 + 3.12 of the
# router's 19.4 ms a layer-step at k = 22 of 512 columns, to move 1.4 MB
# (16,384 tokens on a v5e; PERF.md section 6, PR 44). They sit in an array
# the router has just written, so they are SELECTED from it: compare every
# slot's column with the experts' columns, keep the score where they meet,
# sum over the columns -- one fused pass (0.57 ms there) in which [tokens, k,
# experts] never exists as an array. The scores as a payload of `top_k`'s
# sort were read too and lost: the sort with two payloads takes 0.8 ms more.

def _chosen_scores_fwd(s, idx):
    columns = lax.iota(idx.dtype, s.shape[-1])
    w = jnp.sum(jnp.where(idx[:, :, None] == columns, s[:, None, :], 0.0),
                axis=-1)
    return w, (idx, columns)


def _chosen_scores_bwd(res, dw):
    idx, columns = res
    return jnp.sum(jnp.where(idx[:, :, None] == columns, dw[:, :, None], 0.0),
                   axis=1), None


@jax.custom_vjp
def chosen_scores(s, idx):
    """w[t, j] = s[t, idx[t, j]] (`s` [tokens, experts] float32, `idx`
    [tokens, k] integers): a select over the experts' columns and a sum of
    them, never a fetch by index and never a product with a one-hot (a score
    that is not finite may not meet a 0). A token's k columns are distinct,
    so every sum has one term that is not 0 and the result and its gradient
    `ds[t, e] = sum over j of (dw[t, j] where idx[t, j] == e)` are those of
    `take_along_axis` and of its transpose's scatter-add bit for bit."""
    return _chosen_scores_fwd(s, idx)[0]


chosen_scores.defvjp(_chosen_scores_fwd, _chosen_scores_bwd)


def _chosen_logits(z, idx):
    """(`idx` as int32, z[t, idx[t, j]] float32), both named MOE_ROUTE: all
    the backward pass reads of a token's choice."""
    idx = checkpoint_name(idx.astype(jnp.int32), MOE_ROUTE)
    return idx, checkpoint_name(chosen_scores(z, idx), MOE_ROUTE)


def route(p: MoEParam, params: Params, xf):
    """(chosen experts [tokens, k] int32, their weights [tokens, k] f32):
    sigmoid scores in float32, the top k of score + bias -- among all the
    routed experts (`n_group` 1) or among those of the `topk_group` groups
    whose two best entries of score + bias sum highest -- weights the
    sigmoids of the chosen logits (`chosen_scores`: selected from the
    logits' columns, not fetched by index) normalised and scaled
    (`noaux_tc`). Under `softmax_topk`: the top k of the float32 logits,
    weights the softmax over the chosen logits alone (read the same way);
    no bias, nothing left to normalise or scale. The weights are a function
    of the chosen ids and logits alone, which carry the name MOE_ROUTE: the
    sigmoid over all the columns feeds only the choice, which has no
    gradient, so a block that keeps the two never makes the logits again."""
    z = jnp.dot(xf.astype(jnp.float32), params["router"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
    if _score_func(p) == "softmax_topk":
        idx, zc = _chosen_logits(z, lax.top_k(z, p.num_experts_per_tok)[1])
        return idx, jax.nn.softmax(zc, axis=-1)
    choice = jax.nn.sigmoid(z) + lax.stop_gradient(params["router_bias"])
    if p.n_group > 1:
        grouped = choice.reshape(choice.shape[0], p.n_group, -1)
        _, best = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], axis=-1),
                            p.topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(p.n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            choice.shape)
    idx, zc = _chosen_logits(z, lax.top_k(choice, p.num_experts_per_tok)[1])
    w = jax.nn.sigmoid(zc)
    if p.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + p.norm_topk_eps)
    return idx, w * p.routed_scaling_factor


def _grouped_dot(x, w, group_sizes, ctx):
    """x[rows of group g] @ w[g] for every group; rows past the groups' end
    give zeros."""
    w = precision.cast_in(w)
    if use_kernels(ctx) and x.dtype == jnp.bfloat16:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        return megablox.gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                            tiling=GMM_TILING)
    return lax.ragged_dot(x, w, group_sizes,
                          precision=precision.matmul_precision(),
                          preferred_element_type=precision.preferred_out())


# Dispatch and combine are each other's transpose, written as one pair over
# the buffer's rows. Left to autodiff, the backward of a row gather is a
# scatter-add of thousands of rows, which a TPU runs one row at a time; and a
# pass over the step's tokens x k slots is mostly masked (one slot in four,
# or in eight, lands here). Written by hand as ONE scatter-add of the buffer's
# rows it is nevertheless the cheaper way to sum by token where the buffer is
# a small share of the slots (k = 22 against room for 1.4 slots a token; one
# expert in 64 held): the k gathers fetch every token's row whether its slot
# landed or not, k x tokens rows, and the scatter-add walks the buffer's rows
# alone (`sum_walks_buffer`; SCATTER_ROW_COST holds the chip's readings). A
# `plan` is the routing's index arrays: `tok` [rows] the token of every
# buffer row, `row_slot` [rows] its slot, `row_ok`
# [rows] whether a slot landed in it, and, where the sums go by slot (the k
# gathers), the other way round `slot_row` [tokens, k] (clamped into the
# buffer) and `slot_ok` [tokens, k]. Where the sums walk the buffer nothing
# reads the slot side, and the plan holds none: it costs a second sort.

def _plan(idx, experts_held, rows: int):
    """(the plan, the slots that landed by held expert [held], those of them
    that found room [held]) from the experts every token chose (`idx`
    [tokens, k]), for a buffer of `rows` rows: the slots sorted by held
    expert (stable: by token within an expert), those that land nowhere
    here last; what does not fit is dropped from the END of the sorted
    slots. The plan has its slot side where `sum_walks_buffer` says the
    sums read it."""
    (tokens, k), (first, held) = idx.shape, experts_held
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # row -> slot
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    kept = ends[-1]
    n = min(rows, tokens * k)
    row_slot = jnp.pad(order[:n], (0, rows - n))
    plan = {"tok": row_slot // k, "row_slot": row_slot,
            "row_ok": jnp.arange(rows, dtype=jnp.int32) < kept}
    if not sum_walks_buffer(rows, tokens, k):
        slot_row = jnp.argsort(order).astype(jnp.int32).reshape(tokens, k)
        plan.update(slot_row=jnp.minimum(slot_row, rows - 1),
                    slot_ok=slot_row < kept)
    return plan, sizes, jnp.diff(ends, prepend=0)


def _take_rows(x, index):
    """x[index] along rows; every index is in bounds by construction, so no
    fill pass follows the gather."""
    return x.at[index].get(mode="promise_in_bounds")


def sum_walks_buffer(rows: int, tokens: int, k: int) -> bool:
    """Whether the weighted sum by token walks the buffer's `rows` rows (one
    scatter-add) or the `tokens` x `k` slots (k gathers): the cheaper of the
    two on the chip, from the shapes alone."""
    return SCATTER_ROW_COST * rows < k * tokens


def _weighted_sum_by_token(rows, w, plan, tokens: int):
    """Token t of `tokens` <- sum over its k slots of w[t, j] * rows[row of
    slot (t, j)] in float32, the slots that did not land left out (their
    rows may hold anything); `w` None: every weight 1 (the dispatch's
    transpose). Where the buffer is short beside the slots (a plan without
    a slot side): every landed row times its slot's weight, added
    into its token's row of a float32 zero array by scatter-add, a slab of
    SCATTER_COLUMNS columns at a time (a token's slots that landed in several
    held experts are several rows with one index). Elsewhere: k gathers of
    [tokens] rows and one fused weighted add."""
    if "slot_row" not in plan:
        ok = plan["row_ok"]
        landed = jnp.where(ok[:, None], rows.astype(jnp.float32), 0)
        if w is not None:
            landed = landed * jnp.where(ok, _take_rows(
                w.reshape(-1), plan["row_slot"]), 0.0)[:, None]
        d = rows.shape[1]
        return jnp.concatenate([
            jnp.zeros((tokens, min(SCATTER_COLUMNS, d - c)), jnp.float32).at[
                plan["tok"]].add(landed[:, c:c + SCATTER_COLUMNS],
                                 mode="promise_in_bounds").astype(rows.dtype)
            for c in range(0, d, SCATTER_COLUMNS)], axis=1)

    def landed(j):
        row = jnp.where(plan["slot_ok"][:, j, None], _take_rows(
            rows, plan["slot_row"][:, j]).astype(jnp.float32), 0)
        return row if w is None else row * w[:, j, None]

    return sum(landed(j) for j in range(plan["slot_row"].shape[1])).astype(
        rows.dtype)


def rows_of_tokens(xf, plan):
    """Buffer row r <- token plan["tok"][r]; rows no slot landed in hold
    some token's row."""
    return _rows_of_tokens(xf.shape[0], xf, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of_tokens(tokens: int, xf, plan):
    return _take_rows(xf, plan["tok"])


def _rows_of_tokens_bwd(tokens, plan, g):
    return _weighted_sum_by_token(g, None, plan, tokens), None


_rows_of_tokens.defvjp(
    lambda tokens, xf, plan: (_take_rows(xf, plan["tok"]), plan),
    _rows_of_tokens_bwd)


@jax.custom_vjp
def sum_by_token(rows, w, plan):
    """Token t <- sum over its landed slots of w[t, j] * rows[row of slot
    (t, j)], accumulated in float32: `rows_of_tokens`' transpose, weighted."""
    return _weighted_sum_by_token(rows, w, plan, w.shape[0])


def _sum_by_token_bwd(res, g):
    rows, w, plan = res
    g_tok = rows_of_tokens(g, plan).astype(jnp.float32)
    w_row = jnp.where(plan["row_ok"],
                      _take_rows(w.reshape(-1), plan["row_slot"]), 0.0)
    drows = (w_row[:, None] * g_tok).astype(rows.dtype)
    # <rows[r], g[its token]>: a scalar a row, placed at the row's slot (a
    # slot lands in at most one row; a row nothing landed in adds 0 wherever
    # it points): the buffer's scalars scattered, not tokens x k fetched
    dw_row = jnp.sum(rows.astype(jnp.float32) * g_tok, axis=-1)
    dw = jnp.zeros(w.size, jnp.float32).at[plan["row_slot"]].add(
        jnp.where(plan["row_ok"], dw_row, 0.0), mode="promise_in_bounds")
    return drows, dw.reshape(w.shape), None


sum_by_token.defvjp(
    lambda rows, w, plan: (_weighted_sum_by_token(rows, w, plan, w.shape[0]),
                           (rows, w, plan)), _sum_by_token_bwd)


def moe(p: MoEParam, params: Params, x, ctx, router_x=None):
    """(result [rows, positions, d], counters [len(MOE_COUNTERS)] f32,
    the experts every position chose [rows, positions, k] int32). With a
    latent the rows that travel are the latent's: `latent_down` before the
    dispatch, `latent_up` after the combine (the router's weights applied in
    the latent); the router and the shared expert read x itself -- or the
    router `router_x`, where the layer is given one (another tensor of x's
    shape: the stream as it stood before the attention)."""
    r, n, d = x.shape
    tokens, k = r * n, p.num_experts_per_tok
    act = GATED_FORMS.get(_expert_form(p))
    gated = act is not None
    xf = x.reshape(tokens, d)
    with jax.named_scope("router"):
        idx, w = route(p, params, xf if router_x is None
                       else router_x.reshape(tokens, d))
    lf = xf
    if p.latent_size:
        with jax.named_scope("latent_down"):
            lf = _dot(xf, params["latent_down"])
    with jax.named_scope("dispatch"):
        plan, sizes, kept_sizes = _plan(idx, p.experts_held,
                                        moe_capacity(p, tokens))
        # the backward pass reads the plan, and of the two counts the
        # grouped products' group sizes (`sizes` feeds the counters alone)
        plan = {n: checkpoint_name(a, MOE_ROUTE) for n, a in plan.items()}
        kept_sizes = checkpoint_name(kept_sizes, MOE_ROUTE)
        xs = rows_of_tokens(lf, plan)
    with jax.named_scope("experts"):
        if gated:
            g = _grouped_dot(xs, params["experts_gate"], kept_sizes, ctx)
            u = _grouped_dot(xs, params["experts_up"], kept_sizes, ctx)
            h = (act(g.astype(jnp.float32))
                 * u.astype(jnp.float32)).astype(g.dtype)
        else:
            h = _relu2(_grouped_dot(xs, params["experts_up"], kept_sizes, ctx))
        y = _grouped_dot(h, params["experts_down"], kept_sizes, ctx)
    with jax.named_scope("combine"):
        out = sum_by_token(y, w, plan)
    if p.latent_size:
        with jax.named_scope("latent_up"):
            out = _dot(out, params["latent_up"])
    if p.shared_width():
        with jax.named_scope("shared"):
            out = out + (_swiglu(xf, params["shared_gate"], params["shared_up"],
                                 params["shared_down"]) if gated else
                         _relu2_mlp(xf, params["shared_up"],
                                    params["shared_down"]))
    landed = jnp.sum(sizes)
    counters = jnp.stack([landed, landed - jnp.sum(kept_sizes), jnp.max(sizes),
                          jnp.min(sizes)]).astype(jnp.float32)
    return (out.reshape(r, n, d), lax.stop_gradient(counters),
            idx.reshape(r, n, k))


def _moe_shapes(p: MoEParam, in_shape):
    return (in_shape, (len(MOE_COUNTERS),),
            tuple(in_shape[:-1]) + (p.num_experts_per_tok,))


def infer_moe(layer: LayerSpec, in_shapes):
    return _moe_shapes(layer.moe, in_shapes[0])


def apply_moe(layer: LayerSpec, params: Params, inputs, ctx):
    """One bottom: the experts' input, which the router reads too. Two: the
    experts' input, then the router's."""
    return moe(layer.moe, params, inputs[0], ctx, *inputs[1:])


# -- MTP ---------------------------------------------------------------------

def init_mtp(key, layer: LayerSpec, in_shapes) -> Params:
    p, d = layer.mtp, in_shapes[0][-1]
    k_eh, k_attn, k_moe = jax.random.split(key, 3)
    ones = lambda: jnp.ones((d,), jnp.float32)
    return {"enorm": ones(), "hnorm": ones(),
            "eh_proj": _normal(k_eh, (2 * d, d), p.std),
            "attn_norm": ones(), **init_mla(k_attn, p.attention, d),
            "mlp_norm": ones(), **init_moe_params(k_moe, p.moe, d),
            "norm": ones()}


def infer_mtp(layer: LayerSpec, in_shapes):
    return _moe_shapes(layer.mtp.moe, in_shapes[0])


def apply_mtp(layer: LayerSpec, params: Params, inputs, ctx):
    """(h [rows, positions, d]: the last layer's output before the final
    norm; e: the embedding of the NEXT token at every position) -> the
    module's normed output, for the shared head, and its expert layer's
    counters and choices."""
    p = layer.mtp
    h, e = inputs
    x = _dot(jnp.concatenate([_rms(h, params["hnorm"], p.eps),
                              _rms(e, params["enorm"], p.eps)], axis=-1),
             params["eh_proj"])
    with jax.named_scope("attention"):
        x = x + mla(p.attention, params,
                    _rms(x, params["attn_norm"], p.eps), ctx)
    with jax.named_scope("moe"):
        y, counters, chosen = moe(p.moe, params,
                                  _rms(x, params["mlp_norm"], p.eps), ctx)
    return _rms(x + y, params["norm"], p.eps), counters, chosen


#: layer type -> (which of its tops is its counters, their names); a
#: Mamba2 layer has that top where it is given document ids
COUNTER_TOPS = {"MoE": (1, MOE_COUNTERS), "MTP": (1, MOE_COUNTERS),
                "Mamba2": (1, SSD_COUNTERS)}
#: layer type -> the names its implementation puts on values a recomputation
#: block keeps for the backward pass (`mla` serves both)
#: KDAttention names its RESULT (84 MB a layer at [2, 8192, 2560] bf16) and
#: nothing of the rule: what the rule's backward needs (every chunk's state
#: and pseudo-values, 0.8 GB a layer) the layer's own checkpoints make again
#: a row and a segment at a time, from the layer's inputs alone -- so a
#: block that holds the result has no reason to run the layer again itself
#: (PERF.md section 6, PR 33).
#: GatedMLP names its two input products g = x W_gate and u = x W_up, in the
#: compute dtype (2 x 360.7 MB a layer-step at EvaByte's [1, 16384, 11008]
#: bf16; 2 x 335.5 MB at GLM's [2, 8192, 10240]): the two of the layer's
#: eleven products a step that a bare block made twice, 1.478 TFLOP each at
#: EvaByte's widths. h = silu(g) u is NOT named: from g and u it is one
#: elementwise float32 pass, which costs a few per cent of the product it
#: feeds, where keeping it would hold a third array of that size. The expert
#: layer's shared expert (`moe`, through `_swiglu`) names nothing: it is a
#: fraction of this layer's width, and its block's memory and XLA's schedule
#: of the routed products around it stay what they were (PERF.md section 6,
#: PR 41).
#: InnerProduct (`layers.apply_innerproduct`) names its RESULT wherever the
#: layer stands in a recomputation block, and nowhere else (CaffeNet's fc6-fc8
#: stand in none and trace to the program they were): in the sequence models
#: that is every head -- the [tokens x vocabulary-share] product, the largest
#: single matmul of each model, which the bare block its norm and loss shared
#: with it made four times a head a step (forward, forward again, dX, dW) --
#: and the 8,192 -> 4,096 projection into Nemotron's MTP module. Kept as
#: made, in the product's own dtype (bf16 under the bf16 policy, float32
#: where `float32_out` says so), the bits the second product would have made;
#: the final norm and the loss's float32 passes are still made again, from
#: the block's input and the kept logits. Bytes kept a step at 16,384 tokens:
#: SmallThinker 1,245 MB (37,984 columns), Ling 644 (19,648), LFM2 537
#: (16,384, on the table's matrix: `transposed`), EvaByte 168 (8 x 320,
#: float32), GLM 2 x 634 (19,360) and Nemotron 2 x 537 + 134 (16,384; the
#: second head of both runs on the first's matrix, `param_from`). The head's
#: block is the LAST of the forward pass -- its backward starts the moment
#: its forward ends -- so in the one-head models the kept logits occupy what
#: the recomputed ones would at the same moment and the round's memory is
#: what it was; in the two-head models the main head's logits live across the
#: MTP module (PERF.md section 6, PR 47).
#: MoE (and the MTP module, which calls `moe`) names what its backward pass
#: reads of the ROUTING: the chosen experts and their raw scores (`route`:
#: [tokens, k] int32 and float32) and the plan (`moe`) -- its three index
#: arrays by buffer row where the sums walk the buffer (`_plan` makes no
#: slot side there, and no second sort in either pass), all five where the
#: k gathers run -- with the grouped products' group sizes. A
#: layer-step at 16,384 tokens: 3.09 MB at Nemotron's k = 22 (2.88 the
#: choice, 0.20 the plan by row), 1.09 at Ling's k = 8, 1.83 at
#: SmallThinker's k = 6, 1.15 at LFM2's and 1.00 at GLM's k = 4 (the plan by
#: slot with them) -- 5 to 19 MB a step over a cell's four to eight expert
#: layers. A bare block made all of it again: the float32 `HIGHEST` score
#: product over every column, the `top_k`s (sorts of [tokens, experts]), the
#: select, and `_plan`'s two argsorts over tokens x k slots, 74 + 19 ms a
#: round behind Nemotron's 512-wide router, 79 + 5 behind Ling's. So that the
#: two [tokens, k] arrays are ALL the backward reads, the sigmoid stands
#: AFTER the selection: the sigmoid of a chosen logit is the chosen score to
#: the bit, and `dw w (1 - w)` the same products, but the backward of a
#: sigmoid over all the columns reads every column's score, and the block
#: would make the score product again whatever it kept. The logits z are
#: NOT named ([tokens, experts] float32: 33.6 MB a layer-step at 512
#: columns, read by nothing once the choice is kept), nor the dispatched
#: rows nor the grouped products' results (134 to 235 MB a layer-step: a
#: kept set that large is sized by what a round can hold, not by a layer's
#: type; PERF.md section 6, PR 52)
KEPT_NAMES = {"MLAttention": (ATTN_CORE,), "MTP": (ATTN_CORE, MOE_ROUTE),
              "GQAttention": (ATTN_CORE,), "EVAttention": (ATTN_CORE,),
              "KDAttention": (KDA_OUT,), "GatedMLP": (MLP_PRE,),
              "InnerProduct": (IP_OUT,), "MoE": (MOE_ROUTE,)}
#: kept name -> what marks the device ops that make its values in a compiled
#: program's text, a part of their scope: the name of the Pallas kernel
#: (matched as a prefix: `splash_mha_fwd_residuals`), or the named scope the
#: layer runs its plain products under. Such an op on a recomputed path
#: (`rematted_computation`) means the name did not reach its block's policy
#: (a kept value whose making leaves no such mark has no entry)
KEPT_MAKERS = {ATTN_CORE: "splash_mha_fwd", MLP_PRE: MLP_PRE, IP_OUT: IP_OUT,
               MOE_ROUTE: "router"}
#: layer type -> the named scope, under the layer's own, that holds its
#: attention ("": the whole layer): whose device ops
#: `obs.device.attention_moves` counts
ATTENTION_SCOPES = {"MLAttention": "", "MTP": "attention", "GQAttention": "",
                    "EVAttention": "", "KDAttention": "", "Mamba2": ""}
#: layer type -> the named scope, under the layer's own, that holds its
#: delta rule: whose loops and device ops `obs.device.delta_rule` counts
DELTA_SCOPES = {"KDAttention": "delta"}
#: layer type -> the named scope, under the layer's own, that holds its
#: state-space scan: whose loops and device ops `obs.device.ssm` counts
SSD_SCOPES = {"Mamba2": "ssd"}
#: layer type -> the named scopes, under the layer's own, of its chunk
#: summaries and of its core: whose kernels and bytes `obs.device.eva` counts
EVA_SCOPES = {"EVAttention": ("summaries", "core")}
#: layer type -> the named scope, under the layer's own, of the core a
#: sliding window may mask: whose kernel calls `obs.device.window` counts
WINDOW_SCOPES = {"GQAttention": "core"}
#: the named scopes, under an expert layer's own (`moe`), whose device ops
#: `obs.device.routing_moves` counts: the rows they gather and scatter-add
#: (the buffer's, never tokens x k rows) and the single scalars they fetch or
#: place by an index (a buffer row's weight and its `dw`: a few times the
#: buffer's rows; the chosen scores and their gradient are selects over the
#: experts' columns, `chosen_scores`, because a TPU moves single elements by
#: index one at a time)
ROUTING_SCOPES = ("router", "dispatch", "combine")

SEQ_LAYER_IMPLS = {
    "Embed": (init_embed, apply_embed, infer_embed),
    "RMSNorm": (init_rmsnorm, apply_rmsnorm, infer_same),
    "Eltwise": (None, apply_eltwise, infer_same),
    "GatedMLP": (init_gatedmlp, apply_gatedmlp, infer_same),
    "MLAttention": (init_mlattention, apply_mlattention, infer_same),
    "GQAttention": (init_gqattention, apply_gqattention, infer_same),
    "EVAttention": (init_evattention, apply_evattention, infer_same),
    "KDAttention": (init_kdattention, apply_kdattention, infer_same),
    "Mamba2": (init_mamba2, apply_mamba2, infer_mamba2),
    "ShortConv": (init_shortconv, apply_shortconv, infer_same),
    "MoE": (init_moe, apply_moe, infer_moe),
    "MTP": (init_mtp, apply_mtp, infer_mtp),
}
