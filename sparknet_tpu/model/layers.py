"""JAX implementations of the Caffe layer set, NHWC / TPU-first.

Each layer type provides:
  - `init_<type>(key, layer, in_shapes) -> params dict` (parametric layers)
  - `apply_<type>(layer, params, inputs, ctx) -> outputs tuple`
  - `infer_<type>(layer, in_shapes) -> out_shapes tuple`

Layout: image tensors are NHWC on device (TPU-native minor-dim = channels →
lanes). Parameter storage is also TPU-first: conv weights HWIO, inner-product
weights (in, out). Caffe-layout import/export (OIHW, (out, in) with
NCHW-flatten ordering) lives in `sparknet_tpu.model.caffe_compat` so that
`.caffemodel`-style weights round-trip exactly.

Semantics parity notes are per-layer, citing the reference's model zoo usage
(files under /root/reference/models/) since the actual kernels lived in
native Caffe (see reference `libs/CaffeNet.scala:91,118`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.pooling import caffe_pool_output_size, global_pool2d, pool2d
from ..ops.lrn import lrn as lrn_op
from .. import precision
from .quant import QuantConfig
from .spec import Filler, LayerSpec

Params = Dict[str, jnp.ndarray]


def tp_shards_layer(layer: "LayerSpec", tp_size: int) -> bool:
    """THE tensor-parallel sharding convention, shared by the forward pass
    (ApplyCtx.tp_shards) and the trainer's state construction
    (ParallelTrainer._tp_sharded_layers): an InnerProduct layer is
    column-sharded iff tp_size divides its num_output (a tied head's matrix,
    stored transposed, is its embedding's and is not); everything else is
    replicated across the model axis."""
    return (tp_size > 1 and layer.type == "InnerProduct"
            and not layer.inner_product.transposed
            and layer.inner_product.num_output % tp_size == 0)


@dataclasses.dataclass
class ApplyCtx:
    """Per-call context threaded through layer application.

    tp_axis/tp_size: tensor-parallel mesh axis (inside shard_map). When set,
    InnerProduct layers whose num_output is divisible by tp_size hold COLUMN
    SHARDS of their weights ((in, out/tp_size), bias (out/tp_size,)) and
    all_gather the output features; other layers are replicated
    (`tp_shards_layer` is the single source of truth for the convention).

    interpret: run Pallas kernels under the Pallas INTERPRETER — the CPU
    parity-test mode of the layer path the TPU runs. Which implementation
    an op runs is the op's own decision (`ops/lrn.py`, `ops/pooling.py`),
    from the backend, this boolean and the shapes; the sequence layers
    (model/seq_layers.py) take their exact paths under it instead of jax's
    attention and grouped-matmul kernels.

    quant: serve-side weight-only quantization config (model/quant.py) —
    sets the activation dtype quantized layers dequantize into. Only
    consulted when a layer's params carry the (w_q, w_scale) pair; the
    f32 path never reads it.
    """

    train: bool = False
    rng: Optional[jax.Array] = None
    tp_axis: Optional[str] = None
    tp_size: int = 1
    interpret: bool = False
    quant: Optional[QuantConfig] = None

    def tp_shards(self, layer: "LayerSpec") -> bool:
        return self.tp_axis is not None and tp_shards_layer(layer,
                                                            self.tp_size)

    def fold(self, name: str) -> jax.Array:
        assert self.rng is not None, "dropout in train mode needs an rng key"
        # crc32, not hash(): Python string hashing is randomized per process,
        # which would make dropout masks irreproducible across runs/hosts.
        return jax.random.fold_in(self.rng, zlib.crc32(name.encode()))


# ---------------------------------------------------------------------------
# Fillers (Caffe FillerParameter semantics)
# ---------------------------------------------------------------------------


def fill(key: jax.Array, filler: Filler, shape: Tuple[int, ...],
         fan_in: int) -> jnp.ndarray:
    t = filler.type
    if t == "constant":
        return jnp.full(shape, filler.value, dtype=jnp.float32)
    if t == "gaussian":
        return filler.mean + filler.std * jax.random.normal(key, shape)
    if t == "xavier":
        scale = float(np.sqrt(3.0 / fan_in))
        return jax.random.uniform(key, shape, minval=-scale, maxval=scale)
    if t == "msra":
        std = float(np.sqrt(2.0 / fan_in))
        return std * jax.random.normal(key, shape)
    if t == "uniform":
        return jax.random.uniform(key, shape, minval=filler.min,
                                  maxval=filler.max)
    raise ValueError(f"unknown filler type {t!r}")


# ---------------------------------------------------------------------------
# Quantized-weight resolution (shared by Convolution / InnerProduct)
# ---------------------------------------------------------------------------


def resolve_weight(params: Params, x: jnp.ndarray, ctx: ApplyCtx):
    """(x, w, matmul precision, preferred_element_type) for either weight
    layout. The f32 path is byte-for-byte the pre-quant code: policy cast
    + policy precision. The quantized path (int8 `w_q` + per-channel
    `w_scale`, installed by the serve ModelManager) dequantizes into the
    quant activation dtype — `w_q * scale` fuses into the consuming
    conv/matmul under XLA — casts the activations to match, and runs
    DEFAULT precision with no forced f32 output (the bf16 MXU fast
    path; accumulation still happens in f32 inside the unit)."""
    if "w_q" in params:
        qc = ctx.quant or QuantConfig()
        dt = qc.act_dtype()
        w = (params["w_q"].astype(jnp.float32)
             * params["w_scale"]).astype(dt)
        return x.astype(dt), w, lax.Precision.DEFAULT, None
    return (precision.cast_in(x), precision.cast_in(params["w"]),
            precision.matmul_precision(), precision.preferred_out())


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------


def infer_convolution(layer: LayerSpec, in_shapes):
    (n, h, w, c), = in_shapes[:1]
    p = layer.conv
    oh = (h + 2 * p.pad - p.kernel_size) // p.stride + 1
    ow = (w + 2 * p.pad - p.kernel_size) // p.stride + 1
    return ((n, oh, ow, p.num_output),)


def init_convolution(key, layer: LayerSpec, in_shapes) -> Params:
    p = layer.conv
    c_in = in_shapes[0][-1]
    fan_in = (c_in // p.group) * p.kernel_size * p.kernel_size
    wkey, bkey = jax.random.split(key)
    # HWIO with I = c_in / group (XLA grouped-conv convention).
    w = fill(wkey, p.weight_filler,
             (p.kernel_size, p.kernel_size, c_in // p.group, p.num_output),
             fan_in)
    params = {"w": w}
    if p.bias_term:
        params["b"] = fill(bkey, p.bias_filler, (p.num_output,), fan_in)
    return params


def _s2d_eligible(p, cin: int) -> bool:
    """Space-to-depth rewrite gate: strided, ungrouped, unpadded convs with
    few input channels — i.e. an image-stem conv like CaffeNet's conv1
    (11x11/4 over RGB), whose 3-channel contraction wastes >90% of the MXU.
    The rewrite is EXACT (see apply_convolution) and measured ~1.45x faster
    for conv1 fwd+wgrad on v5e; convs that are already MXU-friendly
    (cin*s*s > 128) or touch padding/groups keep the direct form."""
    return (p.stride > 1 and p.group == 1 and p.pad == 0
            and cin * p.stride * p.stride <= 128)


def _space_to_depth(x: jnp.ndarray, s: int) -> jnp.ndarray:
    n, h, w, c = x.shape
    return x.reshape(n, h // s, s, w // s, s, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(n, h // s, w // s, s * s * c)


def apply_convolution(layer: LayerSpec, params: Params, inputs, ctx: ApplyCtx):
    p = layer.conv
    (x,) = inputs
    x, w, mm_precision, mm_out = resolve_weight(params, x, ctx)
    cin = x.shape[-1]
    if _s2d_eligible(p, cin):
        # EXACT stride-s -> stride-1 rewrite: group the input into s x s
        # patches on the channel axis and regroup the kernel taps the same
        # way. Transformed output row p' contracts input rows
        # s*p' .. s*p'+K-1 with taps 0..K-1, where taps >= k and image rows
        # >= H are zero padding that only ever meet each other — so the
        # first oh x ow outputs equal the direct conv bit-for-bit (same
        # products, same K-sized contraction tree per channel group). The
        # MXU then contracts s*s*cin channels instead of cin.
        s, k = p.stride, p.kernel_size
        n, h, iw, _ = x.shape
        K = k + ((-k) % s)                # kernel taps padded to s multiple
        oh = (h - k) // s + 1
        ow = (iw - k) // s + 1

        def img_pad(size, out):          # to an s multiple that covers the
            need = max(0, s * (out - 1) + K - size)  # last window's taps
            return need + ((-(size + need)) % s)

        xs = _space_to_depth(
            jnp.pad(x, ((0, 0), (0, img_pad(h, oh)),
                        (0, img_pad(iw, ow)), (0, 0))), s)
        wpad = jnp.pad(w, ((0, K - k), (0, K - k), (0, 0), (0, 0)))
        ks = wpad.reshape(K // s, s, K // s, s, cin, w.shape[-1]).transpose(
            0, 2, 1, 3, 4, 5).reshape(K // s, K // s, s * s * cin,
                                      w.shape[-1])
        y = lax.conv_general_dilated(
            xs, ks, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=mm_precision,
            preferred_element_type=mm_out,
        )[:, :oh, :ow]
    else:
        # grouped convs take XLA's native feature_group_count lowering:
        # explicit per-group convs + concat measured -5.3 % end to end on
        # the chip (PERF.md section 6, r1-r5)
        y = lax.conv_general_dilated(
            x, w,
            window_strides=(p.stride, p.stride),
            padding=((p.pad, p.pad), (p.pad, p.pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=p.group,
            precision=mm_precision,
            preferred_element_type=mm_out,
        )
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return (y,)


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


def infer_pooling(layer: LayerSpec, in_shapes):
    n, h, w, c = in_shapes[0]
    p = layer.pool
    if p.global_pooling:
        return ((n, 1, 1, c),)
    oh = caffe_pool_output_size(h, p.kernel_size, p.stride, p.pad)
    ow = caffe_pool_output_size(w, p.kernel_size, p.stride, p.pad)
    return ((n, oh, ow, c),)


def apply_pooling(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    p = layer.pool
    (x,) = inputs
    if p.global_pooling:
        return (global_pool2d(x, p.pool),)
    return (pool2d(x, p.pool, p.kernel_size, p.stride, p.pad),)


# ---------------------------------------------------------------------------
# LRN
# ---------------------------------------------------------------------------


def infer_lrn(layer: LayerSpec, in_shapes):
    return (in_shapes[0],)


def apply_lrn(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    p = layer.lrn
    (x,) = inputs
    return (lrn_op(x, p.local_size, alpha=p.alpha, beta=p.beta, k=p.k,
                   interpret=ctx.interpret),)


# ---------------------------------------------------------------------------
# ReLU
# ---------------------------------------------------------------------------


def infer_relu(layer: LayerSpec, in_shapes):
    return (in_shapes[0],)


def apply_relu(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    (x,) = inputs
    return (jnp.maximum(x, 0),)


# ---------------------------------------------------------------------------
# InnerProduct
# ---------------------------------------------------------------------------


def _flat_dim(shape: Tuple[int, ...]) -> int:
    d = 1
    for s in shape[1:]:
        d *= s
    return d


def infer_innerproduct(layer: LayerSpec, in_shapes):
    if layer.inner_product.axis == -1:
        return (tuple(in_shapes[0][:-1]) + (layer.inner_product.num_output,),)
    n = in_shapes[0][0]
    return ((n, layer.inner_product.num_output),)


def init_innerproduct(key, layer: LayerSpec, in_shapes) -> Params:
    p = layer.inner_product
    fan_in = in_shapes[0][-1] if p.axis == -1 else _flat_dim(in_shapes[0])
    wkey, bkey = jax.random.split(key)
    # Stored (in, out): feeds the MXU directly as x @ w. `transposed`: (out,
    # in), an embedding's table as it lies.
    shape = (p.num_output, fan_in) if p.transposed else (fan_in, p.num_output)
    params = {"w": fill(wkey, p.weight_filler, shape, fan_in)}
    if p.bias_term:
        params["b"] = fill(bkey, p.bias_filler, (p.num_output,), fan_in)
    return params


def apply_innerproduct(layer: LayerSpec, params: Params, inputs, ctx: ApplyCtx):
    (x,) = inputs
    if layer.inner_product.axis != -1:  # else the last axis alone
        if x.ndim == 4:
            # Caffe flattens NCHW-ordered; transpose so imported Caffe
            # weights (and exported ones) line up element-for-element.
            x = jnp.transpose(x, (0, 3, 1, 2))
        x = x.reshape(x.shape[0], -1)
    x, w, mm_precision, mm_out = resolve_weight(params, x, ctx)
    if layer.inner_product.float32_out:
        mm_out = jnp.float32
    # in a recomputation block the product runs under a scope of its own
    # (`seq_layers.KEPT_MAKERS`: how a compiled program's text tells it from
    # one made again) and the layer's result is named below
    kept = layer.block is not None
    with jax.named_scope(IP_OUT) if kept else contextlib.nullcontext():
        if layer.inner_product.transposed:  # a tied head: x @ w^T, w (out, in)
            y = jnp.einsum("...k,nk->...n", x, w, precision=mm_precision,
                           preferred_element_type=mm_out)
        else:
            y = jnp.dot(x, w, precision=mm_precision,
                        preferred_element_type=mm_out)
        if layer.inner_product.divisor != 1.0:
            y = y / layer.inner_product.divisor
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    if ctx.tp_shards(layer):
        # column-parallel: this device computed features
        # [rank*out/m, (rank+1)*out/m); gather the full feature axis so
        # downstream layers see the logical blob. autodiff turns the gather
        # into the matching reduce-scatter of the cotangent.
        y = jax.lax.all_gather(y, ctx.tp_axis, axis=y.ndim - 1, tiled=True)
    if kept:
        # the block keeps the layer's result as the product made it, in its
        # own dtype (`seq_layers.KEPT_NAMES`): a head's logits are the
        # largest product of their model, and the block that holds them
        # reads them back where it would make them a second time. Under
        # tensor parallelism the GATHERED side is named, the value the rest
        # of the block reads: a name on this device's columns would keep
        # 1/m of the bytes and leave the forward made again a second
        # all_gather to run; named here, the backward pass holds neither
        # the product nor the gather twice (the gather's own transpose, the
        # reduce-scatter of the cotangent, is backward proper and stays)
        y = checkpoint_name(y, IP_OUT)
    return (y,)


# ---------------------------------------------------------------------------
# Softmax / SoftmaxWithLoss / Accuracy
# ---------------------------------------------------------------------------


def infer_softmax(layer: LayerSpec, in_shapes):
    return (in_shapes[0],)


def apply_softmax(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    (x,) = inputs
    # Caffe softmax axis=1 == channel; channels are the last axis here.
    return (jax.nn.softmax(x, axis=-1),)


def _squeeze_label(label: jnp.ndarray) -> jnp.ndarray:
    if label.ndim == 2 and label.shape[1] == 1:
        label = label[:, 0]
    return label.astype(jnp.int32)


def infer_softmaxwithloss(layer: LayerSpec, in_shapes):
    return ((),)


def apply_softmaxwithloss(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    logits, label, *docs = inputs
    if layer.loss is not None:
        return (_masked_softmax_loss(layer.loss, logits, label, *docs),)
    label = _squeeze_label(label)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
    return (jnp.mean(nll),)


def _masked_softmax_loss(p, logits, label, docs=None):
    """SoftmaxWithLoss with a LossParam: logits [..., V] against labels of
    the leading shape ([rows, positions] for a sequence model), the mean
    over the positions that have a target, times `loss_weight`. `docs`
    (document ids, the labels' shape; one head): a shifted label that lies
    in another document than its position is the ignore label."""
    label = label.astype(jnp.int32)
    if label.ndim == logits.ndim:  # Caffe's [N, 1] labels
        label = label[..., 0]
    ignore = p.ignore_label
    if p.label_shift or p.heads > 1:
        ignore = -1 if ignore is None else ignore
    if docs is not None:
        assert p.label_shift and p.heads == 1, "document ids cut a shifted label"
        label = jnp.where(shifted(docs, p.label_shift, fill=-1) == docs,
                          shifted(label, p.label_shift, fill=ignore), ignore)
    elif p.heads > 1:
        # [..., heads x V] -> [..., heads, V]; head m's labels beside it
        logits = logits.reshape(logits.shape[:-1] + (p.heads, -1))
        label = jnp.stack([shifted(label, p.label_shift + m, fill=ignore)
                           for m in range(p.heads)], axis=-1)
    elif p.label_shift:
        label = shifted(label, p.label_shift, fill=ignore)
    has_target = (jnp.ones(label.shape, bool) if ignore is None
                  else label != ignore)
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, jnp.where(has_target, label, 0)[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    if p.heads > 1:  # the mean over the heads of each head's own mean
        over = tuple(range(label.ndim - 1))
        count = jnp.maximum(jnp.sum(has_target, axis=over), 1)
        return p.loss_weight * jnp.mean(
            jnp.sum(jnp.where(has_target, nll, 0.0), axis=over)
            / count.astype(jnp.float32))
    count = jnp.maximum(jnp.sum(has_target), 1).astype(jnp.float32)
    return p.loss_weight * jnp.sum(jnp.where(has_target, nll, 0.0)) / count


def infer_accuracy(layer: LayerSpec, in_shapes):
    return ((),)


def apply_accuracy(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    logits, label = inputs
    label = _squeeze_label(label)
    k = layer.accuracy.top_k if layer.accuracy else 1
    if k == 1:
        correct = jnp.argmax(logits, axis=-1).astype(jnp.int32) == label
    else:
        topk = lax.top_k(logits, k)[1].astype(jnp.int32)
        correct = jnp.any(topk == label[:, None], axis=-1)
    return (jnp.mean(correct.astype(jnp.float32)),)


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------


def infer_dropout(layer: LayerSpec, in_shapes):
    return (in_shapes[0],)


def apply_dropout(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    (x,) = inputs
    ratio = layer.dropout.dropout_ratio if layer.dropout else 0.5
    if not ctx.train or ratio == 0.0:
        return (x,)
    keep = 1.0 - ratio
    mask = jax.random.bernoulli(ctx.fold(layer.name), keep, x.shape)
    # Caffe scales at train time by 1/keep so eval needs no rescale.
    return (jnp.where(mask, x / keep, 0.0).astype(x.dtype),)


# ---------------------------------------------------------------------------
# Concat / Flatten (small extras used by common Caffe zoo nets)
# ---------------------------------------------------------------------------


def infer_concat(layer: LayerSpec, in_shapes):
    base = list(in_shapes[0])
    base[-1] = sum(s[-1] for s in in_shapes)
    return (tuple(base),)


def apply_concat(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    return (jnp.concatenate(inputs, axis=-1),)


def infer_flatten(layer: LayerSpec, in_shapes):
    return ((in_shapes[0][0], _flat_dim(in_shapes[0])),)


def apply_flatten(layer: LayerSpec, params, inputs, ctx: ApplyCtx):
    (x,) = inputs
    if x.ndim == 4:
        x = jnp.transpose(x, (0, 3, 1, 2))
    return (x.reshape(x.shape[0], -1),)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

LAYER_IMPLS = {
    "Convolution": (init_convolution, apply_convolution, infer_convolution),
    "Pooling": (None, apply_pooling, infer_pooling),
    "LRN": (None, apply_lrn, infer_lrn),
    "ReLU": (None, apply_relu, infer_relu),
    "InnerProduct": (init_innerproduct, apply_innerproduct, infer_innerproduct),
    "Softmax": (None, apply_softmax, infer_softmax),
    "SoftmaxWithLoss": (None, apply_softmaxwithloss, infer_softmaxwithloss),
    "Accuracy": (None, apply_accuracy, infer_accuracy),
    "Dropout": (None, apply_dropout, infer_dropout),
    "Concat": (None, apply_concat, infer_concat),
    "Flatten": (None, apply_flatten, infer_flatten),
}

from .seq_layers import (IP_OUT, SEQ_LAYER_IMPLS, param_defaults,  # noqa: E402
                         shifted)

LAYER_IMPLS.update(SEQ_LAYER_IMPLS)
