"""Model zoo: programmatic NetSpec builders for the reference's model set.

Mirrors the architectures of the reference zoo (reference `models/`):
  - cifar10_quick  <- models/cifar10/cifar10_quick_train_test.prototxt
  - caffenet       <- models/bvlc_reference_caffenet/train_val.prototxt
                      (AlexNet variant: 5 conv + 2 LRN + 3 FC + dropout)
  - lenet          <- models/tensorflow/mnist/mnist_graph.py (LeNet-style)
  - adult_mlp      <- models/adult/adult.prototxt

and seven families of sequence models, each built from a file of its
published config:
  - glm4_moe_lite  <- huggingface.co/zai-org/GLM-4.7-Flash config.json
                      (latent attention, routed experts of which this chip
                      holds a share, one multi-token-prediction module)
  - lfm2_moe       <- huggingface.co/LiquidAI/LFM2-8B-A1B config.json
                      (gated short convolutions among grouped-query
                      attention by the config's `layer_types`, routed
                      experts without a shared one, a tied head)
  - ling3_flash    <- huggingface.co/inclusionAI/Ling-3.0-flash-VL config.json
                      (the language model: Kimi Delta Attention -- a gated
                      delta rule -- in five layers of six and latent
                      attention with direct queries in the sixth, head-wise
                      output gates, experts chosen among the best groups)
  - evabyte        <- huggingface.co/EvaByte/EvaByte config.json
                      (a dense byte-level decoder: EVA attention -- an exact
                      causal window beside chunk summaries under one softmax
                      -- norms scaled by 1 + w, a float32 residual stream,
                      eight next-byte heads with float32 logits)
  - nemotron_h     <- huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
                      config.json (one mixer a layer by the config's
                      `hybrid_override_pattern`: Mamba-2 state-space mixers
                      and grouped-query attention without a rotary turn, of
                      whose heads this chip holds a share, and LatentMoE --
                      relu^2 experts in a latent narrower than the stream;
                      a multi-token-prediction module built of the same
                      layer types)
  - smallthinker   <- huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
                      config.json (every layer an expert layer whose router
                      reads the stream BEFORE the attention; grouped-query
                      attention by the config's two layouts: global without
                      a rotary turn, or over a sliding window with one;
                      ReGLU experts weighted by a softmax over the chosen
                      logits; no dense layer, no shared expert)
  - granitemoehybrid <- huggingface.co/ibm-granite/granite-4.0-h-micro
                      config.json (two sublayers a layer: a Mamba-2 mixer of
                      ONE group that all 64 heads read, or grouped-query
                      attention without a rotary turn, by the config's
                      `layer_types`, then a dense SwiGLU; muP's four
                      multipliers in the stream, the scores and the logits;
                      a tied head; rows that hold SEVERAL DOCUMENTS -- a
                      second input of document ids cuts the taps, the scan,
                      the attention and the loss at every document's first
                      position)

Specs are built in code (the TPU-native "declarative model" is data either
way); the prototxt importer covers file-based definition parity.
"""
from __future__ import annotations

from typing import Optional, Tuple

from .model.spec import (AccuracyParam, ConvolutionParam, DropoutParam,
                         EltwiseParam, EmbedParam, EVAttentionParam, Filler,
                         GatedMLPParam, GQAttentionParam, InnerProductParam,
                         InputSpec, KDAttentionParam, LayerSpec, LossParam,
                         LRNParam, MLAttentionParam, Mamba2Param, MoEParam,
                         MTPParam, NetSpec, ParamSpec, PoolingParam, RMSNormParam,
                         ShortConvParam)

_GAUSS = lambda std: Filler(type="gaussian", std=std)
_CONST = lambda v=0.0: Filler(type="constant", value=v)
_LRMULT = (ParamSpec(lr_mult=1.0), ParamSpec(lr_mult=2.0))
# AlexNet convention: bias lr_mult 2, bias decay 0
_LRMULT_WD = (ParamSpec(lr_mult=1.0, decay_mult=1.0),
              ParamSpec(lr_mult=2.0, decay_mult=0.0))


def _conv(name, bottom, n_out, k, *, stride=1, pad=0, group=1, std=0.01,
          bias=0.0, params=_LRMULT):
    return LayerSpec(
        name=name, type="Convolution", bottoms=(bottom,), tops=(name,),
        params=params,
        conv=ConvolutionParam(num_output=n_out, kernel_size=k, stride=stride,
                              pad=pad, group=group, weight_filler=_GAUSS(std),
                              bias_filler=_CONST(bias)))


def _relu(name, blob):
    return LayerSpec(name=name, type="ReLU", bottoms=(blob,), tops=(blob,))


def _pool(name, bottom, mode, k, stride):
    return LayerSpec(name=name, type="Pooling", bottoms=(bottom,), tops=(name,),
                     pool=PoolingParam(pool=mode, kernel_size=k, stride=stride))


def _lrn(name, bottom, *, local_size=5, alpha=1e-4, beta=0.75):
    return LayerSpec(name=name, type="LRN", bottoms=(bottom,), tops=(name,),
                     lrn=LRNParam(local_size=local_size, alpha=alpha, beta=beta))


def _ip(name, bottom, n_out, *, std=0.01, bias=0.0, filler=None,
        params=_LRMULT):
    return LayerSpec(
        name=name, type="InnerProduct", bottoms=(bottom,), tops=(name,),
        params=params,
        inner_product=InnerProductParam(
            num_output=n_out,
            weight_filler=filler or _GAUSS(std),
            bias_filler=_CONST(bias)))


def _dropout(name, blob, ratio=0.5):
    return LayerSpec(name=name, type="Dropout", bottoms=(blob,), tops=(blob,),
                     dropout=DropoutParam(dropout_ratio=ratio))


def _heads(logits_blob, label_blob="label"):
    return (
        LayerSpec(name="prob", type="Softmax", bottoms=(logits_blob,),
                  tops=("prob",)),
        LayerSpec(name="accuracy", type="Accuracy",
                  bottoms=(logits_blob, label_blob), tops=("accuracy",),
                  accuracy=AccuracyParam()),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=(logits_blob, label_blob), tops=("loss",)),
    )


def cifar10_quick(batch: int = 100) -> NetSpec:
    """3×(conv5x5 pad2 + pool3/2) + 2 FC, CIFAR-10."""
    return NetSpec(
        name="CIFAR10_quick",
        inputs=(InputSpec("data", (batch, 3, 32, 32)),
                InputSpec("label", (batch, 1), "int32")),
        layers=(
            _conv("conv1", "data", 32, 5, pad=2, std=0.0001),
            _pool("pool1", "conv1", "MAX", 3, 2),
            _relu("relu1", "pool1"),
            _conv("conv2", "pool1", 32, 5, pad=2, std=0.01),
            _relu("relu2", "conv2"),
            _pool("pool2", "conv2", "AVE", 3, 2),
            _conv("conv3", "pool2", 64, 5, pad=2, std=0.01),
            _relu("relu3", "conv3"),
            _pool("pool3", "conv3", "AVE", 3, 2),
            _ip("ip1", "pool3", 64, std=0.1),
            _ip("ip2", "ip1", 10, std=0.1),
        ) + _heads("ip2"),
    )


def caffenet(batch: int = 256, crop: int = 227,
             n_classes: int = 1000) -> NetSpec:
    """BVLC reference CaffeNet (AlexNet variant), the flagship model."""
    return NetSpec(
        name="CaffeNet",
        inputs=(InputSpec("data", (batch, 3, crop, crop)),
                InputSpec("label", (batch, 1), "int32")),
        layers=(
            _conv("conv1", "data", 96, 11, stride=4, std=0.01,
                  params=_LRMULT_WD),
            _relu("relu1", "conv1"),
            _pool("pool1", "conv1", "MAX", 3, 2),
            _lrn("norm1", "pool1"),
            _conv("conv2", "norm1", 256, 5, pad=2, group=2, std=0.01, bias=1.0,
                  params=_LRMULT_WD),
            _relu("relu2", "conv2"),
            _pool("pool2", "conv2", "MAX", 3, 2),
            _lrn("norm2", "pool2"),
            _conv("conv3", "norm2", 384, 3, pad=1, std=0.01,
                  params=_LRMULT_WD),
            _relu("relu3", "conv3"),
            _conv("conv4", "conv3", 384, 3, pad=1, group=2, std=0.01, bias=1.0,
                  params=_LRMULT_WD),
            _relu("relu4", "conv4"),
            _conv("conv5", "conv4", 256, 3, pad=1, group=2, std=0.01, bias=1.0,
                  params=_LRMULT_WD),
            _relu("relu5", "conv5"),
            _pool("pool5", "conv5", "MAX", 3, 2),
            _ip("fc6", "pool5", 4096, std=0.005, bias=1.0, params=_LRMULT_WD),
            _relu("relu6", "fc6"),
            _dropout("drop6", "fc6"),
            _ip("fc7", "fc6", 4096, std=0.005, bias=1.0, params=_LRMULT_WD),
            _relu("relu7", "fc7"),
            _dropout("drop7", "fc7"),
            _ip("fc8", "fc7", n_classes, std=0.01, params=_LRMULT_WD),
        ) + _heads("fc8"),
    )


def lenet(batch: int = 64) -> NetSpec:
    """LeNet-style MNIST convnet (conv5x5x32 + conv5x5x64 + fc512 + fc10),
    mirroring the reference's TF mnist graph."""
    return NetSpec(
        name="LeNet",
        inputs=(InputSpec("data", (batch, 1, 28, 28)),
                InputSpec("label", (batch, 1), "int32")),
        layers=(
            _conv("conv1", "data", 32, 5, pad=2, std=0.1),
            _relu("relu1", "conv1"),
            _pool("pool1", "conv1", "MAX", 2, 2),
            _conv("conv2", "pool1", 64, 5, pad=2, std=0.1),
            _relu("relu2", "conv2"),
            _pool("pool2", "conv2", "MAX", 2, 2),
            _ip("fc1", "pool2", 512, std=0.1, bias=0.1),
            _relu("relu3", "fc1"),
            _ip("fc2", "fc1", 10, std=0.1, bias=0.1),
        ) + _heads("fc2"),
    )


def adult_mlp(batch: int = 64, n_features: int = 1) -> NetSpec:
    """Tiny tabular net (test fixture parity: models/adult/adult.prototxt)."""
    return NetSpec(
        name="adult",
        inputs=(InputSpec("C0", (batch, n_features)),),
        layers=(
            _ip("ip", "C0", 10, filler=Filler(type="xavier")),
            LayerSpec(name="prob", type="Softmax", bottoms=("ip",),
                      tops=("prob",)),
        ),
    )


# -- what the sequence models' decoder blocks share ---------------------------

def _rms_layer(name, bottom, block, eps, unit_offset=False) -> LayerSpec:
    return LayerSpec(name=name, type="RMSNorm", bottoms=(bottom,),
                     tops=(name,), block=block,
                     rmsnorm=RMSNormParam(eps=eps, unit_offset=unit_offset))


def _sum_layer(name, a, b, top, block, float32=False, coeff=()) -> LayerSpec:
    """The residual sum (taken and carried in float32 where the model's
    stream is; `coeff`: a + coeff[1] b where the model scales its branch)."""
    return LayerSpec(name=name, type="Eltwise", bottoms=(a, b), tops=(top,),
                     block=block,
                     eltwise=EltwiseParam(float32=float32, coeff=tuple(coeff))
                     if float32 or coeff else None)


def _ff_layer(l: str, dense: bool, dense_width: int, experts: MoEParam,
              std: float) -> LayerSpec:
    """The feed-forward layer of decoder block `l`, reading `<l>_mlp_norm`:
    a dense SwiGLU `<l>_mlp` in a leading layer, else the routed experts
    `<l>_moe` with their counters and choices."""
    if dense:
        return LayerSpec(
            name=f"{l}_mlp", type="GatedMLP", bottoms=(f"{l}_mlp_norm",),
            tops=(f"{l}_mlp",), block=l,
            gated_mlp=GatedMLPParam(intermediate_size=dense_width, std=std))
    mlp = f"{l}_moe"
    return LayerSpec(name=mlp, type="MoE", bottoms=(f"{l}_mlp_norm",),
                     tops=(mlp, f"{mlp}_counters", f"{mlp}_chosen"),
                     moe=experts, block=l)


def glm4_moe_lite(config: dict, rows: int, positions: int) -> NetSpec:
    """A `glm4_moe_lite` decoder (GLM-4.7-Flash) as ONE CHIP'S SHARE of an
    expert-parallel deployment, for training on `[rows, positions]` int32
    token ids (input `tokens`; the targets are the ids themselves, read one
    and two positions on).

    `config` holds the keys of the model's published `config.json` as run
    here -- `num_hidden_layers` layers of which the first
    `first_k_dense_replace` are dense, `n_routed_experts` experts HELD in
    each expert layer, `vocab_size` rows of the vocabulary HELD -- and a
    `share` block that says what they are a share of: `n_routed_experts` (the
    published count: the router's width), `experts_held` [first, count],
    `vocab_rows` [first, count] and `chips_sharing_a_layer`. Optional there:
    `capacity_factor` (MoEParam) and `mtp_loss_weight` (default 0.3).

    Pre-norm residual blocks: x += MLA(RMSNorm(x)); x += MLP(RMSNorm(x)),
    the MLP dense in the leading layers and routed experts + one shared
    expert after. An untied head over the held vocabulary rows. The
    multi-token-prediction module (DeepSeek-V3's form) reads the last
    layer's output before the final norm and the next token's embedding,
    and shares the embedding and the head. Loss = CE(next token) +
    mtp_loss_weight x CE(second-next token), each a mean over the positions
    that have a target. Every block is a recomputation block."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["rms_norm_eps"], 0.02
    first, held = share["experts_held"]
    vocab = share["vocab_rows"][1]
    assert held == c["n_routed_experts"] and vocab == c["vocab_size"], (
        "the share block and the held counts disagree")
    assert c.get("n_group", 1) == 1 and c.get("topk_group", 1) == 1, (
        "group-limited routing is not built")
    attention = MLAttentionParam(
        num_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), eps=eps, std=std)
    experts = MoEParam(
        n_routed_experts=share["n_routed_experts"],
        experts_held=(first, held),
        num_experts_per_tok=c["num_experts_per_tok"],
        intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"],
        capacity_factor=share.get("capacity_factor"), std=std)
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)
    head = lambda name, bottom, block, param_from=None: LayerSpec(
        name=name, type="InnerProduct", bottoms=(bottom,), tops=(name,),
        inner_product=InnerProductParam(num_output=vocab, bias_term=False,
                                        axis=-1, weight_filler=_GAUSS(std)),
        param_from=param_from, block=block)
    loss = lambda name, logits, shift, weight, block: LayerSpec(
        name=name, type="SoftmaxWithLoss", bottoms=(logits, "tokens"),
        tops=(name,), block=block,
        loss=LossParam(label_shift=shift, loss_weight=weight))

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",),
                        embed=EmbedParam(num_embeddings=vocab, dim=d, std=std))]
    for i in range(c["num_hidden_layers"]):
        l, x = f"l{i}", f"x{i}"
        layers += [
            norm(f"{l}_attn_norm", x, l),
            LayerSpec(name=f"{l}_attn", type="MLAttention",
                      bottoms=(f"{l}_attn_norm",), tops=(f"{l}_attn",),
                      mla=attention, block=l),
            _sum_layer(f"{l}_attn_res", x, f"{l}_attn", f"{l}_h", l),
            norm(f"{l}_mlp_norm", f"{l}_h", l)]
        ff = _ff_layer(l, i < c["first_k_dense_replace"],
                       c["intermediate_size"], experts, std)
        layers += [ff, _sum_layer(f"{l}_mlp_res", f"{l}_h", ff.name,
                                  f"x{i + 1}", l)]
    last = f"x{c['num_hidden_layers']}"
    layers += [norm("final_norm", last, "head"),
               head("lm_head", "final_norm", "head"),
               loss("loss_next", "lm_head", 1, 1.0, "head")]
    losses = ["loss_next"]
    if c.get("num_nextn_predict_layers", 0):
        assert c["num_nextn_predict_layers"] == 1, "one MTP module is built"
        layers += [
            LayerSpec(name="mtp_embed", type="Embed", bottoms=("tokens",),
                      tops=("mtp_embed",), param_from="embed", block="mtp",
                      embed=EmbedParam(num_embeddings=vocab, dim=d, shift=1)),
            LayerSpec(name="mtp", type="MTP", bottoms=(last, "mtp_embed"),
                      tops=("mtp", "mtp_counters", "mtp_chosen"), block="mtp",
                      mtp=MTPParam(attention=attention, moe=experts, eps=eps,
                                   std=std)),
            head("mtp_head", "mtp", "mtp_head", param_from="lm_head"),
            loss("loss_mtp", "mtp_head", 2,
                 float(share.get("mtp_loss_weight", 0.3)), "mtp_head")]
        losses.append("loss_mtp")
    layers.append(LayerSpec(name="loss", type="Eltwise",
                            bottoms=tuple(losses), tops=("loss",),
                            eltwise=EltwiseParam(operation="SUM")))
    return NetSpec(name="glm4_moe_lite",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def lfm2_moe(config: dict, rows: int, positions: int) -> NetSpec:
    """An `lfm2_moe` decoder (LFM2-8B-A1B) as ONE CHIP'S SHARE of an
    expert-parallel deployment, for training on `[rows, positions]` int32
    token ids (input `tokens`; the targets are the ids themselves, read one
    position on).

    `config` holds the keys of the model's published `config.json` as run
    here -- `num_hidden_layers` layers whose operator `layer_types` names one
    by one ("conv": a gated short convolution; "full_attention":
    grouped-query attention), the first `num_dense_layers` with a dense MLP,
    `num_experts` experts HELD in each expert layer, `vocab_size` rows of the
    vocabulary HELD -- and a `share` block as `glm4_moe_lite`'s:
    `num_experts` (the published count: the router's width), `experts_held`
    [first, count], `vocab_rows` [first, count], `chips_sharing_a_layer`,
    optionally `capacity_factor`. `head_dim` where the file gives one, else
    hidden_size / num_attention_heads.

    Pre-norm residual blocks: x += Op(RMSNorm(x)); x += FF(RMSNorm(x)), FF a
    dense SwiGLU in the leading layers and routed experts (no shared one)
    after. The head is the embedding's table transposed (tied), over the
    held vocabulary rows. Loss = CE(next token), a mean over the positions
    that have a target. Every block is a recomputation block."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["norm_eps"], 0.02
    kinds, depth = c["layer_types"], c["num_hidden_layers"]
    first, held = share["experts_held"]
    vocab = share["vocab_rows"][1]
    if held != c["num_experts"] or vocab != c["vocab_size"]:
        raise ValueError("the share block and the held counts disagree: "
                         f"experts_held {share['experts_held']} against "
                         f"num_experts {c['num_experts']}, vocab_rows "
                         f"{share['vocab_rows']} against vocab_size "
                         f"{c['vocab_size']}")
    if len(kinds) != depth or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds} does not name the operator "
                         f"(conv | full_attention) of each of the "
                         f"{depth} layers")
    if c.get("conv_bias") or not c.get("use_expert_bias", True):
        raise ValueError("a convolution bias, or a router without its "
                         "selection bias, is not built")
    attention = GQAttentionParam(
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or d // c["num_attention_heads"],
        rope_theta=float(c["rope_theta"]), eps=eps, std=std)
    conv = ShortConvParam(taps=c["conv_L_cache"], std=std)
    experts = MoEParam(
        n_routed_experts=share["num_experts"], experts_held=(first, held),
        num_experts_per_tok=c["num_experts_per_tok"],
        intermediate_size=c["moe_intermediate_size"], n_shared_experts=0,
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"], norm_topk_eps=1e-6,
        capacity_factor=share.get("capacity_factor"), std=std)
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",),
                        embed=EmbedParam(num_embeddings=vocab, dim=d, std=std))]
    for i, kind in enumerate(kinds):
        l, x = f"l{i}", f"x{i}"
        op = f"{l}_conv" if kind == "conv" else f"{l}_attn"
        layers += [
            norm(f"{l}_op_norm", x, l),
            LayerSpec(name=op, type="ShortConv", bottoms=(f"{l}_op_norm",),
                      tops=(op,), shortconv=conv, block=l)
            if kind == "conv" else
            LayerSpec(name=op, type="GQAttention", bottoms=(f"{l}_op_norm",),
                      tops=(op,), gqa=attention, block=l),
            _sum_layer(f"{l}_op_res", x, op, f"{l}_h", l),
            norm(f"{l}_mlp_norm", f"{l}_h", l)]
        ff = _ff_layer(l, i < c["num_dense_layers"], c["intermediate_size"],
                       experts, std)
        layers += [ff, _sum_layer(f"{l}_mlp_res", f"{l}_h", ff.name,
                                  f"x{i + 1}", l)]
    layers += [
        norm("final_norm", f"x{depth}", "head"),
        LayerSpec(name="lm_head", type="InnerProduct", bottoms=("final_norm",),
                  tops=("lm_head",), param_from="embed", block="head",
                  inner_product=InnerProductParam(
                      num_output=vocab, bias_term=False, axis=-1,
                      transposed=True)),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=("lm_head", "tokens"), tops=("loss",), block="head",
                  loss=LossParam(label_shift=1))]
    return NetSpec(name="lfm2_moe",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def ling3_flash(config: dict, rows: int, positions: int) -> NetSpec:
    """A `ling3_flash` decoder (Ling-3.0-flash's language model: the file's
    own `model_type`, the published config gives none) as ONE CHIP'S SHARE
    of an expert-parallel deployment, for training on `[rows, positions]`
    int32 token ids (input `tokens`; the targets are the ids themselves, read
    one position on).

    `config` holds the keys of the model's published `config.json` as run
    here -- `num_hidden_layers` layers of which the first
    `first_k_dense_replace` have a dense MLP, `num_experts` experts HELD in
    each expert layer, `vocab_size` rows of the vocabulary HELD -- and a
    `share` block as the other decoders': `num_experts` (the published
    count: the router's width), `experts_held` [first, count], `vocab_rows`
    [first, count], `chips_sharing_a_layer`, optionally `capacity_factor`,
    and `first_layer`, the published index of the first layer held (0 where
    absent): layer i here is published layer `first_layer` + i, and a
    published layer j is latent attention where (j + 1) % `layer_group_size`
    is 0 and Kimi Delta Attention elsewhere.

    Pre-norm residual blocks: x += Op(RMSNorm(x)); x += FF(RMSNorm(x)), FF a
    dense SwiGLU in the leading layers and routed experts + one shared
    expert after, the experts chosen among the `topk_group` best of
    `n_group` groups. Latent attention projects its queries directly
    (`q_lora_rank` null); both operators gate their result a head. An untied
    head over the held vocabulary rows. Loss = CE(next token), a mean over
    the positions that have a target. Every block is a recomputation block.
    A non-zero entry of the two swiglu-limit lists at a layer held is
    refused: no clamp is built."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["rms_norm_eps"], 0.02
    depth, first_layer = c["num_hidden_layers"], share.get("first_layer", 0)
    first, held = share["experts_held"]
    vocab = share["vocab_rows"][1]
    if held != c["num_experts"] or vocab != c["vocab_size"]:
        raise ValueError("the share block and the held counts disagree: "
                         f"experts_held {share['experts_held']} against "
                         f"num_experts {c['num_experts']}, vocab_rows "
                         f"{share['vocab_rows']} against vocab_size "
                         f"{c['vocab_size']}")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = c.get(key) or []
        if any(limits[first_layer:first_layer + depth]):
            raise ValueError(f"{key} is not zero at a layer held (published "
                             f"layers {first_layer} to "
                             f"{first_layer + depth - 1}): the swiglu clamp "
                             f"is not built")
    if (c.get("gated_attention_proj_granularity_type") != "head_wise"
            or not c.get("kda_safe_gate") or not c.get("no_kda_lora")
            or not c.get("linear_silu") or c.get("group_norm_size", 1) != 1
            or c.get("q_lora_rank") or c.get("score_function") != "sigmoid"):
        raise ValueError("built: head-wise output gates, the safe KDA gate at "
                         "full rank, SiLU after the convolutions, one norm "
                         "group a head, direct queries, sigmoid scores; the "
                         "file asks for something else")
    latent = MLAttentionParam(
        num_heads=c["num_attention_heads"], q_lora_rank=None,
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), eps=eps, std=std, output_gate=True)
    linear = KDAttentionParam(
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        taps=c["short_conv_kernel_size"],
        lower_bound=float(c["kda_lower_bound"]), eps=eps, std=std)
    experts = MoEParam(
        n_routed_experts=share["num_experts"], experts_held=(first, held),
        num_experts_per_tok=c["num_experts_per_tok"],
        intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["moe_shared_expert_intermediate_size"]
        // c["moe_intermediate_size"],
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"], n_group=c["n_group"],
        topk_group=c["topk_group"],
        capacity_factor=share.get("capacity_factor"), std=std)
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",),
                        embed=EmbedParam(num_embeddings=vocab, dim=d, std=std))]
    for i in range(depth):
        l, x = f"l{i}", f"x{i}"
        is_latent = (first_layer + i + 1) % c["layer_group_size"] == 0
        op = f"{l}_attn" if is_latent else f"{l}_kda"
        layers += [
            norm(f"{l}_op_norm", x, l),
            LayerSpec(name=op, type="MLAttention", bottoms=(f"{l}_op_norm",),
                      tops=(op,), mla=latent, block=l)
            if is_latent else
            LayerSpec(name=op, type="KDAttention", bottoms=(f"{l}_op_norm",),
                      tops=(op,), kda=linear, block=l),
            _sum_layer(f"{l}_op_res", x, op, f"{l}_h", l),
            norm(f"{l}_mlp_norm", f"{l}_h", l)]
        ff = _ff_layer(l, i < c["first_k_dense_replace"],
                       c["intermediate_size"], experts, std)
        layers += [ff, _sum_layer(f"{l}_mlp_res", f"{l}_h", ff.name,
                                  f"x{i + 1}", l)]
    layers += [
        norm("final_norm", f"x{depth}", "head"),
        LayerSpec(name="lm_head", type="InnerProduct", bottoms=("final_norm",),
                  tops=("lm_head",), block="head",
                  inner_product=InnerProductParam(
                      num_output=vocab, bias_term=False, axis=-1,
                      weight_filler=_GAUSS(std))),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=("lm_head", "tokens"), tops=("loss",), block="head",
                  loss=LossParam(label_shift=1))]
    return NetSpec(name="ling3_flash",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def evabyte(config: dict, rows: int, positions: int) -> NetSpec:
    """An `evabyte` decoder (EvaByte: a dense byte-level model), each layer
    whole on this chip, for training on `[rows, positions]` int32 byte ids
    (input `tokens`; the targets are the ids themselves, read one to
    `num_pred_heads` positions on).

    `config` holds the keys of the model's published `config.json` as run
    here: `num_hidden_layers` layers, each x += EVA(RMSNorm(x)); x +=
    SwiGLU(RMSNorm(x)) -- EVA attention (`attention_class` "eva") over
    aligned windows of `window_size` positions and summaries of
    `chunk_size`; every norm's scale 1 + w (`norm_add_unit_offset`); both
    sums taken and carried in float32 (`fp32_skip_add`); an untied head of
    `num_pred_heads` x `vocab_size` columns, its logits float32
    (`fp32_logits`): head m of position i is scored against byte i + 1 + m,
    and the loss is the mean over the heads of each head's mean cross-entropy
    over the positions whose target lies in the row. Every matrix, and the
    summaries' mu and phi, start normal(0, `init_std`). Every block is a
    recomputation block. Keys the builder cannot honour (another attention
    class, fewer key/value heads, biases, a tied head, rope scaling) are
    refused."""
    c = config
    d, eps, std = c["hidden_size"], c["rms_norm_eps"], c["init_std"]
    heads, depth = c["num_attention_heads"], c["num_hidden_layers"]
    if (c.get("attention_class") != "eva" or c["num_key_value_heads"] != heads
            or c.get("attention_bias") or c.get("tie_word_embeddings")
            or c.get("rope_scaling") or c.get("hidden_act", "silu") != "silu"
            or d % heads):
        raise ValueError("built: EVA attention with as many key/value heads "
                         "as query heads, no biases, an untied head, plain "
                         "rotary, SiLU; the file asks for something else")
    attention = EVAttentionParam(
        num_heads=heads, head_dim=d // heads, window_size=c["window_size"],
        chunk_size=c["chunk_size"], rope_theta=float(c["rope_theta"]), std=std)
    offset, f32 = bool(c.get("norm_add_unit_offset")), bool(c.get("fp32_skip_add"))
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps,
                                                  offset)
    res = lambda name, a, b, top, block: _sum_layer(name, a, b, top, block,
                                                    f32)

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",), embed=EmbedParam(
                            num_embeddings=c["vocab_size"], dim=d, std=std))]
    for i in range(depth):
        l, x = f"l{i}", f"x{i}"
        layers += [
            norm(f"{l}_attn_norm", x, l),
            LayerSpec(name=f"{l}_attn", type="EVAttention",
                      bottoms=(f"{l}_attn_norm",), tops=(f"{l}_attn",),
                      eva=attention, block=l),
            res(f"{l}_attn_res", x, f"{l}_attn", f"{l}_h", l),
            norm(f"{l}_mlp_norm", f"{l}_h", l),
            _ff_layer(l, True, c["intermediate_size"], None, std),
            res(f"{l}_mlp_res", f"{l}_h", f"{l}_mlp", f"x{i + 1}", l)]
    layers += [
        norm("final_norm", f"x{depth}", "head"),
        LayerSpec(name="lm_head", type="InnerProduct", bottoms=("final_norm",),
                  tops=("lm_head",), block="head",
                  inner_product=InnerProductParam(
                      num_output=c["num_pred_heads"] * c["vocab_size"],
                      bias_term=False, axis=-1,
                      float32_out=bool(c.get("fp32_logits")),
                      weight_filler=_GAUSS(std))),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=("lm_head", "tokens"), tops=("loss",), block="head",
                  loss=LossParam(label_shift=1, heads=c["num_pred_heads"]))]
    return NetSpec(name="evabyte",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def nemotron_h(config: dict, rows: int, positions: int) -> NetSpec:
    """A `nemotron_h` decoder (Nemotron-3-Super) as ONE CHIP'S SHARE of a
    deployment that is tensor-parallel inside a host and expert-parallel
    across hosts, for training on `[rows, positions]` int32 token ids (input
    `tokens`; the targets are the ids themselves, read one and two positions
    on).

    `config` holds the keys of the model's published `config.json` as run
    here -- one layer a letter of `hybrid_override_pattern` ("M": a Mamba-2
    mixer, "*": grouped-query attention, "E": LatentMoE; any other letter is
    refused), `mamba_num_heads` heads and `n_groups` groups HELD in each
    Mamba-2 mixer, `num_attention_heads` / `num_key_value_heads` heads HELD
    in each attention, `n_routed_experts` experts HELD in each expert layer,
    `vocab_size` rows of the vocabulary HELD -- and a `share` block that says
    what they are a share of: the five published counts under their own
    keys, and [first, count] of each as `mamba_heads_held`,
    `mamba_groups_held`, `attention_heads_held`, `kv_heads_held`,
    `experts_held`, `vocab_rows`; `shared_columns` [first, count], the
    columns held of the shared expert's published width
    `moe_shared_expert_intermediate_size` (a width: the file keeps it whole);
    `chips_sharing_a_layer`; optionally `capacity_factor` (MoEParam) and
    `mtp_loss_weight` (default 0.1).

    Every layer is x += Mixer(RMSNorm(x)), one mixer and no second
    sublayer; the stream is the policy's dtype (`residual_in_fp32` true is
    refused). The attention turns nothing by position and norms no head (the
    Mamba-2 layers carry position). An expert layer's router and shared
    expert read the stream; its routed experts, relu(up x)^2 down, work in
    the `moe_latent_size` latent. A mixer's result is this chip's part of
    the sum over all heads, columns and experts: what the absent ones would
    add is left out. An untied head over the held vocabulary rows. The
    multi-token-prediction module (`num_nextn_predict_layers` 1) reads the
    last layer's output before the final norm and the next token's
    embedding, each normed, concatenated and projected 2d -> d, then one
    layer a letter of `mtp_hybrid_override_pattern` with weights of its
    own, a norm, and the shared head. Loss = CE(next token) +
    mtp_loss_weight x CE(second-next token). Every layer is a recomputation
    block."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["layer_norm_epsilon"], 0.02
    pattern, vocab = c["hybrid_override_pattern"], share["vocab_rows"][1]
    mtp_pattern = c.get("mtp_hybrid_override_pattern", "") \
        if c.get("num_nextn_predict_layers", 0) else ""
    held = {"n_routed_experts": "experts_held",
            "mamba_num_heads": "mamba_heads_held",
            "n_groups": "mamba_groups_held",
            "num_attention_heads": "attention_heads_held",
            "num_key_value_heads": "kv_heads_held",
            "vocab_size": "vocab_rows"}
    wrong = {k: (c[k], share[v]) for k, v in held.items()
             if c[k] != share[v][1]}
    if sum(share["shared_columns"]) > c["moe_shared_expert_intermediate_size"]:
        wrong["moe_shared_expert_intermediate_size"] = (
            c["moe_shared_expert_intermediate_size"], share["shared_columns"])
    if wrong:
        raise ValueError(f"the share block and the held counts disagree "
                         f"(key: held, share's [first, count]): {wrong}")
    if len(pattern) != c["num_hidden_layers"] or set(pattern + mtp_pattern) - set("ME*"):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} / {mtp_pattern!r} does not "
            f"name the mixer (M | E | *) of each of the "
            f"{c['num_hidden_layers']} layers; no other letter is built")
    if (c.get("mamba_hidden_act", "silu") != "silu"
            or c.get("mlp_hidden_act") != "relu2" or not c.get("use_conv_bias")
            or any(c.get(k) for k in ("use_bias", "mlp_bias", "attention_bias",
                                      "mamba_proj_bias", "tie_word_embeddings",
                                      "residual_in_fp32", "sliding_window"))
            or c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1
            or c.get("num_nextn_predict_layers", 0) > 1
            or share["mamba_num_heads"] * c["mamba_head_dim"] != c["expand"] * d):
        raise ValueError("built: SiLU in the mixers, relu^2 experts, biased "
                         "taps, no other bias, an untied head, a stream in "
                         "the policy's dtype, full attention, one routing "
                         "group, one MTP module, expand x hidden = heads x "
                         "head; the file asks for something else")
    mixer = Mamba2Param(
        num_heads=share["mamba_num_heads"], head_dim=c["mamba_head_dim"],
        n_groups=share["n_groups"], state_size=c["ssm_state_size"],
        taps=c["conv_kernel"], chunk_size=c["chunk_size"], eps=eps, std=std,
        dt_min=c["time_step_min"], dt_max=c["time_step_max"],
        dt_floor=c["time_step_floor"],
        heads_held=tuple(share["mamba_heads_held"]),
        groups_held=tuple(share["mamba_groups_held"]))
    attention = GQAttentionParam(
        num_heads=share["num_attention_heads"],
        num_kv_heads=share["num_key_value_heads"], head_dim=c["head_dim"],
        eps=eps, std=std, rotary=False, qk_norm=False,
        heads_held=tuple(share["attention_heads_held"]),
        kv_heads_held=tuple(share["kv_heads_held"]))
    experts = MoEParam(
        n_routed_experts=share["n_routed_experts"],
        experts_held=tuple(share["experts_held"]),
        num_experts_per_tok=c["num_experts_per_tok"],
        intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"],
        capacity_factor=share.get("capacity_factor"), std=std,
        latent_size=c["moe_latent_size"], expert_form="relu2",
        shared_intermediate_size=c["moe_shared_expert_intermediate_size"],
        shared_columns=tuple(share["shared_columns"]))
    mixer.held(), attention.held()  # whole groups, or the builder refuses
    #: letter -> (the layer's suffix, its type, its parameter)
    mixers = {"M": ("mamba", "Mamba2", dict(mamba2=mixer)),
              "*": ("attn", "GQAttention", dict(gqa=attention)),
              "E": ("moe", "MoE", dict(moe=experts))}
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)
    head = lambda name, bottom, block, param_from=None: LayerSpec(
        name=name, type="InnerProduct", bottoms=(bottom,), tops=(name,),
        inner_product=InnerProductParam(num_output=vocab, bias_term=False,
                                        axis=-1, weight_filler=_GAUSS(std)),
        param_from=param_from, block=block)
    loss = lambda name, logits, shift, weight, block: LayerSpec(
        name=name, type="SoftmaxWithLoss", bottoms=(logits, "tokens"),
        tops=(name,), block=block,
        loss=LossParam(label_shift=shift, loss_weight=weight))

    def body(letters: str, l_of, x_of) -> list:
        """x_of(i + 1) = x_of(i) + Mixer(RMSNorm(x_of(i))), a layer a letter,
        each a recomputation block of its own."""
        out = []
        for i, letter in enumerate(letters):
            l, (suffix, kind, param) = l_of(i), mixers[letter]
            mix = f"{l}_{suffix}"
            tops = (mix, f"{mix}_counters", f"{mix}_chosen") \
                if kind == "MoE" else (mix,)
            out += [norm(f"{l}_norm", x_of(i), l),
                    LayerSpec(name=mix, type=kind, bottoms=(f"{l}_norm",),
                              tops=tops, block=l, **param),
                    _sum_layer(f"{l}_res", x_of(i), mix, x_of(i + 1), l)]
        return out

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",),
                        embed=EmbedParam(num_embeddings=vocab, dim=d, std=std))]
    layers += body(pattern, "l{}".format, "x{}".format)
    last = f"x{len(pattern)}"
    layers += [norm("final_norm", last, "head"),
               head("lm_head", "final_norm", "head"),
               loss("loss_next", "lm_head", 1, 1.0, "head")]
    losses = ["loss_next"]
    if mtp_pattern:
        layers += [
            LayerSpec(name="mtp_embed", type="Embed", bottoms=("tokens",),
                      tops=("mtp_embed",), param_from="embed", block="mtp",
                      embed=EmbedParam(num_embeddings=vocab, dim=d, shift=1)),
            norm("mtp_hnorm", last, "mtp"),
            norm("mtp_enorm", "mtp_embed", "mtp"),
            LayerSpec(name="mtp_cat", type="Concat",
                      bottoms=("mtp_hnorm", "mtp_enorm"), tops=("mtp_cat",),
                      block="mtp"),
            LayerSpec(name="mtp_eh_proj", type="InnerProduct",
                      bottoms=("mtp_cat",), tops=("mtp_x0",), block="mtp",
                      inner_product=InnerProductParam(
                          num_output=d, bias_term=False, axis=-1,
                          weight_filler=_GAUSS(std)))]
        layers += body(mtp_pattern, "mtp{}".format, "mtp_x{}".format)
        layers += [
            norm("mtp_norm", f"mtp_x{len(mtp_pattern)}", "mtp_head"),
            head("mtp_head", "mtp_norm", "mtp_head", param_from="lm_head"),
            loss("loss_mtp", "mtp_head", 2,
                 float(share.get("mtp_loss_weight", 0.1)), "mtp_head")]
        losses.append("loss_mtp")
    layers.append(LayerSpec(name="loss", type="Eltwise",
                            bottoms=tuple(losses), tops=("loss",),
                            eltwise=EltwiseParam(operation="SUM")))
    return NetSpec(name="nemotron_h",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def smallthinker(config: dict, rows: int, positions: int) -> NetSpec:
    """A `smallthinker` decoder (SmallThinker-21BA3B) as ONE CHIP'S SHARE of
    an expert-parallel deployment, for training on `[rows, positions]` int32
    token ids (input `tokens`; the targets are the ids themselves, read one
    position on).

    `config` holds the keys of the model's published `config.json` as run
    here -- `num_hidden_layers` layers, each grouped-query attention and then
    routed experts; `sliding_window_layout[i]` 1: layer i attends over its
    last `sliding_window_size` keys, 0: over every key; `rope_layout[i]` 1:
    q and k take the rotary turn, 0: none; `moe_num_primary_experts` experts
    HELD in each layer, `moe_num_active_primary_experts` chosen a token,
    `vocab_size` rows of the vocabulary HELD -- and a `share` block as
    `lfm2_moe`'s: `moe_num_primary_experts` (the published count: the
    router's width), `experts_held` [first, count], `vocab_rows` [first,
    count], `chips_sharing_a_layer`, optionally `capacity_factor`. Every matrix starts
    normal(0, 0.02); `embed_init_std`, where the file gives one, is the
    embedding table's spread instead.

    A layer: n = RMSNorm(x); h = x + GQA(n); x' = h + Experts(RMSNorm(h)),
    the experts chosen by a router that reads n -- the stream before the
    attention -- weighted by a softmax over the chosen logits, each
    (relu(m W_gate) * m W_up) W_down. No dense layer, no shared expert. An
    untied head over the held vocabulary rows. Loss = CE(next token), a mean
    over the positions that have a target. Every layer is a recomputation
    block."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["rms_norm_eps"], 0.02
    depth = c["num_hidden_layers"]
    windows, turns = c["sliding_window_layout"], c["rope_layout"]
    first, held = share["experts_held"]
    vocab = share["vocab_rows"][1]
    if held != c["moe_num_primary_experts"] or vocab != c["vocab_size"]:
        raise ValueError("the share block and the held counts disagree: "
                         f"experts_held {share['experts_held']} against "
                         f"moe_num_primary_experts "
                         f"{c['moe_num_primary_experts']}, vocab_rows "
                         f"{share['vocab_rows']} against vocab_size "
                         f"{c['vocab_size']}")
    for key, layout in (("sliding_window_layout", windows),
                        ("rope_layout", turns)):
        if len(layout) != depth or set(layout) - {0, 1}:
            raise ValueError(f"{key} {layout} does not say 0 or 1 for each "
                             f"of the {depth} layers")
    if not c.get("moe_primary_router_apply_softmax", False):
        raise ValueError("a router without the softmax over its chosen "
                         "logits (moe_primary_router_apply_softmax false: "
                         "the sigmoid form) is not built")
    if c.get("tie_word_embeddings") or c.get("rope_scaling"):
        raise ValueError("a tied head, or scaled rotary frequencies, is not "
                         "built")
    attention = lambda i: GQAttentionParam(
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), eps=eps, std=std, qk_norm=False,
        rotary=bool(turns[i]),
        window=c["sliding_window_size"] if windows[i] else None)
    experts = MoEParam(
        n_routed_experts=share["moe_num_primary_experts"],
        experts_held=(first, held),
        num_experts_per_tok=c["moe_num_active_primary_experts"],
        intermediate_size=c["moe_ffn_hidden_size"], n_shared_experts=0,
        score_func="softmax_topk", expert_form="reglu",
        capacity_factor=share.get("capacity_factor"), std=std)
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",),
                        embed=EmbedParam(num_embeddings=vocab, dim=d,
                                         std=c.get("embed_init_std", std)))]
    for i in range(depth):
        l, x, moe = f"l{i}", f"x{i}", f"l{i}_moe"
        layers += [
            norm(f"{l}_op_norm", x, l),
            LayerSpec(name=f"{l}_attn", type="GQAttention",
                      bottoms=(f"{l}_op_norm",), tops=(f"{l}_attn",),
                      gqa=attention(i), block=l),
            _sum_layer(f"{l}_op_res", x, f"{l}_attn", f"{l}_h", l),
            norm(f"{l}_mlp_norm", f"{l}_h", l),
            # the experts read the norm after the attention, the router the
            # one before it
            LayerSpec(name=moe, type="MoE",
                      bottoms=(f"{l}_mlp_norm", f"{l}_op_norm"),
                      tops=(moe, f"{moe}_counters", f"{moe}_chosen"),
                      moe=experts, block=l),
            _sum_layer(f"{l}_mlp_res", f"{l}_h", moe, f"x{i + 1}", l)]
    layers += [
        norm("final_norm", f"x{depth}", "head"),
        LayerSpec(name="lm_head", type="InnerProduct", bottoms=("final_norm",),
                  tops=("lm_head",), block="head",
                  inner_product=InnerProductParam(
                      num_output=vocab, bias_term=False, axis=-1,
                      weight_filler=_GAUSS(std))),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=("lm_head", "tokens"), tops=("loss",), block="head",
                  loss=LossParam(label_shift=1))]
    return NetSpec(name="smallthinker",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),),
                   layers=tuple(layers))


def granitemoehybrid(config: dict, rows: int, positions: int) -> NetSpec:
    """A `granitemoehybrid` decoder without experts (Granite-4.0-H-Micro) as
    ONE PIPELINE STAGE's layers with a share of the tied vocabulary, for
    training on `[rows, positions]` int32 token ids whose rows hold SEVERAL
    DOCUMENTS one behind another (inputs `tokens` and `doc_ids`, the same
    shape: ids equal along a document and changing at every document's first
    position; the targets are the ids themselves, read one position on,
    and a document's last position has none).

    `config` holds the keys of the model's published `config.json` as run
    here -- `num_hidden_layers` layers whose mixer `layer_types` names one by
    one ("mamba": a Mamba-2 mixer of `mamba_n_heads` heads of `mamba_d_head`
    over `mamba_n_groups` groups of state `mamba_d_state`, `mamba_d_conv`
    biased taps, chunks of `mamba_chunk_size`; "attention": grouped-query
    attention, no rotary turn, no head norm, scores times
    `attention_multiplier`), `vocab_size` rows of the vocabulary HELD -- and
    a `share` block: `vocab_rows` [first, count], `first_layer`.

    Every layer is two sublayers, h += residual_multiplier x
    Mixer(RMSNorm(h)); h += residual_multiplier x SwiGLU(RMSNorm(h)), the
    SwiGLU `intermediate_size` wide; the stream starts as
    `embedding_multiplier` x the table's rows and the logits are RMSNorm(h)
    E^T / `logits_scaling`, on the table itself (tied), over the held rows.
    No position is carried anywhere, so a packed row needs nothing but the
    cuts: the document ids go to every mixer (the taps, the scan's state and
    the keys a query reads stop at a document's first position) and to the
    loss. Loss = CE(next token), a mean over the positions that have a
    target. Every layer is a recomputation block.

    Refused: experts (`num_local_experts` > 0: the family's expert block),
    a `position_embedding_type` other than "nope", any bias but the taps',
    an untied head."""
    c, share = config, config["share"]
    d, eps, std = c["hidden_size"], c["rms_norm_eps"], 0.02
    kinds, depth = c["layer_types"], c["num_hidden_layers"]
    vocab = share["vocab_rows"][1]
    if vocab != c["vocab_size"]:
        raise ValueError(f"the share block and the held count disagree: "
                         f"vocab_rows {share['vocab_rows']} against "
                         f"vocab_size {c['vocab_size']}")
    if len(kinds) != depth or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds} does not name the mixer "
                         f"(mamba | attention) of each of the {depth} layers")
    if (c.get("num_local_experts") or c.get("num_experts_per_tok")
            or c.get("position_embedding_type") != "nope"
            or c.get("attention_bias") or c.get("mamba_proj_bias")
            or not c.get("mamba_conv_bias") or not c.get("tie_word_embeddings")
            or c.get("hidden_act") != "silu"
            or c.get("normalization_function", "rmsnorm") != "rmsnorm"
            or c.get("shared_intermediate_size") != c["intermediate_size"]
            or c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d):
        raise ValueError("built: no experts, no rotary turn (nope), biased "
                         "taps and no other bias, a tied head, SiLU, RMS "
                         "norms, expand x hidden = heads x head; the file "
                         "asks for something else")
    mixer = Mamba2Param(
        num_heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
        n_groups=c["mamba_n_groups"], state_size=c["mamba_d_state"],
        taps=c["mamba_d_conv"], chunk_size=c["mamba_chunk_size"], eps=eps,
        std=std)
    attention = GQAttentionParam(
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=d // c["num_attention_heads"], eps=eps, std=std,
        rotary=False, qk_norm=False,
        score_scale=float(c["attention_multiplier"]))
    branch = (1.0, float(c["residual_multiplier"]))
    norm = lambda name, bottom, block: _rms_layer(name, bottom, block, eps)

    layers = [LayerSpec(name="embed", type="Embed", bottoms=("tokens",),
                        tops=("x0",), embed=EmbedParam(
                            num_embeddings=vocab, dim=d, std=std,
                            multiplier=float(c["embedding_multiplier"])))]
    for i, kind in enumerate(kinds):
        l, x = f"l{i}", f"x{i}"
        mix = f"{l}_mamba" if kind == "mamba" else f"{l}_attn"
        layers += [
            norm(f"{l}_norm", x, l),
            LayerSpec(name=mix, type="Mamba2", bottoms=(f"{l}_norm", "doc_ids"),
                      tops=(mix, f"{mix}_counters"), mamba2=mixer, block=l)
            if kind == "mamba" else
            LayerSpec(name=mix, type="GQAttention",
                      bottoms=(f"{l}_norm", "doc_ids"), tops=(mix,),
                      gqa=attention, block=l),
            _sum_layer(f"{l}_res", x, mix, f"{l}_h", l, coeff=branch),
            norm(f"{l}_mlp_norm", f"{l}_h", l),
            LayerSpec(name=f"{l}_mlp", type="GatedMLP",
                      bottoms=(f"{l}_mlp_norm",), tops=(f"{l}_mlp",), block=l,
                      gated_mlp=GatedMLPParam(
                          intermediate_size=c["intermediate_size"], std=std)),
            _sum_layer(f"{l}_mlp_res", f"{l}_h", f"{l}_mlp", f"x{i + 1}", l,
                       coeff=branch)]
    layers += [
        norm("final_norm", f"x{depth}", "head"),
        LayerSpec(name="lm_head", type="InnerProduct", bottoms=("final_norm",),
                  tops=("lm_head",), param_from="embed", block="head",
                  inner_product=InnerProductParam(
                      num_output=vocab, bias_term=False, axis=-1,
                      transposed=True, divisor=float(c["logits_scaling"]))),
        LayerSpec(name="loss", type="SoftmaxWithLoss",
                  bottoms=("lm_head", "tokens", "doc_ids"), tops=("loss",),
                  block="head", loss=LossParam(label_shift=1))]
    return NetSpec(name="granitemoehybrid",
                   inputs=(InputSpec("tokens", (rows, positions), "int32"),
                           InputSpec("doc_ids", (rows, positions), "int32")),
                   layers=tuple(layers))


#: `model_type` of a published config.json -> its builder (config, rows,
#: positions) -> NetSpec
SEQUENCE_MODELS = {"glm4_moe_lite": glm4_moe_lite, "lfm2_moe": lfm2_moe,
                   "ling3_flash": ling3_flash, "evabyte": evabyte,
                   "nemotron_h": nemotron_h, "smallthinker": smallthinker,
                   "granitemoehybrid": granitemoehybrid}
