"""Declarative model IR: the TPU-native equivalent of Caffe's NetParameter.

The reference framework consumed Caffe prototxt (parsed natively via
`ReadProtoFromTextFileOrDie`, see reference `apps/CifarApp.scala:83-88`) and TF
GraphDefs. Here the IR is a plain-Python dataclass graph that a compiler
(`sparknet_tpu.model.net`) lowers to a pure JAX `apply(params, batch)` function.

Layer set = what the reference model zoo uses (reference
`models/*.prototxt`): Convolution, Pooling, LRN, ReLU, InnerProduct, Softmax,
SoftmaxWithLoss, Accuracy, Dropout — plus Input declarations — and the
sequence-model set a sparse-expert decoder is built from: Embed, RMSNorm,
MLAttention (latent attention), GQAttention (grouped-query attention),
EVAttention (an exact causal window beside chunk summaries, one softmax),
KDAttention (a delta-rule linear attention), Mamba2 (a state-space mixer
that holds a share of its heads), ShortConv (a gated short convolution),
GatedMLP, MoE (an expert layer that holds a share of its experts), MTP (a
multi-token-prediction module) and Eltwise (the residual
sum, as Caffe has it).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class Filler:
    """Parameter initializer spec (Caffe `FillerParameter` semantics).

    type: "constant" (value), "gaussian" (std), "xavier" (uniform +-sqrt(3/fan_in)),
    "uniform" (min/max), "msra" (He normal).
    """

    type: str = "constant"
    value: float = 0.0
    std: float = 0.01
    mean: float = 0.0
    min: float = 0.0
    max: float = 1.0


@dataclass(frozen=True)
class ParamSpec:
    """Per-blob training hyperparameters (Caffe `ParamSpec`)."""

    lr_mult: float = 1.0
    decay_mult: float = 1.0


@dataclass(frozen=True)
class ConvolutionParam:
    num_output: int = 0
    kernel_size: int = 1
    stride: int = 1
    pad: int = 0
    group: int = 1
    bias_term: bool = True
    weight_filler: Filler = field(default_factory=Filler)
    bias_filler: Filler = field(default_factory=Filler)


@dataclass(frozen=True)
class PoolingParam:
    pool: str = "MAX"  # MAX | AVE
    kernel_size: int = 1
    stride: int = 1
    pad: int = 0
    global_pooling: bool = False


@dataclass(frozen=True)
class LRNParam:
    local_size: int = 5
    alpha: float = 1.0
    beta: float = 0.75
    k: float = 1.0
    norm_region: str = "ACROSS_CHANNELS"


@dataclass(frozen=True)
class InnerProductParam:
    num_output: int = 0
    bias_term: bool = True
    #: Caffe's `axis`: the first axis lumped into the inner product. 1 (the
    #: default) flattens everything after the batch; -1 applies the product to
    #: the last axis alone ([rows, positions, d] -> [rows, positions, out])
    axis: int = 1
    #: the matrix is stored (out, in) and the product runs over its
    #: transpose: a head tied to an embedding's table (`param_from` the Embed
    #: layer, whose gradient is then the sum of both uses)
    transposed: bool = False
    #: the product comes out float32 whatever the precision policy (under
    #: bfloat16: its operands rounded, its accumulators as they are): a
    #: head whose logits are not rounded on their way to the loss
    float32_out: bool = False
    #: the result is divided by this (a head whose model scales its logits
    #: down, muP's `logits_scaling`): in the product's own dtype, before the
    #: bias and before a recomputation block names the result
    divisor: float = 1.0
    weight_filler: Filler = field(default_factory=Filler)
    bias_filler: Filler = field(default_factory=Filler)


@dataclass(frozen=True)
class DropoutParam:
    dropout_ratio: float = 0.5


@dataclass(frozen=True)
class AccuracyParam:
    top_k: int = 1


@dataclass(frozen=True)
class LossParam:
    """Caffe's LossParameter + the layer's loss_weight, for SoftmaxWithLoss.
    `ignore_label`: positions whose label equals it leave the mean (None:
    none do). `label_shift` k: the logits at position i are held against the
    label at position i + k of the same row, and the last k positions have
    no target (a next-token loss reads the ids it was given as labels). A
    THIRD bottom, the rows' document ids, takes the target from every
    position whose label lies in another document (a document's last k).
    `heads` h > 1: the logits' last axis is h heads of V side by side, head
    m held against the label at position i + `label_shift` + m; the loss is
    the mean over the heads of each head's mean over the positions it
    scores (several next tokens predicted from one position)."""

    ignore_label: Optional[int] = None
    loss_weight: float = 1.0
    label_shift: int = 0
    heads: int = 1


@dataclass(frozen=True)
class EltwiseParam:
    operation: str = "SUM"
    coeff: Tuple[float, ...] = ()  # SUM only; () = all ones
    #: the sum is taken in float32 and stays float32 whatever the precision
    #: policy: a residual stream carried unrounded from block to block
    float32: bool = False


@dataclass(frozen=True)
class EmbedParam:
    """Rows of a table by integer id (Caffe's Embed, no bias). `shift` k
    looks up the id at position i + k of the same row (0 past the end).
    `multiplier`: the looked-up rows times a constant (muP's
    `embedding_multiplier`), in float32 before the policy's cast."""

    num_embeddings: int = 0
    dim: int = 0
    shift: int = 0
    std: float = 0.02
    multiplier: float = 1.0


@dataclass(frozen=True)
class RMSNormParam:
    eps: float = 1e-5
    #: the scale is 1 + w, the stored w starting at zero
    unit_offset: bool = False


@dataclass(frozen=True)
class MLAttentionParam:
    """Multi-head latent attention (DeepSeek-V2's MLA): queries and
    keys/values go through low-rank latents, a rotary part of width
    `qk_rope_head_dim` rides beside `qk_nope_head_dim` in every head's
    query and ONE rotary key is shared by all heads. Causal.
    `output_gate`: every head's result is scaled by sigmoid(x w_h), one
    learned scalar a head and position, before the output projection."""

    num_heads: int = 0
    #: the rank of the queries' latent (`q_a`, `q_a_norm`, `q_b`); 0 or None:
    #: no latent and no query norm, one direct projection `q` [d, heads x
    #: (nope + rope)]
    q_lora_rank: Optional[int] = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    std: float = 0.02
    output_gate: bool = False


@dataclass(frozen=True)
class KDAttentionParam:
    """Kimi Delta Attention (arXiv:2510.26692), a linear attention with one
    matrix state [head_dim, head_dim] a head, updated by the gated delta rule
    with a per-channel decay (`ops.delta_rule`): q, k, v = SiLU(conv(x W))
    (a depthwise causal convolution of `taps` taps each), q and k L2-normed a
    head (q also x head_dim^-1/2); log-decay `lower_bound` x sigmoid(exp(A_h)
    (x W_a + b)), a vector a head; writing strength sigmoid(x w_b), a scalar
    a head; the result normed a head (one shared scale) and scaled by
    sigmoid(x w_r), a scalar a head, before the output projection. No rotary
    turn."""

    num_heads: int = 0
    head_dim: int = 0
    taps: int = 4
    #: the log-decay's lower bound (`kda_lower_bound`); the chunked rule's
    #: float32 range rests on it (`ops.delta_rule.MIN_LOG_DECAY`)
    lower_bound: float = -5.0
    eps: float = 1e-6
    std: float = 0.02


def _held(share: Optional[Tuple[int, int]], whole: int) -> Tuple[int, int]:
    """(first, count) of a layer's share of `whole` heads or groups; all of
    them where the layer names none."""
    return share if share else (0, whole)


@dataclass(frozen=True)
class GQAttentionParam:
    """Grouped-query attention: `num_heads` query heads of `head_dim` over
    `num_kv_heads` key/value heads (query heads g*n .. g*n + n - 1 read
    key/value head g, n = num_heads / num_kv_heads), an RMS norm over each
    head's `head_dim` on q and on k (one scale vector each, shared by the
    heads; none where `qk_norm` is off), rotary over the whole head on
    contiguous halves (no turn where `rotary` is off: a model whose other
    layers carry position). Causal over every key, or -- `window` -- over a
    query's last `window` keys, itself among them (a sliding window: key j
    where 0 <= p - j < window). The scores are q . k / sqrt(head_dim), or q .
    k x `score_scale` where the model publishes another (muP's
    `attention_multiplier`). With a second bottom, the rows' document ids, a
    query reads the keys of its own document alone.

    A layer may hold a SHARE of its heads (tensor parallelism's view from one
    chip): `heads_held` / `kv_heads_held` (first, count) of the published
    `num_heads` / `num_kv_heads`; it builds the held heads' columns of q, k
    and v and their rows of o alone, and its result is its own part of the
    sum over all heads. None: all of them."""

    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    std: float = 0.02
    rotary: bool = True
    qk_norm: bool = True
    heads_held: Optional[Tuple[int, int]] = None
    kv_heads_held: Optional[Tuple[int, int]] = None
    window: Optional[int] = None
    score_scale: Optional[float] = None

    def held(self) -> Tuple[int, int]:
        """(query heads, key/value heads) this layer builds. Every held
        query head must read a held key/value head, as many of them each (a
        key/value head that fewer chips than query heads' shares can divide
        is held by several, each with a part of its query heads)."""
        first, h = _held(self.heads_held, self.num_heads)
        kv_first, kv = _held(self.kv_heads_held, self.num_kv_heads)
        per = self.num_heads // self.num_kv_heads
        if h % kv or any((first + j) // per - kv_first != j // (h // kv)
                         for j in range(h)):
            raise ValueError(
                f"heads_held {self.heads_held} are not the query heads of "
                f"kv_heads_held {self.kv_heads_held} ({per} a key/value head)")
        return h, kv


@dataclass(frozen=True)
class Mamba2Param:
    """A Mamba-2 mixer (arXiv:2405.21060; the `nemotron_h` family's form):
    [z | xBC | dt] = u W_in; xBC through a depthwise causal convolution of
    `taps` taps with a bias, then SiLU; x [heads, head_dim], B and C
    [n_groups, state_size] (head h reads group h // (num_heads / n_groups));
    time steps softplus(dt + dt_bias); the scan `ops.ssd` with A =
    -exp(A_log) a scalar a head, plus the skip D x; the result times SiLU(z),
    RMS-normed over each group's channels (the gate first); W_out. With a
    second bottom, the rows' document ids, the taps read zeros before a
    document's first position and the scan's state is zero there (a row of
    concatenated documents, none reading another), and the layer's second
    top is a vector of counters (`seq_layers.SSD_COUNTERS`).

    The layer holds a SHARE of its heads and groups (tensor parallelism's
    view from one chip): `heads_held` / `groups_held` (first, count) of the
    published counts -- whole groups with all their heads, so the group norm
    needs nothing from another chip -- builds the held columns of W_in, the
    held taps and the held rows of W_out alone, and its result is its own
    part of the sum over all heads. None: all of them."""

    num_heads: int = 0
    head_dim: int = 0
    n_groups: int = 1
    state_size: int = 0
    taps: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    std: float = 0.02
    #: softplus(dt_bias) starts log-uniform in [dt_min, dt_max], floored at
    #: dt_floor; A_log = log U[1, 16]; D = 1 (Mamba-2's published
    #: initialisation)
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    heads_held: Optional[Tuple[int, int]] = None
    groups_held: Optional[Tuple[int, int]] = None

    def held(self) -> Tuple[int, int]:
        """(heads, groups) this layer builds."""
        first, h = _held(self.heads_held, self.num_heads)
        g_first, g = _held(self.groups_held, self.n_groups)
        per = self.num_heads // self.n_groups
        if h != g * per or first != g_first * per:
            raise ValueError(
                f"heads_held {self.heads_held} are not the heads of "
                f"groups_held {self.groups_held} ({per} a group)")
        return h, g


@dataclass(frozen=True)
class EVAttentionParam:
    """EVA as EvaByte runs it (arXiv:2302.04542, simplified for causal
    byte-level modelling): `num_heads` heads of `head_dim` with as many
    key/value heads, rotary over the whole head on contiguous halves, no
    q/k norm. Position i reads, under ONE softmax, the keys of its own
    aligned window of `window_size` positions up to itself, and one learned
    summary of every `chunk_size` positions of the windows before: key
    mean(k) + mu, value sum_j softmax_j(phi . k_j / sqrt(head_dim)) v_j, mu
    and phi a vector of `head_dim` a head. Rows no longer than a window are
    plain causal attention; longer ones are whole windows."""

    num_heads: int = 0
    head_dim: int = 0
    window_size: int = 0
    chunk_size: int = 0
    rope_theta: float = 10000.0
    std: float = 0.02


@dataclass(frozen=True)
class ShortConvParam:
    """A gated short convolution (LFM2's operator): [B | C | z] = x W_in
    (d -> 3d), a depthwise causal convolution of `taps` taps a channel over
    B * z (zeros before position 0, no bias), (C * that) W_out (d -> d). No
    nonlinearity."""

    taps: int = 3
    std: float = 0.02


@dataclass(frozen=True)
class GatedMLPParam:
    """SwiGLU: (silu(x W_g) * x W_u) W_d."""

    intermediate_size: int = 0
    std: float = 0.02


@dataclass(frozen=True)
class MoEParam:
    """A routed-expert layer that holds a SHARE of its experts (expert
    parallelism's view from one chip): the router scores all
    `n_routed_experts`, the layer holds the weights of experts
    `experts_held[0]` .. `experts_held[0] + experts_held[1] - 1` only and
    sums over the chosen experts it holds; what the absent ones would add is
    left out. `capacity_factor`: room for the slots that land here, as a
    multiple of the even share (tokens x top-k x held / routed); None = room
    for every slot that can land here, so none is ever dropped.

    `latent_size` (LatentMoE): the routed experts work in a latent narrower
    than the stream -- x W_down (d -> latent) before the dispatch, W_up
    (latent -> d) after the combine; the router and the shared expert read
    the stream itself. `expert_form`: "swiglu", down(silu(gate x) up x),
    "reglu", down(relu(gate x) up x), or "relu2", down(relu(up x)^2): two
    products a slot, no gate. `score_func`: "sigmoid" -- sigmoid scores, the
    top k of score + bias, the chosen scores normalised and scaled
    (`noaux_tc`) -- or "softmax_topk": the top k of the router's logits
    themselves and a softmax over the chosen k alone, with no bias (the layer
    stores no `router_bias`), no normalisation left to do and no scaling. A
    layer with a second bottom routes on it and feeds its experts the first
    (a router that reads the stream before the attention its experts follow).
    The shared
    expert's width is `shared_intermediate_size` where given (else
    `n_shared_experts` x `intermediate_size`), of which the layer builds
    the columns `shared_columns` (first, count) where given: a share whose
    result is its own part of the sum over all columns."""

    n_routed_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)  # (first, count)
    num_experts_per_tok: int = 1
    intermediate_size: int = 0
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    #: added to the sum of the chosen scores before the division
    #: (`norm_topk_prob`): the DeepSeek-V3 family's code adds 1e-20, LFM2's 1e-6
    norm_topk_eps: float = 1e-20
    #: group-limited choice (DeepSeek-V3's `noaux_tc`): the routed experts
    #: in `n_group` equal groups, a group's score the sum of its two largest
    #: entries of score + bias, the `topk_group` best groups kept and the top
    #: k taken among their experts; 1 / 1: one group, the plain top k
    n_group: int = 1
    topk_group: int = 1
    capacity_factor: Optional[float] = None
    std: float = 0.02
    latent_size: Optional[int] = None
    expert_form: str = "swiglu"  # swiglu | reglu | relu2
    score_func: str = "sigmoid"  # sigmoid | softmax_topk
    shared_intermediate_size: Optional[int] = None
    shared_columns: Optional[Tuple[int, int]] = None

    def shared_width(self) -> int:
        """Columns of the shared expert this layer builds (0: none)."""
        if not self.n_shared_experts:
            return 0
        if self.shared_columns is not None:
            return self.shared_columns[1]
        return (self.shared_intermediate_size
                or self.n_shared_experts * self.intermediate_size)


@dataclass(frozen=True)
class MTPParam:
    """One multi-token-prediction module (DeepSeek-V3's form): the last
    layer's output and the next token's embedding, each normed, projected
    from 2 x d to d, then one expert block and a norm of its own."""

    attention: MLAttentionParam = field(default_factory=MLAttentionParam)
    moe: MoEParam = field(default_factory=MoEParam)
    eps: float = 1e-5
    std: float = 0.02


@dataclass(frozen=True)
class LayerSpec:
    name: str
    type: str
    bottoms: Tuple[str, ...] = ()
    tops: Tuple[str, ...] = ()
    params: Tuple[ParamSpec, ...] = ()
    include_phase: Optional[str] = None  # None = both; "TRAIN" | "TEST"
    conv: Optional[ConvolutionParam] = None
    pool: Optional[PoolingParam] = None
    lrn: Optional[LRNParam] = None
    inner_product: Optional[InnerProductParam] = None
    dropout: Optional[DropoutParam] = None
    accuracy: Optional[AccuracyParam] = None
    loss: Optional[LossParam] = None
    eltwise: Optional[EltwiseParam] = None
    embed: Optional[EmbedParam] = None
    rmsnorm: Optional[RMSNormParam] = None
    mla: Optional[MLAttentionParam] = None
    gqa: Optional[GQAttentionParam] = None
    kda: Optional[KDAttentionParam] = None
    eva: Optional[EVAttentionParam] = None
    mamba2: Optional[Mamba2Param] = None
    shortconv: Optional[ShortConvParam] = None
    gated_mlp: Optional[GatedMLPParam] = None
    moe: Optional[MoEParam] = None
    mtp: Optional[MTPParam] = None
    #: this layer has no parameters of its own and runs on those of the
    #: layer named here (Caffe shares blobs by `param { name }`): a second
    #: head on the one output matrix, a second lookup in the one table
    param_from: Optional[str] = None
    #: consecutive layers that carry the same tag are one recomputation
    #: block: in training the backward pass keeps the block's inputs and the
    #: values its layers' implementations name as dear to compute again
    #: (`seq_layers.KEPT_NAMES`: an attention core's output and softmax
    #: statistics, a dense SwiGLU's two input products, an InnerProduct's
    #: result -- a head's logits), and computes the rest of its insides again
    #: (`jax.checkpoint`, with a policy only where something is named)
    block: Optional[str] = None


@dataclass(frozen=True)
class InputSpec:
    """A declared net input (Caffe `input:` + `input_shape` blocks).

    Shape is the Caffe-declared shape: (N, C, H, W) for images, (N, D) for
    tabular/labels. Batch dim included, as in the reference prototxts.
    """

    name: str
    shape: Tuple[int, ...]
    dtype: str = "float32"


@dataclass(frozen=True)
class NetSpec:
    name: str
    inputs: Tuple[InputSpec, ...]
    layers: Tuple[LayerSpec, ...]

    def input_names(self) -> List[str]:
        return [i.name for i in self.inputs]

    def layer_by_name(self, name: str) -> LayerSpec:
        for l in self.layers:
            if l.name == name:
                return l
        raise KeyError(name)

    def layers_for_phase(self, phase: str) -> List[LayerSpec]:
        return [
            l
            for l in self.layers
            if l.include_phase is None or l.include_phase == phase
        ]

    def replace(self, **kw) -> "NetSpec":
        return dataclasses.replace(self, **kw)


# Layer types that carry trainable parameters.
PARAMETRIC_LAYER_TYPES = ("Convolution", "InnerProduct", "Embed", "RMSNorm",
                          "MLAttention", "GQAttention", "KDAttention",
                          "EVAttention", "Mamba2", "ShortConv",
                          "GatedMLP", "MoE", "MTP")


def validate(spec: NetSpec) -> None:
    """Structural validation: every bottom must be produced before use."""
    available = set(spec.input_names())
    seen = set()
    for l in spec.layers:
        if l.param_from is not None and l.param_from not in seen:
            raise ValueError(
                f"layer {l.name!r}: param_from {l.param_from!r} names no "
                f"earlier layer")
        seen.add(l.name)
        for b in l.bottoms:
            if b not in available:
                raise ValueError(
                    f"layer {l.name!r}: bottom {b!r} not produced by any "
                    f"earlier layer or input (have {sorted(available)})"
                )
        available.update(l.tops)
