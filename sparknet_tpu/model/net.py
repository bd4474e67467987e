"""NetSpec -> pure JAX function compiler.

The reference's equivalent is Caffe's native net builder (`FloatNet` built from
a `NetParameter`, wrapped at reference `libs/CaffeNet.scala:28-68`). Here the
"net" is data: a `CompiledNet` holds
  - `init_params(key) -> params` (pytree: {layer_name: {"w": ..., "b": ...}})
  - `apply(params, batch, train=, rng=) -> {blob_name: array}`
and everything downstream (`jit`, `grad`, `shard_map`) composes functionally.

Layout: 4D inputs are declared NCHW in prototxt but consumed NHWC on device;
`CompiledNet.input_shapes` reports the NHWC shapes the caller must feed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import LAYER_IMPLS, ApplyCtx, Params
from .quant import QuantConfig
from .spec import InputSpec, LayerSpec, NetSpec, validate

PyTree = Dict[str, Params]

#: CompiledNet.compile memo: identical NetSpecs (frozen, hashable) compile
#: once per process — the spec-level half of the compile-cache story
_SPEC_MEMO: Dict[NetSpec, "CompiledNet"] = {}


def _recompute_blocks(layers) -> List[list]:
    """`layers` cut into runs: consecutive layers with one `block` tag
    together, every untagged layer alone."""
    runs: List[list] = []
    for layer in layers:
        if (runs and layer.block is not None
                and runs[-1][0].block == layer.block):
            runs[-1].append(layer)
        else:
            runs.append([layer])
    return runs


def _kept_names(layers) -> Tuple[str, ...]:
    """The names the types of these layers (one recomputation block's) put
    on values the block is to keep for the backward pass
    (`seq_layers.KEPT_NAMES`)."""
    from .seq_layers import KEPT_NAMES
    return tuple(dict.fromkeys(
        n for l in layers for n in KEPT_NAMES.get(l.type, ())))


@functools.cache
def _keep(names: Tuple[str, ...]):
    """The policy that keeps the values so named, ONE object a set of names:
    jax memoises a block's partial evaluation by the policy's identity, so
    blocks that share the object share the functions their jitted parts
    lower to (a policy a block: Nemotron's round lowered to 868 functions
    and 3.8 MB of text where this gives 154 and 2.3; PERF.md section 6,
    PR 52)."""
    return jax.checkpoint_policies.save_only_these_names(*names)


def _to_nhwc_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    if len(shape) == 4:
        n, c, h, w = shape
        return (n, h, w, c)
    return shape


_DTYPES = {"float32": jnp.float32, "int32": jnp.int32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class CompiledNet:
    spec: NetSpec
    #: blob name -> NHWC device shape for every net input
    input_shapes: Dict[str, Tuple[int, ...]]
    #: blob name -> dtype string
    input_dtypes: Dict[str, str]
    #: blob name -> shape for every top produced in TRAIN phase (() = scalar)
    blob_shapes: Dict[str, Tuple[int, ...]]
    #: names of output blobs (tops never consumed by a later layer), per phase
    output_names: Tuple[str, ...]

    # -- construction -------------------------------------------------------

    @staticmethod
    def compile(spec: NetSpec) -> "CompiledNet":
        # stamped as a compile event (obs/device.py): every spec compile
        # lands in the process-wide record, so jit-cache churn driven by
        # repeated net construction is scrapeable, not invisible.
        # Identical specs (frozen dataclasses, hashable) return the
        # memoized CompiledNet — router lanes, elastic rebuilds, and
        # serve hot-swap retraces of the same architecture skip
        # re-validation, and the event records cache_hit="true". A memo
        # MISS stamps cache_hit=None ("unknown"): spec compilation is
        # pure Python — the persistent XLA cache neither applies to it
        # nor should claim it — so its real duration still lands in the
        # compile-seconds histogram while never counting as a fresh-XLA
        # miss against the warm-replica acceptance.
        import time as _time

        from ..obs.device import note_compile
        try:
            cached = _SPEC_MEMO.get(spec)
        except TypeError:  # unhashable spec (hand-built with lists)
            cached = None
        if cached is not None:
            note_compile("net", 0.0, cache_hit=True)
            return cached
        t0 = _time.perf_counter()
        net = CompiledNet._compile(spec)
        note_compile("net", _time.perf_counter() - t0)
        try:
            _SPEC_MEMO[spec] = net
        except TypeError:
            pass
        return net

    @staticmethod
    def _compile(spec: NetSpec) -> "CompiledNet":
        validate(spec)
        input_shapes = {i.name: _to_nhwc_shape(i.shape) for i in spec.inputs}
        input_dtypes = {i.name: i.dtype for i in spec.inputs}
        blob_shapes: Dict[str, Tuple[int, ...]] = dict(input_shapes)
        consumed: set = set()
        produced: List[str] = list(input_shapes)
        for layer in spec.layers:
            if layer.type not in LAYER_IMPLS:
                raise ValueError(f"unsupported layer type {layer.type!r} "
                                 f"(layer {layer.name!r})")
            _, _, infer = LAYER_IMPLS[layer.type]
            in_shapes = tuple(blob_shapes[b] for b in layer.bottoms)
            out_shapes = infer(layer, in_shapes)
            for t, s in zip(layer.tops, out_shapes):
                blob_shapes[t] = s
                produced.append(t)
            consumed.update(b for b in layer.bottoms if b not in layer.tops)
        outputs = tuple(
            dict.fromkeys(t for t in produced
                          if t not in consumed and t not in input_shapes))
        return CompiledNet(spec=spec, input_shapes=input_shapes,
                           input_dtypes=input_dtypes, blob_shapes=blob_shapes,
                           output_names=outputs)

    # -- parameters ---------------------------------------------------------

    def init_params(self, key: jax.Array) -> PyTree:
        params: PyTree = {}
        shapes: Dict[str, Tuple[int, ...]] = dict(self.input_shapes)
        for layer in self.spec.layers:
            init, _, infer = LAYER_IMPLS[layer.type]
            in_shapes = tuple(shapes[b] for b in layer.bottoms)
            if init is not None and layer.param_from is None:
                key, sub = jax.random.split(key)
                params[layer.name] = init(sub, layer, in_shapes)
            for t, s in zip(layer.tops, infer(layer, in_shapes)):
                shapes[t] = s
        return params

    def param_layers(self) -> List[str]:
        return [l.name for l in self.spec.layers
                if LAYER_IMPLS[l.type][0] is not None
                and l.param_from is None]

    def counter_blobs(self) -> Dict[str, Tuple[str, ...]]:
        """{blob: the names of its entries} for every top that is a
        layer's vector of counters (an expert layer's, an MTP module's, a
        Mamba-2 mixer's under document ids):
        what a trainer sums over a round's steps and returns with the
        round's scalars. {} for a net of layers that count nothing."""
        from .seq_layers import COUNTER_TOPS
        return {l.tops[COUNTER_TOPS[l.type][0]]: COUNTER_TOPS[l.type][1]
                for l in self.spec.layers_for_phase("TRAIN")
                if l.type in COUNTER_TOPS
                and len(l.tops) > COUNTER_TOPS[l.type][0]}

    def kept_makers(self) -> Dict[str, str]:
        """{name: what marks the device ops that make its values (a Pallas
        kernel's name, a named scope: `seq_layers.KEPT_MAKERS`)} for every
        name a recomputation block of this net keeps for the backward pass
        (`seq_layers.KEPT_NAMES` of the block's layer types). {} for a net
        without blocks, or whose blocks' layers name nothing."""
        from .seq_layers import KEPT_MAKERS
        return {n: KEPT_MAKERS[n] for n in _kept_names(
            l for l in self.spec.layers_for_phase("TRAIN")
            if l.block is not None) if n in KEPT_MAKERS}

    def attention_scopes(self) -> Tuple[Dict[str, str], int]:
        """({layer type: the scope under such a layer's own that holds its
        attention}, the positions those layers attend over) for the
        types of this net's layers in `seq_layers.ATTENTION_SCOPES`; ({}, 0)
        for a net without any."""
        from .seq_layers import ATTENTION_SCOPES
        layers = [l for l in self.spec.layers_for_phase("TRAIN")
                  if l.type in ATTENTION_SCOPES]
        return ({l.type: ATTENTION_SCOPES[l.type] for l in layers},
                max((self.blob_shapes[l.bottoms[0]][1] for l in layers),
                    default=0))

    def delta_scopes(self) -> Tuple[Dict[str, str], Tuple[str, ...]]:
        """({layer type: the scope under such a layer's own that holds its
        delta rule}, the names those layers' blocks keep for the backward
        pass) for the types of this net's layers in
        `seq_layers.DELTA_SCOPES`; ({}, ()) for a net without any."""
        from .seq_layers import DELTA_SCOPES
        layers = [l for l in self.spec.layers_for_phase("TRAIN")
                  if l.type in DELTA_SCOPES]
        return ({l.type: DELTA_SCOPES[l.type] for l in layers},
                _kept_names(l for l in layers if l.block is not None))

    def ssd_scopes(self) -> Dict[str, str]:
        """{layer type: the scope under such a layer's own that holds its
        state-space scan} for the types of this net's layers in
        `seq_layers.SSD_SCOPES`; {} for a net without any."""
        from .seq_layers import SSD_SCOPES
        return {l.type: SSD_SCOPES[l.type]
                for l in self.spec.layers_for_phase("TRAIN")
                if l.type in SSD_SCOPES}

    def ssd_kernel_shape(self, layer: LayerSpec) -> Dict[str, int]:
        """What `ops.ssd.ssd` walks for a Mamba-2 layer of this net where a
        Pallas call may run: {"chunk": positions a chunk, "heads_per_program":
        the heads of a group one kernel program works, "programs_per_group"};
        {} where the layer's shape is not the kernels' (the `jnp` form)."""
        from ..ops import ssd as ssd_ops
        p = layer.mamba2
        heads, groups = p.held()
        q = min(p.chunk_size, self.blob_shapes[layer.bottoms[0]][1])
        at_once = ssd_ops.program_heads(q, heads // groups, p.head_dim,
                                        p.state_size)
        return {"chunk": q, "heads_per_program": at_once,
                "programs_per_group": heads // groups // at_once} if at_once else {}

    def ssd_kernels(self) -> Dict[str, Dict[str, int]]:
        """{layer: `ssd_kernel_shape` of it, and "documents": 1 where the
        layer is fed document ids (its scan, taps and counter cut by them)}
        for this net's Mamba-2 layers; {} for a net without any."""
        return {l.name: {**self.ssd_kernel_shape(l),
                         "documents": int(len(l.bottoms) > 1)}
                for l in self.spec.layers_for_phase("TRAIN")
                if l.type == "Mamba2"}

    def eva_scopes(self) -> Tuple[Dict[str, Tuple[str, str]], Optional[dict]]:
        """({layer type: the scopes under such a layer's own that hold its
        chunk summaries and its core}, what one such layer's core is given:
        `seq_layers.eva_core_blocks`) for the types of this net's layers in
        `seq_layers.EVA_SCOPES`; ({}, None) for a net without any."""
        from .seq_layers import EVA_SCOPES, eva_core_blocks
        layers = [l for l in self.spec.layers_for_phase("TRAIN")
                  if l.type in EVA_SCOPES]
        if not layers:
            return {}, None
        return ({l.type: EVA_SCOPES[l.type] for l in layers},
                eva_core_blocks(layers[0].eva,
                                self.blob_shapes[layers[0].bottoms[0]][1]))

    def window_scopes(self) -> Tuple[Dict[str, str], Dict[str, dict]]:
        """({layer type: the scope under such a layer's own that holds its
        core}, {layer: {"window": its window or None, "blocks_visited",
        "blocks_causal": `seq_layers.gqa_core_blocks`}}) for this net's
        layers of `seq_layers.WINDOW_SCOPES`' types, where at least one of
        them attends under a window; ({}, {}) for a net in which none
        does."""
        from .seq_layers import WINDOW_SCOPES, gqa_core_blocks
        layers = [l for l in self.spec.layers_for_phase("TRAIN")
                  if l.type in WINDOW_SCOPES]
        if all(l.gqa.window is None for l in layers):
            return {}, {}
        return ({l.type: WINDOW_SCOPES[l.type] for l in layers},
                {l.name: {"window": l.gqa.window, **gqa_core_blocks(
                    l.gqa, self.blob_shapes[l.bottoms[0]][1])}
                 for l in layers})

    def routing_scopes(self) -> Tuple[Tuple[str, ...], int]:
        """(the scopes under which this net's expert layers choose experts
        and move rows to and from them, the width of the rows they move:
        the stream's, or the latent's where the experts work in one):
        `seq_layers.ROUTING_SCOPES` for a net with a layer of one of
        `seq_layers.COUNTER_TOPS`' types that count routed slots; ((), 0)
        for a net without any."""
        from .seq_layers import COUNTER_TOPS, MOE_COUNTERS, ROUTING_SCOPES
        widths = [(l.moe and l.moe.latent_size)
                  or self.blob_shapes[l.bottoms[0]][-1]
                  for l in self.spec.layers_for_phase("TRAIN")
                  if COUNTER_TOPS.get(l.type, (0, ()))[1] == MOE_COUNTERS]
        return (ROUTING_SCOPES, widths[0]) if widths else ((), 0)

    # -- execution ----------------------------------------------------------

    def apply(self, params: PyTree, batch: Dict[str, jnp.ndarray], *,
              train: bool = False, rng: Optional[jax.Array] = None,
              phase: Optional[str] = None, tp_axis: Optional[str] = None,
              tp_size: int = 1, interpret: bool = False,
              quant: Optional[QuantConfig] = None
              ) -> Dict[str, jnp.ndarray]:
        """Run the net. `batch` maps input blob names to NHWC arrays.

        Returns every blob produced (inputs excluded), so callers can read
        hidden activations by name — parity with the reference's
        `forward(rowIt, dataBlobNames)` path (`libs/CaffeNet.scala:101-107`)
        used by FeaturizerApp.

        tp_axis/tp_size: run tensor-parallel (inside shard_map over that
        mesh axis) with column-sharded InnerProduct weights — see ApplyCtx.

        interpret: run Pallas kernels under the Pallas interpreter (the
        CPU parity-test mode of the layer path the TPU runs; ApplyCtx).

        quant: serving-side weight-only quantization config (model/
        quant.py). `params` may then hold int8 `w_q` + per-channel
        `w_scale` leaves in place of `w` for Convolution/InnerProduct
        layers; the layer impls dequantize at use into the quant
        activation dtype. With f32 `w` leaves this knob changes nothing —
        the f32 path is untouched by construction.
        """
        phase = phase or ("TRAIN" if train else "TEST")
        ctx = ApplyCtx(train=train, rng=rng, tp_axis=tp_axis,
                       tp_size=tp_size, interpret=interpret,
                       quant=quant)
        blobs: Dict[str, jnp.ndarray] = dict(batch)
        all_tops = set()

        def run(layers, params, blobs) -> Dict[str, jnp.ndarray]:
            """The tops `layers` produce, in order, from `blobs`."""
            blobs, tops = dict(blobs), {}
            for layer in layers:
                _, apply_fn, _ = LAYER_IMPLS[layer.type]
                inputs = tuple(blobs[b] for b in layer.bottoms)
                # the scope carries the layer's TYPE and NAME into every
                # op's metadata (forward, and under transpose(jvp(...))
                # backward): obs.device.program_report reads them back from
                # the compiled text, so a reader of a device trace needs no
                # model table
                with jax.named_scope(f"{layer.type}/{layer.name}"):
                    outputs = apply_fn(
                        layer, params.get(layer.param_from or layer.name),
                        inputs, ctx)
                for t, v in zip(layer.tops, outputs):
                    blobs[t] = tops[t] = v
            return tops

        for layers in _recompute_blocks(self.spec.layers_for_phase(phase)):
            if train and layers[0].block is not None:
                # one recomputation block: the backward pass keeps its
                # inputs, its parameters and the values its layers name
                # (an attention core's output and statistics, a head's
                # logits), and computes the rest of it again; a block that
                # names nothing gets the bare `jax.checkpoint`
                needs = {b: blobs[b] for l in layers for b in l.bottoms
                         if b in blobs}
                owners = {l.param_from or l.name for l in layers}
                names = _kept_names(layers)
                policy = _keep(names) if names else None
                tops = jax.checkpoint(functools.partial(run, layers),
                                      policy=policy)(
                    {k: v for k, v in params.items() if k in owners}, needs)
            else:
                tops = run(layers, params, blobs)
            blobs.update(tops)
            all_tops.update(tops)
        for name in batch:
            if name not in all_tops:
                blobs.pop(name, None)
        return blobs

    def loss_fn(self, loss_blob: str = "loss",
                tp_axis: Optional[str] = None, tp_size: int = 1,
                interpret: bool = False):
        """Returns `f(params, batch, rng) -> (loss, aux_blobs)` for jax.grad."""

        def f(params, batch, rng=None):
            blobs = self.apply(params, batch, train=True, rng=rng,
                               tp_axis=tp_axis, tp_size=tp_size,
                               interpret=interpret)
            return blobs[loss_blob], blobs

        return f

    def example_batch(self, key: Optional[jax.Array] = None,
                      batch_size: Optional[int] = None) -> Dict[str, jnp.ndarray]:
        """Synthesize a correctly-shaped random batch (for tests/AOT warmup)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        batch = {}
        for name, shape in self.input_shapes.items():
            if batch_size is not None:
                shape = (batch_size,) + tuple(shape[1:])
            key, sub = jax.random.split(key)
            if self.input_dtypes[name] == "int32":
                batch[name] = jax.random.randint(sub, shape, 0, 10, jnp.int32)
            else:
                batch[name] = jax.random.normal(sub, shape, jnp.float32)
        return batch
