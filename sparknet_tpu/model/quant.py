"""Weight-only int8 quantization for the serve path.

Per-channel symmetric absmax quantization of the parametric layers'
weight matrices (Convolution HWIO, InnerProduct (in, out) — the output
channel is the LAST axis in both layouts, so one rule covers both):

    scale[o] = max(|w[..., o]|) / 127        (per output channel)
    w_q[..., o] = round(w[..., o] / scale[o])  in int8

Symmetric means the zero point is identically 0 and is elided from the
stored pytree — the scale vector IS the whole side-car. Dequantization at
use is `w_q * scale` cast to the activation dtype (bfloat16 by default:
int8 weights at rest + bf16 activations in flight, the Pope et al. 2022
serving recipe); XLA fuses the dequant multiply into the consuming
conv/matmul, so the weight never materializes in f32.

This is a SERVING transform: `ModelManager` quantizes at checkpoint load
time (`QuantConfig` on ServeConfig) and gates the install on a parity
canary against the f32 forward — training state never sees these leaves.
Biases stay in f32 (they're O(channels) bytes and add directly into the
accumulator).

Quantized layer params look like `{"w_q": int8[..., O], "w_scale":
f32[O], "b": f32[O]}` in place of `{"w": f32[..., O], "b": ...}`; the
layer impls in `model/layers.py` dispatch on the `w_q` key, so a params
pytree is self-describing and the f32 path is untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

#: layer param trees carrying one of these keys are quantized leaves
QUANT_KEYS = ("w_q", "w_scale")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Knobs for the quantized serve path (ServeConfig.quant surfaces
    these; `mode="int8"` is the only wire format today).

    act: activation dtype for quantized forwards — "bfloat16" (default:
    halves host->device input bytes and runs the MXU fast path) or
    "float32" (debug: isolates weight-quant error from activation
    rounding).

    rtol/atol: the calibrated parity tolerance the load-time canary
    enforces between the quantized and f32 forwards on the same batch
    (the PR 7 Pallas-pin pattern, promoted from test-time to load-time:
    a quantization whose outputs drift past this NEVER SERVES — the
    manager rolls back and rejects the checkpoint). Defaults calibrated
    on the zoo serve models' prob/logit outputs under int8+bf16
    (tests/test_quant.py pins them per model; worst measured drift is
    ~0.05 on fresh-init lenet probs — near-uniform logits are the
    adversarial case — while a corrupted scale lands >0.3, so the gate
    separates cleanly)."""

    mode: str = "int8"
    act: str = "bfloat16"
    rtol: float = 0.05
    atol: float = 0.08

    def __post_init__(self) -> None:
        # the ElasticConfig rule: a typo'd knob fails at config
        # construction, not at the first forward's trace
        if self.mode != "int8":
            raise ValueError(f"unknown quant mode {self.mode!r}: "
                             f"expected 'int8'")
        if self.act not in ("bfloat16", "float32"):
            raise ValueError(f"unknown quant act dtype {self.act!r}: "
                             f"expected 'bfloat16' or 'float32'")
        if self.rtol < 0 or self.atol < 0:
            raise ValueError("quant rtol/atol must be >= 0")

    def act_dtype(self):
        return jnp.bfloat16 if self.act == "bfloat16" else jnp.float32

    @staticmethod
    def coerce(v: Any) -> Optional["QuantConfig"]:
        """ServeConfig/CLI sugar: None, a mode string ("int8"), a dict of
        fields, or a QuantConfig -> QuantConfig | None."""
        if v is None or isinstance(v, QuantConfig):
            return v
        if isinstance(v, str):
            return QuantConfig(mode=v)
        if isinstance(v, dict):
            return QuantConfig(**v)
        raise ValueError(f"quant must be None, a mode string, a dict, or "
                         f"a QuantConfig (got {type(v).__name__})")


def quantize_leaf(w: np.ndarray) -> Dict[str, jnp.ndarray]:
    """One weight tensor -> {"w_q": int8, "w_scale": f32 per out channel}.
    The scale floor keeps an all-zero channel from dividing by zero (its
    quantized rows are exactly zero either way)."""
    w = np.asarray(w, dtype=np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = np.maximum(absmax / 127.0, np.float32(1e-12)).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return {"w_q": jnp.asarray(q), "w_scale": jnp.asarray(scale)}


def quantize_params(params: Dict[str, Dict[str, Any]],
                    cfg: Optional[QuantConfig] = None
                    ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """A JaxNet params pytree -> its weight-only-quantized twin. Every
    >=2-D "w" leaf (conv HWIO / inner-product (in,out)) becomes the
    (w_q, w_scale) pair; biases and 1-D leaves ride along in f32. The
    input pytree is not mutated."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for lname, lp in params.items():
        out[lname] = {}
        for pname, leaf in lp.items():
            if pname == "w" and np.ndim(leaf) >= 2:
                out[lname].update(quantize_leaf(np.asarray(leaf)))
            else:
                out[lname][pname] = jnp.asarray(leaf)
    return out


def is_quantized(params: Dict[str, Dict[str, Any]]) -> bool:
    """True when any layer of the pytree carries quantized leaves."""
    return any("w_q" in lp for lp in params.values())


def dequantize_params(qparams: Dict[str, Dict[str, Any]]
                      ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """The f32 reconstruction (tests / export): w = w_q * w_scale. NOT
    the serving path — layers dequantize lazily inside the forward."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for lname, lp in qparams.items():
        out[lname] = {}
        if "w_q" in lp:
            out[lname]["w"] = (lp["w_q"].astype(jnp.float32)
                               * lp["w_scale"])
        for pname, leaf in lp.items():
            if pname not in QUANT_KEYS:
                out[lname][pname] = leaf
    return out
