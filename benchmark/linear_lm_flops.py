"""`lm_flops.py`'s count for a sequence model with linear-attention layers:
Kimi Delta Attention (`kda`: the gated delta rule with a per-channel decay)
among latent attention whose queries are projected directly (`mla` with
`q_rank` 0), as the configuration's reference layer table names them. The
terms of the kinds `lm_flops.py` knows (`mla`, `mlp`, `moe`, `head`) are its
own, loaded from the file beside this one; this file adds the direct query
projection and the head-wise gate those terms leave out, the new kind's
products, and the operations and bytes of the one place that is its alone:
the delta rule itself.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward). For the delta rule the
algorithm is the RECURRENCE: a position and head, the state's product with
the key, the rank-one update and the state's product with the query, 3 x dk
x dv MACs (the chunked form that runs does about twice that at a chunk of
64, and none of the excess counts). Not counted: anything recomputed, norms,
softmax, rotary, the decays, routing, the optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lm_flops.py")
    name = "bench_linear_lm_flops_base"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


TRAIN_FWD_MULT = _lm().TRAIN_FWD_MULT
even_slots_per_row = _lm().even_slots_per_row


def _kda_macs(a: dict) -> float:
    """Projection and tap MACs a position of one Kimi Delta Attention layer:
    q, k, v and the decay d -> heads x head_dim each, the three short
    convolutions, the writing strength and the output gate d -> heads, the
    output projection."""
    f = a["heads"] * a["head_dim"]
    return a["d"] * 4.0 * f + 3.0 * f * a["taps"] + 2.0 * a["d"] * a["heads"] \
        + f * a["d"]


def _delta_macs(a: dict) -> float:
    """The recurrence's MACs a position of one layer: S k, k u^T and S q, a
    head."""
    return a["heads"] * 3.0 * a["head_dim"] * a["head_dim"]


def _direct_mla_macs(a: dict) -> float:
    """What `lm_flops._mla_macs` leaves out of a latent attention without a
    query latent: the direct projection d -> heads x (nope + rope), and the
    head-wise output gate d -> heads."""
    return a["d"] * a["heads"] * (a["nope"] + a["rope"] + 1.0)


def forward_macs_per_row(layers, positions: int, slots_per_row: dict) -> dict:
    """`lm_flops.forward_macs_per_row` with the new kind: its projections
    under "dense", the rule under "delta"; a latent attention with `q_rank`
    0 gains its direct query projection and gate."""
    macs = dict(_lm().forward_macs_per_row(layers, positions, slots_per_row),
                delta=0.0)
    for _, kind, a in layers:
        if kind == "kda":
            macs["dense"] += positions * _kda_macs(a)
            macs["delta"] += positions * _delta_macs(a)
        elif kind == "mla" and not a["q_rank"]:
            macs["dense"] += positions * _direct_mla_macs(a)
    return macs


def train_flops_per_row(layers, positions: int, slots_per_row=None) -> float:
    macs = forward_macs_per_row(
        layers, positions, slots_per_row or even_slots_per_row(layers, positions))
    return 2.0 * TRAIN_FWD_MULT * sum(macs.values())


def kda_delta_step_cost(layers, rows: int, positions: int, itemsize: int,
                        chunk: int = 64) -> dict:
    """Operations and the least HBM bytes of the delta rules of ONE training
    step, all layers together. A position and layer, forward: q, k, v read
    and o written (`itemsize` an element), the log-decay and the writing
    strength read (float32); backward: twice that (the operands read again,
    their gradients written) and one float32 state a chunk of `chunk`
    positions written and read back."""
    ops = nbytes = 0.0
    for _, kind, a in layers:
        if kind == "kda":
            h, hd = a["heads"], a["head_dim"]
            forward = positions * h * (hd * (4.0 * itemsize + 4.0) + 4.0)
            states = positions / chunk * h * hd * hd * 4.0 * 2.0
            ops += rows * positions * _delta_macs(a)
            nbytes += rows * (3.0 * forward + states)
    return {"ops": 2.0 * TRAIN_FWD_MULT * ops, "bytes": nbytes}
