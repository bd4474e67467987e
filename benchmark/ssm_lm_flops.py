"""The benchmark's count of what a training step needs of a sequence model
built of one mixer a layer: Mamba-2 state-space mixers (`mamba2`),
grouped-query attention (`gqa`) and LatentMoE (`latent_moe`: relu^2 experts
in a latent narrower than the stream), an untied head that a
multi-token-prediction module (`eh_proj` and the `mtp<j>_*` layers) uses a
second time -- as the configuration's reference layer table names them, AT
THE HEADS, COLUMNS AND EXPERTS THIS CHIP HOLDS. The attention's terms are
`hybrid_lm_flops.py`'s, loaded from the file beside this one; this file adds
the other kinds' products and the operations and bytes of the two places
that are theirs alone: the scan, and the routed experts' two grouped
products.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward). For the scan the algorithm is
the RECURRENCE: a position and head, the state's update x (x) B and its
product with C, 2 x head_dim x state MACs (the chunked form that runs does
more, and none of the excess counts). Not counted: anything recomputed,
norms, softmax, the taps' bias, the decays, the gates, the skip, routing,
the optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _hybrid():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hybrid_lm_flops.py")
    name = "bench_ssm_lm_flops_base"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


TRAIN_FWD_MULT = _hybrid().TRAIN_FWD_MULT


def _mamba_macs(a: dict) -> float:
    """Projection and tap MACs a position of one Mamba-2 mixer: d -> gate, x,
    B, C and time steps; the taps over x, B and C; heads x head_dim -> d."""
    inner = a["heads"] * a["head_dim"]
    conv = inner + 2.0 * a["groups"] * a["state"]
    return a["d"] * (inner + conv + a["heads"]) + conv * a["taps"] + inner * a["d"]


def _ssd_macs(a: dict) -> float:
    """The recurrence's MACs a position of one mixer: x (x) B into the state
    and the state's product with C, a head."""
    return a["heads"] * 2.0 * a["head_dim"] * a["state"]


def _latent_dense_macs(a: dict) -> float:
    """What every position pays in one LatentMoE layer: the router, the two
    latent projections and the held columns of the shared expert (two
    products)."""
    return a["d"] * (a["routed"] + 2.0 * a["latent"] + 2.0 * a["shared"])


def _latent_expert_macs(a: dict) -> float:
    """MACs a routed slot: up and down, in the latent."""
    return 2.0 * a["latent"] * a["width"]


def even_slots_per_row(layers, positions: int) -> dict:
    """Routed slots a row that land here if the router spreads them evenly."""
    return {name: positions * a["k"] * a["held"] / a["routed"]
            for name, kind, a in layers if kind == "latent_moe"}


def forward_macs_per_row(layers, positions: int, slots_per_row: dict) -> dict:
    """{"dense": projections, taps, routers, latent projections, shared
    experts, the MTP projection and the head once a use; "core": the
    attention cores; "ssd": the scans; "experts": the routed experts} MACs a
    row, forward. `slots_per_row`: {expert layer: routed slots that land on
    this chip a row} (a counter's reading, or `even_slots_per_row`)."""
    hybrid = _hybrid()
    macs = {"dense": 0.0, "core": 0.0, "ssd": 0.0, "experts": 0.0}
    head_uses = 1 + any(kind == "eh_proj" for _, kind, _ in layers)
    for name, kind, a in layers:
        if kind == "mamba2":
            macs["dense"] += positions * _mamba_macs(a)
            macs["ssd"] += positions * _ssd_macs(a)
        elif kind == "gqa":
            macs["dense"] += positions * hybrid._gqa_macs(a)
            macs["core"] += hybrid._gqa_core_macs(a, positions)
        elif kind == "latent_moe":
            macs["dense"] += positions * _latent_dense_macs(a)
            macs["experts"] += slots_per_row[name] * _latent_expert_macs(a)
        elif kind == "head":
            macs["dense"] += head_uses * positions * a["d"] * a["vocab"]
        elif kind == "eh_proj":
            macs["dense"] += positions * 2.0 * a["d"] * a["d"]
    return macs


def train_flops_per_row(layers, positions: int, slots_per_row=None) -> float:
    macs = forward_macs_per_row(
        layers, positions, slots_per_row or even_slots_per_row(layers, positions))
    return 2.0 * TRAIN_FWD_MULT * sum(macs.values())


def ssd_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and the least HBM bytes of the scans of ONE training step,
    all mixers together. A position and layer, forward: x read and y written
    (`itemsize` an element), B and C read a group, the time step read a head
    (float32); backward: twice that (the operands read again, their gradients
    written) and one float32 state a chunk written and read back."""
    ops = nbytes = 0.0
    for _, kind, a in layers:
        if kind == "mamba2":
            h, hd, n = a["heads"], a["head_dim"], a["state"]
            forward = positions * (itemsize * (2.0 * h * hd + 2.0 * a["groups"] * n)
                                   + 4.0 * h)
            states = positions / a["chunk"] * h * hd * n * 4.0 * 2.0
            ops += rows * positions * _ssd_macs(a)
            nbytes += rows * (3.0 * forward + states)
    return {"ops": 2.0 * TRAIN_FWD_MULT * ops, "bytes": nbytes}


def latent_experts_cost(layers, slots: float, held_layers: int,
                        itemsize: int) -> dict:
    """Operations and HBM bytes of the routed experts' grouped products for
    `slots` routed slots in all over `held_layers` expert-layer passes (a
    step's, or a round's): two products a slot forward, four backward; every
    pass reads the held experts' weights once forward and twice backward and
    writes their gradient, and moves each slot's activations (the latent in
    and out, the width twice)."""
    a = next(x for _, k, x in layers if k == "latent_moe")
    ops = 2.0 * TRAIN_FWD_MULT * slots * _latent_expert_macs(a)
    weights = held_layers * a["held"] * 2.0 * a["latent"] * a["width"]
    acts = slots * (2.0 * a["latent"] + 2.0 * a["width"])
    return {"ops": ops, "bytes": itemsize * (4.0 * weights + 3.0 * acts)}
