"""The benchmark's own count of what a sequence model's training step needs:
matmul and causal-attention FLOPs of the share of the model one chip holds,
from the configuration's reference layer table (`layer_table`), and the
operations and bytes of the two places that run as kernels of their own (the
attention core, the experts' grouped products). Peaks and the roofline
arithmetic are `flops.py`'s.

Counted: 2 x MACs of every matrix product the algorithm needs, forward +
input gradient + weight gradient (3 x forward); for the attention core the
causal half of the scores and of the values (forward 2 products, backward 4).
Not counted: anything recomputed, norms, softmax, rotary, routing, the
optimizer.
"""
from __future__ import annotations

#: forward + input gradient + weight gradient
TRAIN_FWD_MULT = 3.0


def _mla_macs(a: dict) -> float:
    """Projection MACs a position of one latent-attention layer."""
    h = a["heads"]
    return (a["d"] * a["q_rank"] + a["q_rank"] * h * (a["nope"] + a["rope"])
            + a["d"] * (a["kv_rank"] + a["rope"])
            + a["kv_rank"] * h * (a["nope"] + a["v"]) + h * a["v"] * a["d"])


def _core_macs(a: dict, positions: int) -> float:
    """Causal score and value MACs a ROW of one attention core: every query
    meets the keys up to its own position."""
    pairs = positions * (positions + 1) / 2.0
    return pairs * a["heads"] * (a["nope"] + a["rope"] + a["v"])


def _expert_macs(a: dict) -> float:
    """MACs a routed slot (one position through one expert)."""
    return 3.0 * a["d"] * a["width"]


def forward_macs_per_row(layers, positions: int, slots_per_row: dict) -> dict:
    """{"dense": projections, MLPs, shared experts, routers, heads and the
    MTP projection; "core": the attention cores; "experts": the routed
    experts} MACs a row, forward. `slots_per_row`: {expert layer: routed
    slots that land on this chip a row} (a counter's reading, or the even
    share from `even_slots_per_row`)."""
    dense = core = experts = 0.0
    head_macs = 0.0
    for name, kind, a in layers:
        if kind == "mla":
            dense += positions * _mla_macs(a)
            core += _core_macs(a, positions)
        elif kind == "mlp":
            dense += positions * 3.0 * a["d"] * a["width"]
        elif kind == "moe":
            dense += positions * (a["d"] * a["routed"]
                                  + a["shared"] * _expert_macs(a))
            experts += slots_per_row[name] * _expert_macs(a)
        elif kind == "head":
            dense += positions * a["d"] * a["vocab"]
            head_macs = a["d"] * a["vocab"]  # the MTP module shares it
        elif kind == "mtp":
            m = a["moe"]
            dense += positions * (2 * a["d"] * a["d"] + _mla_macs(a["attn"])
                                  + a["d"] * m["routed"]
                                  + m["shared"] * _expert_macs(m) + head_macs)
            core += _core_macs(a["attn"], positions)
            experts += slots_per_row[name] * _expert_macs(m)
    return {"dense": dense, "core": core, "experts": experts}


def even_slots_per_row(layers, positions: int) -> dict:
    """Routed slots a row that land here if the router spreads them evenly."""
    out = {}
    for name, kind, a in layers:
        m = a["moe"] if kind == "mtp" else a
        if kind in ("moe", "mtp"):
            out[name] = positions * m["k"] * m["held"] / m["routed"]
    return out


def train_flops_per_row(layers, positions: int, slots_per_row=None) -> float:
    macs = forward_macs_per_row(
        layers, positions, slots_per_row or even_slots_per_row(layers, positions))
    return 2.0 * TRAIN_FWD_MULT * sum(macs.values())


def core_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and HBM bytes the attention cores of ONE training step
    need, all layers together: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv (twelve passes over a
    [rows, positions, heads, 256]-sized tensor; the softmax statistics are
    a 256th of one)."""
    macs = elems = 0.0
    for _, kind, a in layers:
        a = a["attn"] if kind == "mtp" else a
        if kind in ("mla", "mtp"):
            macs += rows * _core_macs(a, positions)
            elems += rows * positions * a["heads"] * 6.0 * (
                a["nope"] + a["rope"] + a["v"])
    return {"ops": 2.0 * TRAIN_FWD_MULT * macs, "bytes": elems * itemsize}


def experts_cost(layers, slots: float, held_layers: int, itemsize: int) -> dict:
    """Operations and HBM bytes of the routed experts' grouped products for
    `slots` routed slots in all over `held_layers` expert-layer passes (a
    step's, or a round's): three products a slot forward, twice that
    backward; every pass reads the held experts' weights once forward and
    twice backward and writes their gradient, and moves each slot's
    activations."""
    a = next(x["moe"] if k == "mtp" else x for _, k, x in layers
             if k in ("moe", "mtp"))
    ops = 2.0 * TRAIN_FWD_MULT * slots * _expert_macs(a)
    weights = held_layers * a["held"] * 3.0 * a["d"] * a["width"]
    acts = slots * (2 * a["d"] + 3 * a["width"])
    return {"ops": ops, "bytes": itemsize * (4.0 * weights + 3.0 * acts)}
