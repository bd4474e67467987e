"""`lm_flops.py`'s count for a sequence model whose layers are of two more
kinds: a gated short convolution (`shortconv`) and grouped-query attention
(`gqa`), as the configuration's reference layer table names them. The terms
of the kinds `lm_flops.py` knows (`mlp`, `moe`, `head`, ...) are its own,
loaded from the file beside this one; this file adds the two new kinds' and
the operations and bytes of the two places that are theirs alone: the
attention core at grouped heads, and the gates and taps between a short
convolution's two projections.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward); for the attention core the
causal half of the scores and of the values at the published head width.
Not counted: anything recomputed, norms, softmax, rotary, routing, the
optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lm_flops.py")
    name = "bench_hybrid_lm_flops_base"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


TRAIN_FWD_MULT = _lm().TRAIN_FWD_MULT
even_slots_per_row = _lm().even_slots_per_row


def _shortconv_macs(a: dict) -> float:
    """MACs a position of one gated short convolution: d -> 3d, the taps,
    d -> d."""
    return a["d"] * 3.0 * a["d"] + a["d"] * a["taps"] + a["d"] * a["d"]


def _gqa_macs(a: dict) -> float:
    """Projection MACs a position of one grouped-query attention layer."""
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return a["d"] * (q + 2.0 * kv) + q * a["d"]


def _gqa_core_macs(a: dict, positions: int) -> float:
    """Causal score and value MACs a ROW of one core: every query head
    meets the keys up to its own position, at the published head width."""
    return positions * (positions + 1) / 2.0 * a["heads"] * 2.0 * a["head_dim"]


def forward_macs_per_row(layers, positions: int, slots_per_row: dict) -> dict:
    """`lm_flops.forward_macs_per_row` with the two new kinds: their
    projections (and taps) under "dense", the grouped cores under "core"."""
    macs = _lm().forward_macs_per_row(layers, positions, slots_per_row)
    for _, kind, a in layers:
        if kind == "shortconv":
            macs["dense"] += positions * _shortconv_macs(a)
        elif kind == "gqa":
            macs["dense"] += positions * _gqa_macs(a)
            macs["core"] += _gqa_core_macs(a, positions)
    return macs


def train_flops_per_row(layers, positions: int, slots_per_row=None) -> float:
    macs = forward_macs_per_row(
        layers, positions, slots_per_row or even_slots_per_row(layers, positions))
    return 2.0 * TRAIN_FWD_MULT * sum(macs.values())


def gqa_core_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and HBM bytes the grouped-query cores of ONE training step
    need, all layers together: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv: six passes over a tensor of
    the query heads' size and six over one of the key/value heads'."""
    macs = elems = 0.0
    for _, kind, a in layers:
        if kind == "gqa":
            macs += rows * _gqa_core_macs(a, positions)
            elems += rows * positions * 6.0 * a["head_dim"] * (
                a["heads"] + a["kv_heads"])
    return {"ops": 2.0 * TRAIN_FWD_MULT * macs, "bytes": elems * itemsize}


def shortconv_mix_step_cost(layers, rows: int, positions: int,
                            itemsize: int) -> dict:
    """Operations and the least HBM bytes of the gates and taps of ONE
    training step, all short convolutions together. A token a layer:
    forward reads B, C, z (3d) and writes the gated result (d); backward
    reads B, C, z and the result's gradient (4d) and writes the three
    gradients (3d): eleven passes over a [rows, positions, d] tensor.
    Operations a channel: the gate, `taps` products and `taps` - 1 sums, the
    second gate forward, and about twice that backward."""
    elems = ops = 0.0
    for _, kind, a in layers:
        if kind == "shortconv":
            n = float(rows) * positions * a["d"]
            elems += 11.0 * n
            ops += TRAIN_FWD_MULT * n * (2.0 * a["taps"] + 1.0)
    return {"ops": ops, "bytes": elems * itemsize}
