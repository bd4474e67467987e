"""`lm_flops.py`'s count for a sequence model whose attention is grouped-query
attention (`gqa`) under a mask the layer names: over every key (`window`
None) or over a sliding window of a query's last `window` keys, as the
configuration's reference layer table says layer by layer. The terms of the
kinds `lm_flops.py` knows (`moe`, `head`, ...) are its own and the `gqa`
projections' `hybrid_lm_flops.py`'s, loaded from the files beside this one;
this file adds the cores' products from the mask as defined (without a
window the causal half, as there), and the operations and bytes of the
cores, the windowed ones and the global ones apart.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward; a core's forward 2 products
and backward 4). For a core a query meets the keys its mask grants -- under a
window of W over n positions W n - W (W - 1) / 2 pairs a head, never the
causal half -- not what a tile computes. The routed experts' part is of the
slots that landed here. Not counted: anything recomputed, norms, softmax,
rotary, routing, the optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _hybrid():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hybrid_lm_flops.py")
    name = "bench_window_lm_flops_base"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


_lm = _hybrid()._lm
TRAIN_FWD_MULT = _lm().TRAIN_FWD_MULT
even_slots_per_row = _lm().even_slots_per_row
#: projection MACs a position of one grouped-query attention layer:
#: `hybrid_lm_flops.py`'s own
_gqa_macs = _hybrid()._gqa_macs


def windowed(a: dict, positions: int) -> bool:
    """Whether the layer's window cuts a row of `positions` short."""
    return a.get("window") is not None and a["window"] < positions


def core_pairs(a: dict, positions: int) -> float:
    """(query, key) pairs a head of one core reads over a ROW: position p
    its min(p + 1, W) last keys; every key up to itself without a window."""
    if not windowed(a, positions):
        return positions * (positions + 1) / 2.0
    w = a["window"]
    return w * positions - w * (w - 1) / 2.0


def _core_macs(a: dict, positions: int) -> float:
    """Score and value MACs a row of one core: a pair costs head_dim of each,
    a query head."""
    return core_pairs(a, positions) * a["heads"] * 2.0 * a["head_dim"]


def forward_macs_per_row(layers, positions: int, slots_per_row: dict) -> dict:
    """`lm_flops.forward_macs_per_row` with the new kind: its projections
    under "dense", its cores under "core"."""
    macs = _lm().forward_macs_per_row(layers, positions, slots_per_row)
    for _, kind, a in layers:
        if kind == "gqa":
            macs["dense"] += positions * _gqa_macs(a)
            macs["core"] += _core_macs(a, positions)
    return macs


def train_flops_per_row(layers, positions: int, slots_per_row=None) -> float:
    macs = forward_macs_per_row(
        layers, positions, slots_per_row or even_slots_per_row(layers, positions))
    return 2.0 * TRAIN_FWD_MULT * sum(macs.values())


def core_step_cost(layers, rows: int, positions: int, itemsize: int,
                   sliding: bool) -> dict:
    """Operations and HBM bytes the cores of ONE training step need, those
    under a window that cuts the row short (`sliding`) or those over every
    key, all such layers together, keyed by layer beside the totals: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv: six passes over a tensor of the query heads' size and six over
    one of the key/value heads'."""
    macs = elems = 0.0
    names = []
    for name, kind, a in layers:
        if kind == "gqa" and windowed(a, positions) == sliding:
            names.append(name)
            macs += rows * _core_macs(a, positions)
            elems += rows * positions * 6.0 * a["head_dim"] * (
                a["heads"] + a["kv_heads"])
    return {"ops": 2.0 * TRAIN_FWD_MULT * macs, "bytes": elems * itemsize,
            "layers": names}
