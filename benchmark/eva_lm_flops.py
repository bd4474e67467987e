"""`lm_flops.py`'s count for a dense sequence model whose attention is EVA
(`eva`: an exact causal window beside chunk summaries under one softmax), as
the configuration's reference layer table names its layers. The terms of the
kinds `lm_flops.py` knows (`mlp`, `head`) are its own, loaded from the file
beside this one; this file adds the new kind's projections, the core's
products from the mask as defined, the summaries' products, and the
operations and bytes of the two places that are its alone: the core and the
summaries.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward; the core's forward 2 products
and backward 4). For the core a query meets the causal keys of its own window
and one summary of every chunk of the windows before it -- what the mask
grants, not what a tile computes. Not counted: anything recomputed, norms,
softmax, rotary, the optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lm_flops.py")
    name = "bench_eva_lm_flops_base"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


TRAIN_FWD_MULT = _lm().TRAIN_FWD_MULT


def _eva_macs(a: dict) -> float:
    """Projection MACs a position of one EVA layer: q, k, v and the output,
    d x heads x head_dim each."""
    return 4.0 * a["d"] * a["heads"] * a["head_dim"]


def core_pairs(a: dict, positions: int) -> float:
    """(query, key column) pairs a ROW of one core reads: position i the
    (i mod W) + 1 keys of its window up to itself and floor(i / W) x W / C
    summaries; a row within one window its causal keys alone."""
    w = min(a["window"], positions)
    windows = positions // w
    own = windows * w * (w + 1) / 2.0
    summaries = (w / a["chunk"]) * w * windows * (windows - 1) / 2.0
    return own + summaries


def _core_macs(a: dict, positions: int) -> float:
    """Score and value MACs a row of one core: a pair costs head_dim of
    each, a head."""
    return core_pairs(a, positions) * 2.0 * a["head_dim"] * a["heads"]


def _summary_macs(a: dict) -> float:
    """The summaries' MACs a position of one layer: phi . k and a x v, a
    head (the mean of the keys is adds alone)."""
    return 2.0 * a["head_dim"] * a["heads"]


def forward_macs_per_row(layers, positions: int) -> dict:
    """`lm_flops.forward_macs_per_row` with the new kind: its projections
    under "dense", the cores under "core", the summaries under "summaries"
    (none where a row lies within one window)."""
    macs = dict(_lm().forward_macs_per_row(layers, positions, {}), summaries=0.0)
    for _, kind, a in layers:
        if kind == "eva":
            macs["dense"] += positions * _eva_macs(a)
            macs["core"] += _core_macs(a, positions)
            if positions > a["window"]:
                macs["summaries"] += positions * _summary_macs(a)
    return macs


def train_flops_per_row(layers, positions: int) -> float:
    return 2.0 * TRAIN_FWD_MULT * sum(
        forward_macs_per_row(layers, positions).values())


def eva_core_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and the least HBM bytes of the EVA cores of ONE training
    step, all layers together. Forward reads q, the key columns and the value
    columns and writes o; backward reads q, keys, values, o and do and
    writes dq, dkeys, dvalues: six passes over a [positions, heads,
    head_dim] tensor and six over one of positions + positions / C rows (the
    softmax statistics are a 128th of one)."""
    macs = elems = 0.0
    for _, kind, a in layers:
        if kind == "eva":
            columns = positions + (positions // a["chunk"]
                                   if positions > a["window"] else 0)
            macs += rows * _core_macs(a, positions)
            elems += rows * a["heads"] * a["head_dim"] * 6.0 * (positions + columns)
    return {"ops": 2.0 * TRAIN_FWD_MULT * macs, "bytes": elems * itemsize}


def eva_summary_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and the least HBM bytes of the chunk summaries of ONE
    training step, all layers together. Forward reads k and v and writes one
    summary key and value a chunk; backward reads k, v and the summaries'
    gradients and writes its part of dk and dv: three passes over k and v
    and three over the summaries. Nothing where a row lies within one
    window."""
    macs = elems = 0.0
    for _, kind, a in layers:
        if kind == "eva" and positions > a["window"]:
            macs += rows * positions * _summary_macs(a)
            elems += rows * a["heads"] * a["head_dim"] * 3.0 * 2.0 * (
                positions + positions // a["chunk"])
    return {"ops": 2.0 * TRAIN_FWD_MULT * macs, "bytes": elems * itemsize}
