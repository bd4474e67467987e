"""The benchmark's count of what a training step needs of a sequence model
whose layers are TWO sublayers -- a Mamba-2 mixer (`mamba2`) or grouped-query
attention (`gqa`), then a dense SwiGLU (`swiglu`) -- under a tied head
(`head`), on rows that hold several documents, as the configuration's
reference layer table names them. The mixer's and the scan's terms are
`ssm_lm_flops.py`'s and the attention's `hybrid_lm_flops.py`'s, loaded from
the files beside this one; this file adds the SwiGLU's products, the tied
head counted once (it holds no parameters of its own and is one product), and
the attention core under DOCUMENT masks.

Counted as there: 2 x MACs of every product the algorithm needs, forward +
input gradient + weight gradient (3 x forward). For the scan the algorithm is
the RECURRENCE (a position and head: x (x) B into the state and the state's
product with C), whatever chunk and head block the kernels walk. For the
attention core the algorithm needs a query's pairs with the keys at or before
it IN ITS DOCUMENT: `pairs_per_row`, what the traffic drew (the sum over a
row's documents of len (len + 1) / 2); without it, the causal half of the
whole row, which is what the kernel visits wherever the boundaries fall. Not
counted: anything recomputed, norms, softmax, the taps' bias, the decays, the
gates, the skip, the multipliers, the optimizer.
"""
from __future__ import annotations

import importlib.util
import os
import sys


def _beside(file: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), file)
    name = "bench_packed_flops_" + file.removesuffix(".py")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


_ssm, _hybrid = _beside("ssm_lm_flops.py"), _beside("hybrid_lm_flops.py")
TRAIN_FWD_MULT = _ssm.TRAIN_FWD_MULT


def _swiglu_macs(a: dict) -> float:
    """MACs a position of one dense SwiGLU: gate, up and down."""
    return 3.0 * a["d"] * a["width"]


def causal_pairs(positions: int) -> float:
    """(query, key) pairs a row of one document holds."""
    return positions * (positions + 1) / 2.0


def forward_macs_per_row(layers, positions: int, pairs_per_row=None) -> dict:
    """{"dense": the mixers' projections and taps, the SwiGLUs, the head;
    "core": the attention cores over `pairs_per_row` pairs a row (None: one
    document a row); "ssd": the scans} MACs a row, forward."""
    pairs = causal_pairs(positions) if pairs_per_row is None else pairs_per_row
    macs = {"dense": 0.0, "core": 0.0, "ssd": 0.0}
    for _, kind, a in layers:
        if kind == "mamba2":
            macs["dense"] += positions * _ssm._mamba_macs(a)
            macs["ssd"] += positions * _ssm._ssd_macs(a)
        elif kind == "gqa":
            macs["dense"] += positions * _hybrid._gqa_macs(a)
            macs["core"] += pairs * a["heads"] * 2.0 * a["head_dim"]
        elif kind == "swiglu":
            macs["dense"] += positions * _swiglu_macs(a)
        elif kind == "head":
            macs["dense"] += positions * a["d"] * a["vocab"]
    return macs


def train_flops_per_row(layers, positions: int, pairs_per_row=None) -> float:
    return 2.0 * TRAIN_FWD_MULT * sum(
        forward_macs_per_row(layers, positions, pairs_per_row).values())


def ssd_step_cost(layers, rows: int, positions: int, itemsize: int) -> dict:
    """Operations and the least HBM bytes of the scans of ONE training step,
    all mixers together: `ssm_lm_flops.ssd_step_cost`'s count (the
    recurrence's MACs; x and y, B and C a group, a time step a head, one
    float32 state a chunk of the table's `chunk` positions written and read
    back), which reads the same whatever implements the scan and wherever a
    row's documents begin."""
    return _ssm.ssd_step_cost(layers, rows, positions, itemsize)
