"""`gqa_device_ms` (all ops under the layers of type `GQAttention`, both
passes, the recomputed forward with them) under this cell's own name: that
metric's list of cells is pinned to the LFM2 cell by
`tests/benchmark/test_hybrid_round.py`, so a model that sets sliding windows
among global layers reads the same number through the same reader here."""
from __future__ import annotations

import os


def read(run):
    return run.ctx.load(os.path.join("readers", "gqa_device_ms.py")).read(run)
