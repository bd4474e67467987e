"""Device time a round of the optimizer: ops under `solver_update` that XLA did
not fuse behind a weight-gradient matmul (those count with the matmul's
layer, backward) (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").phase_ms(run, "optimizer")
