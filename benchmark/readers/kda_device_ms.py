"""Device time a round of the Kimi Delta Attention layers (type
`KDAttention`: the projections, the convolutions, the gates, the delta rule,
the output gate and projection), both passes, the recomputed forward with
them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "KDAttention")
