"""Device time a round of the gated short-convolution layers (type
`ShortConv`: the two projections, the gates and the taps), both passes, the
recomputed forward with them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "ShortConv")
