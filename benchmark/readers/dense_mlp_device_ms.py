"""Device time a round of the dense SwiGLUs that follow every mixer (type
`GatedMLP`: the two kept input products, the gate, the product back), both
passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "GatedMLP") or None
