"""Device time a round of the Pooling layers, both passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "Pooling")
