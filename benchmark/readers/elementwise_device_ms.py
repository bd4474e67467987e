"""Device time a round of the ReLU and Dropout layers, both passes: what XLA
left of them as ops of their own (a ReLU fused behind a convolution counts
with the convolution) (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "ReLU", "Dropout")
