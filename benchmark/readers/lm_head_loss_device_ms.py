"""Device time a round of both heads' logits (the `InnerProduct` layers over
the shared output matrix) and softmax losses, both passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(
        run, "InnerProduct", "SoftmaxWithLoss")
