"""Device time a round of the Convolution and InnerProduct layers, both passes:
the matmul fusions and whatever XLA fused behind them, the layers' own bias
and layout passes with them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(
        run, "Convolution", "InnerProduct")
