"""Device time a round of the latent-attention layers (type `MLAttention`),
both passes, the recomputed forward with them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "MLAttention")
