"""Device time a round of the EVA attention layers (type `EVAttention`: the
projections, the rotary turns, the chunk summaries, the core, the output
projection), both passes, the recomputed forward with them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "EVAttention")
