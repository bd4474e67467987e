"""Device time a round of the grouped-query attention layers (type
`GQAttention`), both passes, the recomputed forward with them
(scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "GQAttention")
