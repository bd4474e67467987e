"""Device time a round of the grouped-query attention of a model whose rows
hold several documents (type `GQAttention`: three projections, the core under
segment ids -- a query reads its own document's keys --, the product back),
both passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "GQAttention") or None
