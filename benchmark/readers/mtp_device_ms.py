"""Device time a round of the multi-token-prediction module (type `MTP`: its
projection, attention, experts and norm), both passes; its head and loss are
counted with the heads (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "MTP")
