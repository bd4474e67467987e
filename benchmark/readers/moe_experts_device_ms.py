"""Device time a round of the routed experts' grouped products and the
activation between them: the ops under scope `.../experts` of every expert
layer and of the MTP module's, both passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").sum_ms(
        run, lambda op: "/experts/" in "/" + op["scope"] + "/")
