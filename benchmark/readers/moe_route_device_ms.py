"""Device time a round of what routing costs besides the experts' products:
the ops under scopes `.../router`, `.../dispatch` and `.../combine` of every
expert layer and of the MTP module's, both passes (scope_math.py)."""
from __future__ import annotations


def read(run):
    parts = ("/router/", "/dispatch/", "/combine/")
    return run.ctx.load("scope_math.py").sum_ms(
        run, lambda op: any(p in "/" + op["scope"] + "/" for p in parts))
