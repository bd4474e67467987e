"""The part of the averaging all-reduces during which no other operation ran
on that device, per round."""
from __future__ import annotations


def read(run):
    if run.trace is None or run.ctx.cell["chips"] < 2:
        return None
    return run.ctx.load("metric_math.py").traced_rounds_ms(run, "collective_exposed_s")
