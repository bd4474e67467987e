"""1 - device busy / traced window, mean over the chips, in %."""
from __future__ import annotations


def read(run):
    return run.ctx.load("metric_math.py").idle_share(run)
