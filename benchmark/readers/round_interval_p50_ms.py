"""Median time between consecutive round completions: the steadier statistic
beside train_round_rate."""
from __future__ import annotations


def read(run):
    m = run.ctx.load("metric_math.py")
    v = m.median(m.intervals(run.round_done_s))
    return None if v is None else 1e3 * v
