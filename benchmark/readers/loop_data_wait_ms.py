"""run_loop's own t_data per round: what the loop waited for the prefetch
thread (metrics rows)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("metric_math.py").row_mean(run, "t_data_ms")
