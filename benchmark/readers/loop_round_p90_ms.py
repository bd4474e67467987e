"""90th percentile of the time between run_loop's round completions: the slow
rounds (saves, a late prefetch)."""
from __future__ import annotations


def read(run):
    m = run.ctx.load("metric_math.py")
    v = m.percentile(m.intervals(run.round_done_s), 90)
    return None if v is None else 1e3 * v
