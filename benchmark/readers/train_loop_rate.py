"""samples/s/chip through run_loop: every round the loop completed in the
window, saves and all, over all the window's time (host clock, at the loop's
own loss fetch)."""
from __future__ import annotations


def read(run):
    m = run.ctx.load("metric_math.py")
    return m.window_rate(run.round_done_s, run.samples_per_round_per_chip)
