"""run_loop's own t_ckpt_fetch on the rounds that carried a save's stall."""
from __future__ import annotations


def read(run):
    return run.ctx.load("metric_math.py").row_mean(run, "t_ckpt_fetch_ms",
                                                  only_positive=True)
