"""samples/s/chip of the trainer's round: every round completed in the window
over all the window's time (host clock around the loss fetch)."""
from __future__ import annotations


def read(run):
    m = run.ctx.load("metric_math.py")
    return m.window_rate(run.round_done_s, run.samples_per_round_per_chip)
