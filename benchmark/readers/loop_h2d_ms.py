"""The benchmark's span around trainer.place_batches (host arrays), per round."""
from __future__ import annotations


def read(run):
    return run.ctx.load("metric_math.py").span_ms_per_round(run, "h2d")
