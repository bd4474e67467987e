"""The benchmark's span around ImagePreprocessor.convert_batch, per round."""
from __future__ import annotations


def read(run):
    return run.ctx.load("metric_math.py").span_ms_per_round(run, "preprocess")
