"""Time in the LRN kernels (the round's only custom calls) per round, on the
fullest device, from the trace."""
from __future__ import annotations


def read(run):
    m = run.ctx.load("metric_math.py")
    if run.trace is None or not run.trace["fullest"]["kernel_calls"]:
        return None
    return m.traced_rounds_ms(run, "kernel_s")
