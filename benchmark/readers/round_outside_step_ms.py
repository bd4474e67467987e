"""Device time a round of the round program's ops outside `tau_step`: slicing
the stack for the scan, the peeled last step's copy of the other rows, the
boundary average and the health reductions (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").phase_ms(run, "outside_step")
