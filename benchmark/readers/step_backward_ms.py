"""Device time a round of the step's backward pass: ops inside `tau_step` on a
`transpose(` path (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").phase_ms(run, "backward")
