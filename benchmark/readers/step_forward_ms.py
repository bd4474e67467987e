"""Device time a round of the step's forward pass: the round program's ops inside
`tau_step` that are neither under `solver_update` nor on a `transpose(` path
(the loss's own arithmetic with them), from the trace joined with the
program's report (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").phase_ms(run, "forward")
