"""Device time a round of the Mamba-2 mixers (type `Mamba2`: the two
projections, the taps, the scan, the gate and its norm), both passes, the
recomputed forward with them (scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "Mamba2") or None
