"""Device time a round of the LRN layers, both passes: the Pallas kernels AND
the layout passes around them (`lrn_kernel_ms` is the kernels alone)
(scope_math.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").layer_type_ms(run, "LRN")
