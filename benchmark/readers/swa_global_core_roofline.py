"""The GLOBAL cores' share of their roofline (the layers without a window, or
under one as long as the row: causal over every key, no rotary turn in this
model, seven query heads a key/value head): `swa_window_core_roofline`'s
arithmetic over the causal half of the score square and the time under those
layers' `core` scopes."""
from __future__ import annotations


def read(run):
    return run.ctx.load("readers/swa_window_core_roofline.py").read(run, sliding=False)
