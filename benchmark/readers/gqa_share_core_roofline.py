"""The grouped-query cores' share of their roofline at the heads THIS CHIP
HOLDS (the reference's layer table gives the held query and key/value heads
of 128): `gqa_core_roofline`'s reading -- hybrid_lm_flops.py's count over the
time under `GQAttention/*/core` -- under a name of this cell's own (that
metric's list is the hybrid cell's alone)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("readers/gqa_core_roofline.py").read(run)
