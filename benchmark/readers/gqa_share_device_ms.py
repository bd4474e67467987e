"""Device time a round of the grouped-query attention layers of a model that
holds a SHARE of their heads and turns nothing by position (type
`GQAttention`, the MTP module's with them), both passes: `gqa_device_ms`'s
reading, under a name of this cell's own (that metric's list is the hybrid
cell's alone)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("readers/gqa_device_ms.py").read(run) or None
