"""Host time a round of `train_round`'s child span `round_keys`: the per-worker
key split and its placement over the mesh (program_spans.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("program_spans.py").span_ms_per_round(run, "round_keys")
