"""Host time of one `trainer.train_round` call: the program's own span
`sparknet:train_round`, mean over the traced rounds (program_spans.py)."""
from __future__ import annotations


def read(run):
    return run.ctx.load("program_spans.py").span_ms_per_round(run, "train_round")
