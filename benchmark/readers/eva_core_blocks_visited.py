"""Key blocks one EVA layer's forward core kernel visits, all query blocks
together: the program's own count from the tables the kernel was built with
(`program_report("train_round")["eva"]`; `blocks` there is how many it would
visit under no mask). Nothing to read from a program whose report has no
such part, or whose layers did not run the kernel."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    rep, _ = run.ctx.load("scope_math.py").report()
    part = (rep or {}).get("eva") or {}
    if part:
        run.notes["eva"] = part
    return part.get("blocks_visited") or None
