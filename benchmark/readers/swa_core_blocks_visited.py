"""Key blocks the grouped-query cores' forward kernels visit, all layers and
query blocks together: the program's own count from the tables the kernels
were built with (`program_report("train_round")["window"]`; `blocks_causal`
there is what a causal mask over every key would send them to). Nothing to
read from a program whose report has no such part, or whose layers did not
run the kernel."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    rep, _ = run.ctx.load("scope_math.py").report()
    part = (rep or {}).get("window") or {}
    if part:
        run.notes["window"] = part
    return part.get("blocks_visited") or None
