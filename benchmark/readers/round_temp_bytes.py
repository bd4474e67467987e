"""Temporary bytes a device of the compiled round program: XLA's memory
analysis, as the program's `program_report("train_round")` gives it."""
from __future__ import annotations


def read(run):
    return run.ctx.load("scope_math.py").memory_bytes(run, "temp")
