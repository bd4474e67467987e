"""Process start to the first timed round, without the reference's time (it runs
after the window)."""
from __future__ import annotations


def read(run):
    return run.setup_s
