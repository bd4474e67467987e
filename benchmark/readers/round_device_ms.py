"""Device busy time per round, mean over the chips, from the trace."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace["busy_s"] / run.trace["rounds"]
