"""The program's kept spans `state_from_params` (and `restore`) before the window:
the state built and placed, the compiles of its placement programs with it. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_state_s")
