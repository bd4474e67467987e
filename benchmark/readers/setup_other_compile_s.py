"""`trace_s` + `lower_s` + `backend_s` of every entry of the compile log before the
window that is not the round's: state placement, the benchmark's stack and
norms programs, jax's own small ones. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_other_compile_s")
