"""Process start (`ctx.t0`) to the start of the program's first kept start-up
span: the interpreter, the imports and the backend's start-up. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_import_s")
