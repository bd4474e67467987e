"""`backend_s` of the compile log's `train_round` entries before the window: the
XLA compile, or on a persistent-cache hit the key, the retrieval and the load. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_round_backend_s")
