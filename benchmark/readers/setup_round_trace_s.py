"""`trace_s` + `lower_s` of the compile log's `train_round` entries before the
window: jax tracing the round and lowering it, which no cache saves. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_round_trace_s")
