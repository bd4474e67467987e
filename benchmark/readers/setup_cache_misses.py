"""Entries of the compile log before the window whose `cache` is not `hit`:
executables built from scratch (a miss, or the cache not consulted). (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_cache_misses")
