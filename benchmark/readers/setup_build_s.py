"""The program's kept spans `resolve_spec` + `build_trainer` (`compile_net`,
`trainer_init` inside it) before the window: the spec, the net, the mesh, the
trainer. (startup_account.py)"""
from __future__ import annotations


def read(run):
    return run.ctx.load("startup_account.py").read(run, "setup_build_s")
