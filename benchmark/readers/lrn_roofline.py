"""The LRN kernels' share of their roofline: the least time the chip could take
for their operations and bytes (flops.py, peaks.json) over the time they took."""
from __future__ import annotations


def read(run):
    flops = run.ctx.load("flops.py")
    if run.trace is None or not run.trace["fullest"]["kernel_calls"]:
        return None
    c, d = run.ctx.config, run.trace["fullest"]
    cost = flops.lrn_step_cost(run.ctx.reference.LAYERS, c["crop"],
                               c["local_batch"],
                               4 if c["precision"] == "float32" else 2)
    steps = c["tau"] * d["rounds"]
    share, bound = flops.roofline_share(cost["ops"] * steps, cost["bytes"] * steps,
                                        d["kernel_s"], flops.peaks(run.device_kind))
    run.notes["lrn_roofline_bound"] = bound
    return share
