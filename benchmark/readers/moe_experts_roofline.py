"""The routed experts' share of their roofline: three products a routed slot
forward and six backward (lm_flops.py) for the slots the window's counters
say landed on this chip's experts, and the bytes of the held weights and the
slots' activations, over the time the ops under `.../experts` took."""
from __future__ import annotations


def read(run):
    ms = run.ctx.load("readers/moe_experts_device_ms.py").read(run)
    moe = run.notes.get("moe", {})
    if not ms or not moe:
        return None
    lm, flops = run.ctx.load("lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = lm.experts_cost(run.ctx.reference.layer_table(c),
                           moe["slots_landed_per_round"],
                           len(moe["by_layer"]) * c["tau"],
                           4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"], cost["bytes"], 1e-3 * ms,
                                        flops.peaks(run.device_kind))
    run.notes["moe_experts_roofline_bound"] = bound
    return share
