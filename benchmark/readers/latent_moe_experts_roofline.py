"""The latent experts' share of their roofline: two products a routed slot
forward and four backward at [latent, width] (ssm_lm_flops.py) for the slots
the window's counters say landed on this chip's experts, and the bytes of the
held weights and the slots' activations, over the time the ops under
`.../experts` took. Nothing to read in a program whose experts are another
kind."""
from __future__ import annotations


def read(run):
    ms = run.ctx.load("readers/moe_experts_device_ms.py").read(run)
    moe = run.notes.get("moe", {})
    c = run.ctx.config
    layers = run.ctx.reference.layer_table(c)
    if not ms or not moe or not any(k == "latent_moe" for _, k, _ in layers):
        return None
    ssm, flops = run.ctx.load("ssm_lm_flops.py"), run.ctx.load("flops.py")
    cost = ssm.latent_experts_cost(layers, moe["slots_landed_per_round"],
                                   len(moe["by_layer"]) * c["tau"],
                                   4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"], cost["bytes"], 1e-3 * ms,
                                        flops.peaks(run.device_kind))
    run.notes["latent_moe_experts_roofline_bound"] = bound
    return share
