"""The attention cores' share of their roofline: the least time the chip could
take for the causal score and value products of forward + backward
(lm_flops.py, peaks.json) over the time the ops under scope `.../core` took
(every latent-attention layer's and the MTP module's; the forward pass made
again for the backward counts in the time and not in the operations)."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: "/core/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    lm, flops = run.ctx.load("lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = lm.core_step_cost(run.ctx.reference.layer_table(c), c["local_batch"],
                             c["seq_len"], 4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["mla_core_roofline_bound"] = bound
    return share
