"""The EVA cores' share of their roofline: the least time the chip could take
for the score and value products the mask grants, forward + backward, and for
moving q, the key and value columns and the results (eva_lm_flops.py,
peaks.json) over the time the ops under scope `EVAttention/*/core` took.
Compute-bound at the cell's size (the note says which side binds); the tiles
the kernel computes and the mask then empties count in the time and not in
the operations. Nothing to read in a program without such layers."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "EVAttention"
                   and "/core/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    eva, flops = run.ctx.load("eva_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = eva.eva_core_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["eva_core_roofline_bound"] = bound
    return share
