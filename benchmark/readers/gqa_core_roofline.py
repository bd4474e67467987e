"""The grouped-query attention cores' share of their roofline: the least time
the chip could take for the causal score and value products of forward +
backward at the published head width (hybrid_lm_flops.py, peaks.json) over
the time the ops under scope `GQAttention/*/core` took. A head padded to a
whole lane row shows as a lower share: the padding's products are no part of
the count. Nothing to read in a program without such layers."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "GQAttention"
                   and "/core/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    hybrid, flops = run.ctx.load("hybrid_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = hybrid.gqa_core_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["gqa_core_roofline_bound"] = bound
    return share
