"""The chunk summaries' share of their roofline: the least time the chip
could take for reading k and v and writing one summary key and value a
chunk, forward + backward, and for their few products (eva_lm_flops.py,
peaks.json) over the time the ops under scope `EVAttention/*/summaries`
took (the concatenation that sets the summaries behind the keys runs there
too, and counts in the time alone). Bandwidth-bound: the note says which
side binds. Nothing to read in a program without such layers."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "EVAttention"
                   and "/summaries/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    eva, flops = run.ctx.load("eva_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = eva.eva_summary_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["eva_summary_roofline_bound"] = bound
    return share
