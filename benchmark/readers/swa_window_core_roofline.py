"""The SLIDING cores' share of their roofline: the least time the chip could
take for the score and value products of the pairs the window grants (W n -
W (W - 1) / 2 a head, never the causal half), forward + backward, and for
moving q, k, v and the results (window_lm_flops.py, peaks.json) over the time
the ops under scope `GQAttention/<layer>/core` took, for the layers whose
window cuts the row short. The tiles the kernel computes and the mask then
empties count in the time and not in the operations. Nothing to read in a
program without such layers."""
from __future__ import annotations


def read(run, sliding=True):
    window, flops = run.ctx.load("window_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = window.core_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2, sliding)
    ms = run.ctx.load("scope_math.py").sum_ms(
        run, lambda op: op["layer_type"] == "GQAttention"
        and op["layer"] in cost["layers"]
        and "/core/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    key = "swa_window_core_roofline" if sliding else "swa_global_core_roofline"
    run.notes[key + "_bound"] = bound
    return share
