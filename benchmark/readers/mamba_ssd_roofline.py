"""The state-space scans' share of their roofline: the least time the chip
could take for the recurrence's products and for moving its operands,
results and chunk states at the heads held (ssm_lm_flops.py, peaks.json) over
the time the ops under scope `Mamba2/*/ssd` took; the forward pass made again
for the backward counts in the time and not in the operations or bytes.
Whatever implements the scan, the count reads the same work. Nothing to read
in a program without such layers."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "Mamba2"
                   and "/ssd/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    ssm, flops = run.ctx.load("ssm_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = ssm.ssd_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["mamba_ssd_roofline_bound"] = bound
    return share
