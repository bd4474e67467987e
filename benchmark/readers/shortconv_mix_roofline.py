"""The short convolutions' gates and taps as a share of their roofline: the
least time the chip could take to move what they must (forward reads 3d and
writes d a token a layer, backward reads 4d and writes 3d:
hybrid_lm_flops.py, peaks.json) over the time the ops under scope
`ShortConv/*/mix` took; the forward pass made again for the backward counts
in the time and not in the bytes. Nothing to read in a program without such
layers."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "ShortConv"
                   and "/mix/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    hybrid, flops = run.ctx.load("hybrid_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = hybrid.shortconv_mix_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["shortconv_mix_roofline_bound"] = bound
    return share
