"""Model FLOP/s utilisation of a dense sequence-model cell whose attention
is EVA: the matmul, core and summary FLOPs the model needs a row
(eva_lm_flops.py, from the reference's layer table; recomputation does not
count) x rows/s on the device's clock over the traced rounds, over the chip's
peak."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    eva, flops = run.ctx.load("eva_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    per_row = eva.train_flops_per_row(run.ctx.reference.layer_table(c), c["seq_len"])
    rate = (run.trace["rounds"] * run.samples_per_round_per_chip
            / run.trace["window_s"])
    return 100.0 * rate * per_row / flops.peaks(run.device_kind)["bf16_flops_per_s"]
