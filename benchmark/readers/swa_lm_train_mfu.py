"""Model FLOP/s utilisation of a sequence-model cell whose attention mixes
sliding windows and global layers: the matmul and attention FLOPs this chip's
share of the model needs a row (window_lm_flops.py, from the reference's
layer table: a windowed core's pairs are the window's, the routed experts'
part that of the slots the window's counters say landed here; recomputation
does not count) x rows/s on the device's clock over the traced rounds, over
the chip's peak."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    window, flops = run.ctx.load("window_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    layers = run.ctx.reference.layer_table(c)
    rows_per_step = c["local_batch"]
    landed = {blob[:-len("_counters")]: v["slots_landed_per_step"] / rows_per_step
              for blob, v in run.notes.get("moe", {}).get("by_layer", {}).items()}
    per_row = window.train_flops_per_row(layers, c["seq_len"], landed or None)
    rate = (run.trace["rounds"] * run.samples_per_round_per_chip
            / run.trace["window_s"])
    return 100.0 * rate * per_row / flops.peaks(run.device_kind)["bf16_flops_per_s"]
