"""Model FLOP/s utilisation of a sequence-model cell built of state-space
mixers, attention and latent experts: THE SHARE OF THE WHOLE STEP -- every
matmul, attention-core and scan FLOP this chip's share of the model needs a
row (ssm_lm_flops.py, from the reference's layer table; the routed experts'
part from the slots the window's counters say landed here; recomputation does
not count) x rows/s on the device's clock over the traced rounds, over the
chip's peak."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    ssm, flops = run.ctx.load("ssm_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    layers = run.ctx.reference.layer_table(c)
    rows_per_step = c["local_batch"]
    landed = {blob[:-len("_counters")]: v["slots_landed_per_step"] / rows_per_step
              for blob, v in run.notes.get("moe", {}).get("by_layer", {}).items()}
    per_row = ssm.train_flops_per_row(layers, c["seq_len"], landed or None)
    rate = (run.trace["rounds"] * run.samples_per_round_per_chip
            / run.trace["window_s"])
    return 100.0 * rate * per_row / flops.peaks(run.device_kind)["bf16_flops_per_s"]
