"""Model FLOP/s utilisation of a sequence-model cell built of state-space
mixers, one attention and dense SwiGLUs on rows that hold several documents:
THE SHARE OF THE WHOLE STEP -- every matmul, scan and attention-core FLOP the
model needs a row (packed_ssm_lm_flops.py, from the reference's layer table;
the core over the pairs of one document, from the documents the traffic drew
for the check round; recomputation does not count) x rows/s on the device's
clock over the traced rounds, over the chip's peak."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    packed, flops = run.ctx.load("packed_ssm_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    per_row = packed.train_flops_per_row(
        run.ctx.reference.layer_table(c), c["seq_len"],
        (run.notes.get("doc_boundaries") or {}).get("causal_pairs_per_row"))
    rate = (run.trace["rounds"] * run.samples_per_round_per_chip
            / run.trace["window_s"])
    return 100.0 * rate * per_row / flops.peaks(run.device_kind)["bf16_flops_per_s"]
