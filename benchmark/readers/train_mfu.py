"""Model FLOP/s utilisation: the benchmark's conv + inner-product training
FLOPs per image (flops.py) x samples/s/chip over the chip's peak (peaks.json),
the rate taken on the device's clock over the traced rounds (starting and
stopping the profiler stalls the host, so a traced run's host-clock rate reads
low). End-to-end utilisation, not a kernel's roofline share."""
from __future__ import annotations


def read(run):
    flops = run.ctx.load("flops.py")
    if run.trace is None:
        return None
    rate = (run.trace["rounds"] * run.samples_per_round_per_chip
            / run.trace["window_s"])
    c = run.ctx.config
    per_image = flops.train_flops_per_image(run.ctx.reference.LAYERS,
                                            c["crop"], c["n_classes"])
    return 100.0 * rate * per_image / flops.peaks(run.device_kind)["bf16_flops_per_s"]
