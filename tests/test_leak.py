"""RSS leak gates for the composed streaming system (r5, VERDICT weak #1).

The r4 soak put the TPU run's RSS growth down to its host-to-device path
because a CPU-backend control held flat — but nothing FAILED if a future change
made the CPU path's slope nonzero. These are the tripwires. Two gates,
because on the CPU backend a full-size train round runs ~20x slower than
the same math un-shard_mapped (CPU-backend artifact, irrelevant on TPU),
so one test cannot have both big bytes and the full loop inside a CI
budget:

  1. BIG BYTES, no trainer: 150 rounds of ~4.7 MB preprocessed batches
     through the production ingest path (parallel shard readers -> C++
     decode -> ring -> ImagePreprocessor via the loop's own
     prepare_round_batches). This is where the byte-sized buffers live;
     a retained-batch leak accrues ~700 MB over the window.
  2. FULL LOOP, small shapes: 60 train() rounds (lenet) with per-round
     checkpoints and logging — the loop glue (metrics, hooks, checkpoint
     writer, loss pipeline) at CI speed.

The size-matched full-loop evidence at the r4 TPU soak's exact shapes is
the slower companion artifact: `scripts/soak_stream.py --cpu-control`
-> SOAK_CONTROL_r05.json (300 rounds, 4.31 MB/round, RSS 830 -> 802 MB:
flat).
"""
import json
import os

import numpy as np
import pytest


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for ln in f:
            if ln.startswith("VmRSS:"):
                return int(ln.split()[1]) / 1024.0
    return -1.0


@pytest.mark.slow
def test_ingest_pipeline_rss_flat(tmp_path):
    """Gate 1: production ingest at soak byte size, RSS flat."""
    from sparknet_tpu import precision
    from sparknet_tpu.apps.train_loop import prepare_round_batches
    from sparknet_tpu.data import imagenet
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    from sparknet_tpu.data.streaming import make_parallel_source
    from sparknet_tpu.schema import Field, Schema

    size, crop, b, tau, rounds = 72, 67, 32, 5, 150
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=8, per_shard=256, n_classes=16, size=size)
    labels = imagenet.load_label_map(label_path)
    schema = Schema(Field("data", "float32", (crop, crop, 3)),
                    Field("label", "int32", (1,)))
    pp = ImagePreprocessor(schema, mean_image=None, crop=crop, seed=0,
                           out_dtype="bfloat16")
    cdt = precision.compute_dtype()
    src = make_parallel_source(imagenet.list_shards(root), labels, 1, b,
                               tau, 4, height=size, width=size)
    samples = {}
    with src:
        for rnd in range(rounds):
            batches = prepare_round_batches(src, rnd, tau, 0, pp, cdt)
            assert batches["data"].shape[1] == b
            samples[rnd] = _rss_mb()
    assert src.skipped == 0
    baseline = max(v for r, v in samples.items() if 15 <= r <= 40)
    steady = float(np.median([v for r, v in samples.items()
                              if r >= rounds - 15]))
    growth = steady - baseline
    # one retained round is ~4.7 MB f32 (or 2.4 MB bf16): a leak accrues
    # ~260-500 MB over the asserted ~110 rounds
    assert growth < 40.0, (
        f"RSS grew {growth:.1f} MB from post-warmup peak {baseline:.1f} "
        f"to steady {steady:.1f} over ~{rounds - 40} ingest rounds of "
        f"~4.7 MB each — the ingest pipeline is retaining memory "
        f"(samples: {sorted(samples.items())[::15]})")


@pytest.mark.slow
def test_train_loop_rss_flat(tmp_path):
    """Gate 2: the full train() loop (checkpoints, metrics, loss
    pipeline, round hooks) holds RSS flat at CI shapes."""
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data import imagenet
    from sparknet_tpu.data.streaming import make_parallel_source
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet

    size, b, tau, rounds = 28, 8, 2, 60
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=4, per_shard=64, n_classes=10, size=size)
    labels = imagenet.load_label_map(label_path)
    src = make_parallel_source(imagenet.list_shards(root), labels, 1, b,
                               tau, 2, height=size, width=size)

    class GrayTo28:
        def convert_batch(self, batch, train=True, rng=None):
            x = batch["data"].astype(np.float32).mean(axis=1)
            return {"data": x[..., None], "label": batch["label"]}

    cfg = RunConfig(model="lenet", n_classes=10, n_devices=1,
                    local_batch=b, tau=tau, max_rounds=rounds,
                    eval_every=0, precision="float32",
                    workdir=str(tmp_path / "wk"),
                    checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=10, log_every=4, seed=0)
    samples = {}

    def hook(rnd, state):
        samples[rnd] = _rss_mb()

    jsonl = str(tmp_path / "m.jsonl")
    train(cfg, lenet(batch=b), src, None,
          logger=Logger(str(tmp_path / "log.txt"), echo=False,
                        jsonl_path=jsonl),
          batch_transform=GrayTo28(), round_hook=hook)
    losses = [json.loads(ln)["loss"] for ln in open(jsonl) if "loss" in ln]
    assert len(losses) == rounds and np.isfinite(losses).all()
    baseline = max(v for r, v in samples.items() if 10 <= r <= 25)
    steady = float(np.median([v for r, v in samples.items()
                              if r >= rounds - 8]))
    growth = steady - baseline
    assert growth < 25.0, (
        f"RSS grew {growth:.1f} MB from post-warmup peak {baseline:.1f} "
        f"to steady {steady:.1f} over the train() loop "
        f"(samples: {sorted(samples.items())[::6]})")
