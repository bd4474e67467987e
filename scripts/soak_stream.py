"""Long-soak of the composed streaming system (r4).

Unit and chaos tests prove the pieces and the crash story; this proves
ENDURANCE: thousands of consecutive τ-rounds on the real chip through the
full production ingest path — parallel shard readers (C tar member index +
pread), bounded ring buffers, per-round preprocessing on the prefetch
thread, periodic checkpoints with per-reader stream cursors — while
tracking host RSS for leaks (an unbounded queue, an unfreed buffer, or a
growing cursor map would show as monotonic RSS growth over hours).

Writes `--out` (default SOAK.json): rounds completed, wall time,
RSS first/median/last, stream epochs, skipped counter, loss finiteness.
(The r4/r5 runs' figures are in PERF.md's Findings; their record files
were removed in PR 21.)

Run: python scripts/soak_stream.py --rounds 6000 [--out SOAK.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for ln in f:
            if ln.startswith("VmRSS:"):
                return int(ln.split()[1]) / 1024.0
    return -1.0


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rounds", type=int, default=6000)
    p.add_argument("--out", default="SOAK.json")
    p.add_argument("--sources", type=int, default=4)
    p.add_argument("--shards", type=int, default=32)
    p.add_argument("--per-shard", type=int, default=256)
    p.add_argument("--keep", action="store_true",
                   help="keep the temp shard/work dirs (default: removed)")
    p.add_argument("--sample-every", type=int, default=50,
                   help="rounds between RSS samples")
    p.add_argument("--cpu-control", action="store_true",
                   help="run the IDENTICAL loop on the CPU backend at the "
                   "SAME shapes (r5: the r4 control ran ~1 MB rounds vs "
                   "the TPU run's 4.3 MB — a size-dependent framework "
                   "leak would have hidden; this control is size-matched)")
    p.add_argument("--store", default=None, choices=("gs",),
                   help="serve the shards from a local fake-GCS server "
                   "and stream them as gs:// urls (r5: endurance for the "
                   "ranged-HTTP + member-carve bucket path — connection "
                   "reuse, per-epoch freshness checks, index cache)")
    args = p.parse_args()

    if args.cpu_control:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data import imagenet
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    from sparknet_tpu.data.streaming import make_parallel_source
    from sparknet_tpu.schema import Field, Schema
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import caffenet

    crop, size, b, tau = 67, 72, 32, 5
    root = tempfile.mkdtemp(prefix="soak_shards_")
    work = tempfile.mkdtemp(prefix="soak_work_")
    print(f"soak: building {args.shards}x{args.per_shard} synthetic shards "
          f"under {root}", file=sys.stderr)
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=args.shards, per_shard=args.per_shard,
        n_classes=16, size=size)
    labels = imagenet.load_label_map(label_path)
    shards = imagenet.list_shards(root)
    server = None
    if args.store == "gs":
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tests"))
        from fake_stores import serve_dir_for_ingest
        server, gs_root = serve_dir_for_ingest(root)
        shards = imagenet.list_shards(gs_root)
        print(f"soak: streaming {gs_root} via the in-process fake server",
              file=sys.stderr)
    src = make_parallel_source(shards, labels, 1, b,
                               tau, args.sources, height=size, width=size)
    schema = Schema(Field("data", "float32", (crop, crop, 3)),
                    Field("label", "int32", (1,)))
    pp = ImagePreprocessor(schema, mean_image=None, crop=crop, seed=0,
                           out_dtype="bfloat16")
    cfg = RunConfig(model="caffenet", n_classes=16, crop=crop, n_devices=1,
                    local_batch=b, tau=tau, max_rounds=args.rounds,
                    eval_every=0, precision="bfloat16", workdir=work,
                    checkpoint_dir=os.path.join(work, "ck"),
                    checkpoint_every=200, log_every=8, seed=0)

    t0 = time.time()
    samples = []
    partial_path = args.out + ".partial.jsonl"

    def hook(rnd, state):
        if rnd % args.sample_every == 0:
            s = {"round": rnd, "rss_mb": round(rss_mb(), 1),
                 "wall_s": round(time.time() - t0, 1),
                 "skipped": int(src.skipped)}
            samples.append(s)
            # incremental persistence: a soak that dies at round 5800 (the
            # very leak/fault it hunts) must still leave its evidence
            with open(partial_path, "a") as f:
                f.write(json.dumps(s) + "\n")
            if rnd % 500 == 0:
                print(f"soak round {rnd}: rss {s['rss_mb']} MB "
                      f"({s['wall_s']:.0f}s)", file=sys.stderr)

    jsonl = os.path.join(work, "metrics.jsonl")
    try:
        train(cfg, caffenet(batch=b, crop=crop, n_classes=16), src, None,
              logger=Logger(os.path.join(work, "log.txt"), echo=False,
                            jsonl_path=jsonl),
              batch_transform=pp, round_hook=hook)

        losses = [json.loads(ln).get("loss") for ln in open(jsonl)
                  if "loss" in ln]
        rss = [s["rss_mb"] for s in samples]
        result = {
            "rounds": args.rounds,
            "backend": "cpu-control" if args.cpu_control else "device",
            "store": args.store or "local",
            "round_batch_mb": round(tau * b * crop * crop * 3 * 2 / 1e6, 2),
            "images": args.rounds * b * tau,
            "wall_s": round(time.time() - t0, 1),
            "readers": src.n_sources,
            "stream_epochs": max(ep for (_, _), ep in src.cursors),
            "skipped": int(src.skipped),
            "rss_mb": {"first": rss[0], "median": float(np.median(rss)),
                       "last": rss[-1], "max": max(rss)},
            "losses": {"n": len(losses), "first": losses[0],
                       "last": losses[-1],
                       "all_finite": bool(np.isfinite(losses).all())},
            "rss_samples": samples[:: max(1, len(samples) // 60)],
        }
        from sparknet_tpu.obs import run_metadata
        result["meta"] = run_metadata()
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        if os.path.exists(partial_path):
            os.remove(partial_path)  # superseded by the full artifact
        print(json.dumps({k: v for k, v in result.items()
                          if k != "rss_samples"}))
    finally:
        if server is not None:
            from fake_stores import stop_serving
            stop_serving(server)
        if not args.keep:
            import shutil
            shutil.rmtree(root, ignore_errors=True)
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
