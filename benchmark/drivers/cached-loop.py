"""Traffic kind `cached-loop`: the program's own training loop (`run_loop`, as
`train()` calls it) fed from an in-memory uint8 corpus, the path the reference
app trains from: decode once, cache the 256x256 partition in host memory, crop
per minibatch. ArrayDataset -> RoundSampler -> ImagePreprocessor (native fused
crop / mean / cast on the prefetch thread) -> place_batches -> donated round,
with async collect and async checkpoints, all inside the clock.

Traffic parameters (`traffic/<mix>.json`): `corpus_images`, `image_size`,
`checkpoint_every`, `warmup_rounds`, `trace_skip_rounds`, `trace_rounds`,
`crop_check_rows`.
"""
from __future__ import annotations

import os
import time

import numpy as np


def _crop_mismatches(batch, images, labels, mean, crop: int, rows: int,
                     seed: int) -> int:
    """How many of `rows` rows drawn from the seed out of a prepared round are
    NOT a crop of a corpus image of that label minus the mean image, to one
    bfloat16 rounding. Plain numpy over every offset: the benchmark's own
    reading of what the ingest layer has to produce."""
    rng = np.random.default_rng((seed, 0xC0))
    tau, n = batch["label"].shape[:2]
    span = images.shape[-1] - crop + 1
    bad = 0
    for t, i in zip(rng.integers(0, tau, rows), rng.integers(0, n, rows)):
        row = np.asarray(batch["data"][t, i], np.float32)          # HWC
        found = False
        for c in np.nonzero(labels[:, 0] == int(batch["label"][t, i, 0]))[0]:
            full = (images[c].astype(np.float32) - mean).transpose(1, 2, 0)
            corner = full[:span, :span]                            # every (y, x)
            near = np.all(np.abs(corner - row[0, 0]) <= np.abs(corner) / 128
                          + 1e-3, axis=-1)
            for y, x in zip(*np.nonzero(near)):
                want = full[y:y + crop, x:x + crop]
                if np.all(np.abs(want - row) <= np.abs(want) / 128 + 1e-3):
                    found = True
                    break
            if found:
                break
        bad += not found
    return bad


class _WindowOver(Exception):
    """Raised from the round hook to leave `run_loop` when the window has
    closed: the loop's own abort path drains the collector, waits out the
    save in flight and writes no final checkpoint."""


def run(ctx):
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.apps.train_loop import (prepare_round_batches,
                                              probe_value, run_loop)
    from sparknet_tpu.data.dataset import ArrayDataset, RoundSampler
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    from sparknet_tpu.schema import Field, Schema
    from sparknet_tpu.utils.logger import Logger

    common, seeded, compare = (ctx.load(n) for n in
                               ("common.py", "seeded.py", "compare.py"))
    tr = ctx.traffic
    spans = common.Spans()
    prog = common.Program(
        ctx, max_rounds=10 ** 9, eval_every=0, resume=False, workdir=ctx.tmp,
        checkpoint_dir=os.path.join(ctx.tmp, "checkpoints"),
        checkpoint_every=int(tr["checkpoint_every"]))
    cfg, trainer = prog.cfg, prog.trainer
    ctx.phase("build")

    images, labels = seeded.corpus(ctx.seed, int(tr["corpus_images"]),
                                   int(tr["image_size"]), prog.n_classes)
    mean = seeded.mean_image(ctx.seed, int(tr["image_size"]))
    schema = Schema(Field("data", "float32", (prog.crop, prog.crop, 3)),
                    Field("label", "int32", (1,)))
    pp = ImagePreprocessor(schema, mean_image=mean, crop=prog.crop,
                           seed=ctx.seed, out_dtype=ctx.config["precision"])
    pp.convert_batch = spans.wrap("preprocess", pp.convert_batch)
    ctx.phase("corpus")

    # the benchmark's span around placement
    place = trainer.place_batches

    def place_batches(batches, compute_dt=None):
        if isinstance(next(iter(batches.values())), jax.Array):
            return place(batches, compute_dt)  # already placed: a passthrough
        with spans.span("h2d"):
            return place(batches, compute_dt)

    trainer.place_batches = place_batches
    trainer.init_state = lambda key: prog.fresh_state()  # the benchmark's weights

    warm = int(tr["warmup_rounds"])
    tracer = common.Tracer(ctx, int(tr["trace_skip_rounds"]),
                           int(tr["trace_rounds"]))
    win = {"open": None, "compiles": None, "program": None}

    class Rows(Logger):
        """run_loop's metrics rows kept in memory, each stamped on arrival:
        a round's row is written when its loss has been fetched, which is
        the round's completion as the loop sees it."""

        def __init__(self):
            super().__init__(path=None, echo=False)
            self.rows = []

        def metrics(self, step, **kv):
            if "loss" not in kv:
                return
            now = time.perf_counter()
            self.rows.append((now, step, kv))
            ctx.phase("round", step=step)
            if step == warm:
                win["open"], win["compiles"] = now, common.CompileCounter.now()
                ctx.phase("warmup")
            elif step > warm and now <= win["open"] + ctx.seconds:
                tracer.round_completed()

    log = Rows()

    def hook(rnd, state):
        if rnd == 0:
            win["program"] = prog.round_readings(state, float("nan"))
        t_open = win["open"]
        if t_open is not None and time.perf_counter() >= t_open + ctx.seconds:
            raise _WindowOver

    dataset = ArrayDataset({"data": images, "label": labels})
    try:
        run_loop(cfg, trainer, dataset, None, log, batch_transform=pp,
                 probe=lambda s: probe_value(s, trainer.net),
                 round_hook=hook, trainer_factory=trainer.resized)
    except _WindowOver:
        pass
    tracer.stop()
    compiles = common.CompileCounter.now() - win["compiles"]
    variants = trainer.compiled_variants()
    traced = tracer.reduce()
    ctx.phase("trace_reduced")

    # `correct`, once the window has closed. The loop's own round 0 has to
    # equal, to the bit, a direct call of the same program on the rows the
    # loop was handed; and the check round on those rows (the same call at
    # the configuration's check_lr_scale) is compared with the reference.
    # Round 0's rows are prepared again by the loop's own feed (sampler ->
    # preprocessor -> cast, keyed on seed and round, so the same rows):
    # holding them through the window would cost the host 4 GB it lacks.
    dt = jnp.dtype(ctx.config["precision"])
    ctx.phase("window_closed")
    batch = prepare_round_batches(
        RoundSampler(dataset, trainer.n_local_devices, cfg.local_batch,
                     cfg.tau, seed=cfg.seed), 0, cfg.tau, cfg.seed, pp, dt)
    loop0 = dict(win["program"], loss=float(log.rows[0][2]["loss"]))
    state, loss = trainer.train_round(prog.fresh_state(), place(batch, dt),
                                      seeded.round_key(ctx.seed, 0))
    direct = prog.round_readings(state, loss)
    del state
    ctx.phase("direct_round")
    drift = max([abs(direct["loss"] - loop0["loss"])]
                + [abs(direct[k][leaf] - loop0[k][leaf])
                   for k in ("update_norms",) for leaf in direct[k]]
                + [abs(d[leaf] - l[leaf]) for d, l in zip(
                    direct["momentum_norms"], loop0["momentum_norms"])
                   for leaf in d])
    program = prog.check_round(place(batch, dt))
    t_ref = time.perf_counter()
    reference = prog.reference_round(
        lambda t, w: (jnp.asarray(batch["data"][t]),
                      jnp.asarray(batch["label"][t])))
    ref_s = time.perf_counter() - t_ref
    ctx.phase("reference")
    checks = prog.checks(program, reference)
    checks.append(compare.exact("loop_round0_drift", drift))
    checks.append(compare.exact("crop_mismatch", _crop_mismatches(
        batch, images, labels, mean, prog.crop, int(tr["crop_check_rows"]),
        ctx.seed)))

    stamps = [t for t, _, _ in log.rows]
    w_stamps, w_losses = common.window_rounds(
        stamps, [kv["loss"] for _, _, kv in log.rows], win["open"],
        ctx.seconds)
    inside = [kv for t, _, kv in log.rows
              if win["open"] < t <= win["open"] + ctx.seconds]
    return ctx.result(
        setup_s=win["open"] - ctx.t0, round_done_s=w_stamps,
        samples_per_round_per_chip=prog.samples_per_round_per_chip,
        losses=w_losses, checks=checks, compiles_in_window=compiles,
        device_kind=prog.device_kind, spans=spans.spans, loop_rows=inside,
        trace=traced,
        notes={"reference_s": ref_s, "compiled_variants": variants,
               "window": (win["open"], win["open"] + ctx.seconds)})
