"""Traffic kind `device-round`: the trainer's round fed from round stacks made
on the device. The host does nothing but dispatch: a fresh stack for every
(donated) round, the loss fetched one round late, as the training loop runs
it. The trainer, model, solver and kernels do all the work; the loop, ingest,
placement, collect and checkpoint layers are bypassed.

Traffic parameters (`traffic/<mix>.json`): `warmup_rounds` before the window
opens, `trace_skip_rounds` / `trace_rounds` for the traced stretch.
"""
from __future__ import annotations

import collections
import time


def run(ctx):
    common, seeded = ctx.load("common.py"), ctx.load("seeded.py")
    spans = common.Spans()
    prog = common.Program(ctx)
    ctx.phase("build")
    trainer = prog.trainer
    make_stack, step_rows = prog.stack_makers()

    def dispatch(state, stack, r):
        with spans.span("train_round"):
            return trainer.train_round(state, stack,
                                       seeded.round_key(ctx.seed, r))

    # round 0, through the window's own call and feed: the one `correct` reads
    program = prog.check_round(make_stack(0))
    ctx.phase("check_round")
    state = prog.fresh_state()

    # one loop from here on: warm-up completions, then the window's
    warm = int(ctx.traffic["warmup_rounds"])
    tracer = common.Tracer(ctx, int(ctx.traffic["trace_skip_rounds"]),
                           int(ctx.traffic["trace_rounds"]))
    stamps, losses, pending = [], [], collections.deque()
    t_open = compiles_open = None
    r = 1
    stack = make_stack(r)
    while True:
        state, loss = dispatch(state, stack, r)
        with spans.span("make_stack"):
            stack = make_stack(r + 1)
        pending.append(loss)
        r += 1
        if len(pending) < 2:
            continue
        with spans.span("fetch_loss"):
            losses.append(float(pending.popleft()))
        stamps.append(time.perf_counter())
        if t_open is None:
            if len(stamps) >= warm:
                t_open, compiles_open = stamps[-1], common.CompileCounter.now()
                ctx.phase("warmup")
            continue
        tracer.round_completed()
        if stamps[-1] - t_open >= ctx.seconds:
            break
    tracer.stop()
    compiles = common.CompileCounter.now() - compiles_open
    float(pending.popleft())  # drain; the round in flight is not counted
    variants = trainer.compiled_variants()
    del state, stack, pending, loss

    # the reference, once the program's state is freed: step by step, each
    # worker's rows made alone, so it adds seconds and no stack
    t_ref = time.perf_counter()
    reference = prog.reference_round(step_rows)
    ref_s = time.perf_counter() - t_ref

    w_stamps, w_losses = common.window_rounds(stamps, losses, t_open,
                                              ctx.seconds)
    return ctx.result(
        setup_s=t_open - ctx.t0, round_done_s=w_stamps,
        samples_per_round_per_chip=prog.samples_per_round_per_chip,
        losses=w_losses, checks=prog.checks(program, reference),
        compiles_in_window=compiles, device_kind=prog.device_kind,
        spans=spans.spans, trace=tracer.reduce(),
        notes={"reference_s": ref_s, "compiled_variants": variants})
