"""Traffic kind `byte-round`: `token-round`'s closed loop for a dense
byte-level model. Round stacks of byte ids made on the device, a fresh
`[tau, rows, positions]` int32 stack for every (donated) round, the loss
fetched one round late, as the training loop runs it; the trainer, model,
solver and kernels do all the work.

What differs from `token-round` (whose trainer, weights, stacks, check round
and reference call are loaded from the file beside this one): the ids are
bytes, uniform over the model's whole vocabulary; the model scores several
next bytes a position; no layer routes, so `correct` is the first round's
four comparisons alone and the run note carries no expert counters.
`tokens_per_s_per_chip` in the run note reads bytes/s. (`token-round`'s
Program draws its ids over `share.vocab_rows` of the configuration file: a
dense model's file says there that it holds every id.)

Traffic parameters (`traffic/<mix>.json`): `warmup_rounds` before the window
opens, `trace_skip_rounds` / `trace_rounds` for the traced stretch.
"""
from __future__ import annotations

import collections
import os
import time


def program(ctx):
    """`token-round`'s Program over a model without expert layers."""
    token = ctx.load(os.path.join("drivers", "token-round.py"))

    class Program(token.Program):
        def checks(self, program: dict, reference: dict) -> list:
            return self.ctx.load("compare.py").first_round_checks(
                program, reference, self.ctx.reference.LIMITS)

    return Program(ctx)


def run(ctx):
    common, seeded = ctx.load("common.py"), ctx.load("seeded.py")
    spans = common.Spans()
    prog = program(ctx)
    ctx.phase("build")
    trainer = prog.trainer
    make_stack, step_rows = prog.stack_makers()

    def dispatch(state, stack, r):
        with spans.span("train_round"):
            return trainer.train_round(state, stack,
                                       seeded.round_key(ctx.seed, r))

    # round 0, through the window's own call and feed: the one `correct` reads
    first = prog.check_round(make_stack(0))
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
        with spans.span("fetch_loss"):  # one round late
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

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    reference = prog.reference_round(step_rows)
    ref_s = time.perf_counter() - t_ref
    ctx.phase("reference")

    w_stamps, w_losses = common.window_rounds(stamps, losses, t_open,
                                              ctx.seconds)
    rate = ctx.load("metric_math.py").window_rate(
        w_stamps, prog.samples_per_round_per_chip)
    return ctx.result(
        setup_s=t_open - ctx.t0, round_done_s=w_stamps,
        samples_per_round_per_chip=prog.samples_per_round_per_chip,
        losses=w_losses, checks=prog.checks(first, reference),
        compiles_in_window=compiles, device_kind=prog.device_kind,
        spans=spans.spans, trace=tracer.reduce(),
        notes={"reference_s": ref_s, "compiled_variants": variants,
               "tokens_per_s_per_chip": None if rate is None
               else rate * prog.positions,
               "round_losses": [w_losses[0], w_losses[-1]] if w_losses else None})
