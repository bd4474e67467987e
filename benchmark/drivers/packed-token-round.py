"""Traffic kind `packed-token-round`: `token-round`'s closed loop on rows
that hold SEVERAL DOCUMENTS. Round stacks of token ids made on the device and,
beside them, document ids of the same shape; a fresh pair of `[tau, rows,
positions]` int32 stacks for every (donated) round, the loss and the mixers'
boundary counters fetched one round late, as the training loop runs it; the
trainer, model, solver and kernels do all the work.

What differs from `token-round` (whose trainer, weights, ids, check round and
reference call are loaded from the file beside this one): every step's every
row is documents one behind another -- lengths 2^u, u uniform in
[`log2_min_len`, `log2_max_len`], drawn from `--seed` by the benchmark's
integer mixer, the last cut by the row's end; the document ids count them
from 0, so a row's last id is the boundaries it holds --; the model is fed
`doc_ids` beside `tokens` and reads its targets off both (the next id of the
same row, none at a document's last position); no layer routes, so `correct`
is the first round's four comparisons and ONE exact check: the boundaries
every mixer counted in the check round are those the traffic drew. The run
note carries the window's boundaries a round.

Traffic parameters (`traffic/<mix>.json`): `log2_min_len`, `log2_max_len`;
`warmup_rounds` before the window opens, `trace_skip_rounds` / `trace_rounds`
for the traced stretch.
"""
from __future__ import annotations

import collections
import os
import time

import numpy as np


def document_ids(seeded, seed: int, round_index, t0, nt: int, *, tau: int,
                 rows: int, positions: int, lo: int, hi: int):
    """`nt` steps from step `t0` of round `round_index`'s stack: document ids
    [nt, rows, positions] int32. A row's documents have lengths floor(2^u), u
    uniform in [lo, hi) from the mixer's bits, one behind another from
    position 0 -- enough of them drawn to fill a row of the shortest -- and
    the one that reaches the row's end is cut there. Position p's id is the
    number of documents that end at or before it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)
    most = -(-positions // (1 << lo))                     # documents a row
    shape = (nt, rows, most)
    t, r, k = (lax.broadcasted_iota(jnp.uint32, shape, d) for d in range(3))
    row = (u32(round_index) * jnp.uint32(tau) + t + u32(t0)) * jnp.uint32(rows) + r
    h = seeded._mix(seeded._mix((row * jnp.uint32(most) + k) * jnp.uint32(seeded._GOLD)
                                + jnp.uint32(seed & 0xFFFFFFFF)) + jnp.uint32(0xD0C5))
    u = lo + (hi - lo) * (h >> 8).astype(jnp.float32) / jnp.float32(1 << 24)
    lengths = jnp.clip(jnp.floor(jnp.exp2(u)).astype(jnp.int32), 1 << lo, 1 << hi)
    ends = jnp.cumsum(lengths, axis=-1)                   # [nt, rows, most]
    p = lax.broadcasted_iota(jnp.int32, (nt, rows, positions), 2)
    # the documents that have ended at or before p: a binary search a row
    flat = lambda x: x.reshape((-1, x.shape[-1]))
    return jax.vmap(lambda e, q: jnp.searchsorted(e, q, side="right"))(
        flat(ends), flat(p)).reshape(p.shape).astype(jnp.int32)


def program(ctx):
    """`token-round`'s Program fed document ids beside its token ids."""
    token = ctx.load(os.path.join("drivers", "token-round.py"))

    class Program(token.Program):
        def stack_makers(self):
            """(make_stack(round) -> {"tokens", "doc_ids": [tau, rows,
            positions]} placed as the trainer takes it, step_rows(t, w) ->
            (ids, document ids) of round 0's step t)."""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            seeded, seed = self.ctx.load("seeded.py"), self.ctx.seed
            kw = dict(tau=self.tau, rows=self.rows, positions=self.positions)
            span = dict(lo=int(self.ctx.traffic["log2_min_len"]),
                        hi=int(self.ctx.traffic["log2_max_len"]))
            mesh = self.trainer.mesh
            sharding = NamedSharding(mesh, P(None, mesh.axis_names[0]))
            place = lambda x: jax.lax.with_sharding_constraint(x, sharding)
            make = jax.jit(lambda r: {
                "tokens": place(token.token_rows(seeded, seed, r, 0, self.tau,
                                                 vocab=self.vocab, **kw)),
                "doc_ids": place(document_ids(seeded, seed, r, 0, self.tau,
                                              **kw, **span))})
            rows = jax.jit(lambda t: (
                token.token_rows(seeded, seed, 0, t, 1, vocab=self.vocab, **kw)[0],
                document_ids(seeded, seed, 0, t, 1, **kw, **span)[0]))
            return (lambda r: make(jnp.uint32(r)),
                    lambda t, w=0: rows(jnp.uint32(t)))

        def boundaries_drawn(self, step_rows) -> float:
            """The boundaries round 0's rows hold, all steps together: a
            row's last document id."""
            return float(sum(int(np.asarray(step_rows(t)[1])[:, -1].sum())
                             for t in range(self.tau)))

        def causal_pairs_per_row(self, step_rows) -> float:
            """(query, key) pairs of one document a row of round 0 holds,
            the mean over its rows: the sum over a row's documents of len
            (len + 1) / 2, what the attention core's algorithm needs."""
            lens = [np.bincount(row) for t in range(self.tau)
                    for row in np.asarray(step_rows(t)[1])]
            return float(np.mean([np.sum(n * (n + 1.0) / 2.0) for n in lens]))

        def reference_round(self, rows, precision: str = "float32", **kw) -> dict:
            solver = dict(self.config["solver"])
            solver["base_lr"] *= self.check_lr_scale
            return self.ctx.reference.round_reference(
                self.params0, rows, tau=self.tau, solver=solver,
                precision=precision, layers=self.layers,
                devices=list(self.trainer.mesh.devices.flat), **kw)

        def checks(self, program: dict, reference: dict, drawn: float) -> list:
            compare = self.ctx.load("compare.py")
            out = compare.first_round_checks(program, reference,
                                             self.ctx.reference.LIMITS)
            counted = {b: float(v[0]) for b, v in program["counters"].items()}
            check = compare.exact("doc_boundaries_miscounted", sum(
                abs(v - drawn) for v in counted.values()) if counted
                else float("nan"))  # (no mixer counted: nothing saw the cuts)
            out.append(dict(check, drawn=drawn, counted=counted))
            return out

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
    stamps, losses, counters, pending = [], [], [], collections.deque()
    t_open = compiles_open = None
    r = 1
    stack = make_stack(r)
    while True:
        state, loss = dispatch(state, stack, r)
        with spans.span("make_stack"):
            stack = make_stack(r + 1)
        pending.append((loss, trainer.last_counters))
        r += 1
        if len(pending) < 2:
            continue
        with spans.span("fetch_loss"):  # one round late, counters with it
            loss, dev = pending.popleft()
            losses.append(float(loss))
            counters.append({b: np.asarray(v) for b, v in (dev or {}).items()})
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
    float(pending.popleft()[0])  # drain; the round in flight is not counted
    variants = trainer.compiled_variants()
    del state, stack, pending, loss, dev

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    reference = prog.reference_round(step_rows)
    ref_s = time.perf_counter() - t_ref
    drawn, pairs = prog.boundaries_drawn(step_rows), prog.causal_pairs_per_row(step_rows)
    ctx.phase("reference")

    w_stamps, w_losses = common.window_rounds(stamps, losses, t_open,
                                              ctx.seconds)
    # the window's boundaries a round, as the first mixer counted them
    seen = [float(next(iter(c.values()))[0]) for t, c in zip(stamps, counters)
            if c and t_open < t <= t_open + ctx.seconds]
    rate = ctx.load("metric_math.py").window_rate(
        w_stamps, prog.samples_per_round_per_chip)
    return ctx.result(
        setup_s=t_open - ctx.t0, round_done_s=w_stamps,
        samples_per_round_per_chip=prog.samples_per_round_per_chip,
        losses=w_losses, checks=prog.checks(first, reference, drawn),
        compiles_in_window=compiles, device_kind=prog.device_kind,
        spans=spans.spans, trace=tracer.reduce(),
        notes={"reference_s": ref_s, "compiled_variants": variants,
               "tokens_per_s_per_chip": None if rate is None
               else rate * prog.positions,
               "round_losses": [w_losses[0], w_losses[-1]] if w_losses else None,
               "doc_boundaries": {
                   "check_round_drawn": drawn, "causal_pairs_per_row": pairs,
                   "per_round": float(np.mean(seen)) if seen else None,
                   "documents_per_row": (float(np.mean(seen)) / (
                       prog.tau * prog.rows) + 1.0) if seen else None}})
