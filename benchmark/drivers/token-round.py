"""Traffic kind `token-round`: the trainer's round fed from round stacks of
token ids made on the device. The host does nothing but dispatch: a fresh
`[tau, rows, positions]` int32 stack for every (donated) round, the loss and
the expert layers' counters fetched one round late, as the training loop runs
it. The trainer, model, solver and kernels do all the work; the loop, ingest,
placement, collect and checkpoint layers are bypassed.

The ids come from `--seed` (the benchmark's integer mixer, uniform over the
vocabulary rows this chip holds; one document a row; the targets are the next
and second-next id of the same row, which the model reads off the ids
itself). The weights come from the configuration's `weights_seed` (its
`assumed` says why).

The system under test is built as `train()` builds it: `RunConfig` ->
`resolve_spec` (the configuration file is the model file) -> `build_trainer`.

Traffic parameters (`traffic/<mix>.json`): `warmup_rounds` before the window
opens, `trace_skip_rounds` / `trace_rounds` for the traced stretch.
"""
from __future__ import annotations

import collections
import os
import time

import numpy as np


def token_rows(seeded, seed: int, round_index, t0, nt: int, *, tau: int,
               rows: int, positions: int, vocab: int):
    """`nt` steps from step `t0` of round `round_index`'s stack: ids
    [nt, rows, positions] int32, uniform over [0, vocab). Every id of every
    round differs in its draw; `round_index` and `t0` may be traced."""
    import jax.numpy as jnp
    from jax import lax

    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)
    shape = (nt, rows, positions)
    t, r, p = (lax.broadcasted_iota(jnp.uint32, shape, d) for d in range(3))
    row = (u32(round_index) * jnp.uint32(tau) + t + u32(t0)) * jnp.uint32(rows) + r
    h = seeded._mix((row * jnp.uint32(positions) + p) * jnp.uint32(seeded._GOLD)
                    + jnp.uint32(seed & 0xFFFFFFFF))
    return (seeded._mix(h + jnp.uint32(7)) % jnp.uint32(vocab)).astype(jnp.int32)


class Program:
    """The trainer of a token cell and the benchmark's weights for it."""

    def __init__(self, ctx):
        from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
        from sparknet_tpu.parallel import make_mesh
        from sparknet_tpu.utils.config import RunConfig

        c = ctx.config
        self.ctx, self.config = ctx, c
        self.chips = ctx.cell["chips"]
        assert self.chips == 1 and c.get("workers", 1) == 1, \
            "a token cell is one worker on one chip"
        self.tau, self.rows, self.positions = c["tau"], c["local_batch"], c["seq_len"]
        self.vocab = c["share"]["vocab_rows"][1]
        self.check_lr_scale = float(c["check_lr_scale"])
        self.cfg = RunConfig.from_dict({
            "model": os.path.join(ctx.root, c["model"]), "tau": self.tau,
            "local_batch": self.rows, "precision": c["precision"],
            "solver": dict(c["solver"]), "n_devices": self.chips,
            "seed": ctx.seed, **c.get("run_config", {})})
        spec = resolve_spec(self.cfg, tokens=(self.rows, self.positions))
        self.trainer = build_trainer(self.cfg, spec, make_mesh(self.chips))
        self.device_kind = self.trainer.mesh.devices.flat[0].device_kind
        self.layers = ctx.reference.layer_table(c)

    def params0(self):
        """The benchmark's weights, made anew on the device (a second of
        work): never held beside a running round, whose state and
        temporaries fill the chip."""
        return self.ctx.reference.init_params(self.config["weights_seed"],
                                              self.layers)

    def fresh_state(self):
        return self.trainer.state_from_params(self.params0())

    def stack_makers(self):
        """(make_stack(round) -> {"tokens": [tau, rows, positions]} placed as
        the trainer takes it, step_rows(t, w) -> step t's ids of round 0)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        seeded, seed = self.ctx.load("seeded.py"), self.ctx.seed
        kw = dict(tau=self.tau, rows=self.rows, positions=self.positions,
                  vocab=self.vocab)
        mesh = self.trainer.mesh
        sharding = NamedSharding(mesh, P(None, mesh.axis_names[0]))
        make = jax.jit(lambda r: {"tokens": jax.lax.with_sharding_constraint(
            token_rows(seeded, seed, r, 0, self.tau, **kw), sharding)})
        rows = jax.jit(lambda t: token_rows(seeded, seed, 0, t, 1, **kw)[0])
        return (lambda r: make(jnp.uint32(r)),
                lambda t, w=0: rows(jnp.uint32(t)))

    @property
    def samples_per_round_per_chip(self) -> float:
        return float(self.tau * self.rows)  # a sample is one row

    def check_round(self, stack) -> dict:
        """Round 0 through the window's own call, on the program the window
        runs, from the benchmark's weights, at the configuration's
        `check_lr_scale`: the readings `correct` compares with the
        reference, and the round's own counters. `stack` is consumed."""
        import jax
        import jax.numpy as jnp

        seeded = self.ctx.load("seeded.py")
        state, loss = self.trainer.train_round(
            self.fresh_state(), stack, seeded.round_key(self.ctx.seed, 0),
            lr_scale=self.check_lr_scale)
        counters = self.trainer.last_counters

        @jax.jit
        def norms(params, momentum, params0):
            f32 = lambda x: x.astype(jnp.float32)
            norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
            return (jax.tree.map(lambda p, p0: norm(f32(p[0]) - p0), params, params0),
                    jax.tree.map(lambda m: norm(f32(m[0])), momentum))

        upd, mom = jax.tree.map(float, norms(state.params, state.momentum,
                                             self.params0()))
        flat = lambda tree: {f"{ln}/{pn}": x for ln, lp in tree.items()
                             for pn, x in lp.items()}
        layer, leaf = self.ctx.reference.PROBE_LEAF
        return {"loss": float(loss),
                "probe": [np.asarray(state.momentum[layer][leaf][0])],
                "update_norms": flat(upd), "momentum_norms": [flat(mom)],
                "counters": {b: np.asarray(v) for b, v in (counters or {}).items()}}

    def reference_round(self, rows, precision: str = "float32") -> dict:
        solver = dict(self.config["solver"])
        solver["base_lr"] *= self.check_lr_scale
        return self.ctx.reference.round_reference(
            self.params0, rows, tau=self.tau, solver=solver,
            precision=precision, layers=self.layers,
            mtp_weight=self.config["share"].get("mtp_loss_weight", 0.3),
            devices=list(self.trainer.mesh.devices.flat))

    def routing_diff(self, ids, ref: dict) -> dict:
        """Per expert layer, the share of routed slots (position x chosen
        expert) whose expert differs between the program's forward pass (the
        precision policy's) and the reference's (float32; `ref`, its round's
        "chosen"), both from the benchmark's weights on step 0's ids. The
        router itself is float32 on both sides; what differs is the stream
        it reads."""
        import jax
        from sparknet_tpu import precision

        net, params0 = self.trainer.net, self.params0()
        with precision.policy(self.config["precision"]):
            blobs = jax.jit(lambda p, t: {
                k: v for k, v in net.apply(p, {"tokens": t}).items()
                if k.endswith("_chosen")})(params0, ids)
        prog = {k[:-len("_chosen")]: np.asarray(v) for k, v in blobs.items()}
        return {name: float(np.mean(~np.any(
            ref[name][..., :, None] == prog[name][..., None, :], axis=-1)))
            for name in ref}

    def checks(self, program: dict, reference: dict, routing: dict) -> list:
        compare = self.ctx.load("compare.py")
        limits = self.ctx.reference.LIMITS
        out = compare.first_round_checks(program, reference, limits)
        dropped = sum(float(v[1]) for v in program["counters"].values())
        out.append(compare.exact("moe_dropped_slots", dropped))
        worst = max(routing, key=routing.get)
        out.append(compare.judged({
            "name": "routing_diff_share", "value": routing[worst],
            "layer": worst, "limit": limits["routing_diff_share"],
            "by_layer": routing}))
        return out


def counter_summary(names, rounds: list, steps: int) -> dict:
    """The window's counters as plain numbers, for the readers and the `run`
    note. `rounds`: [{blob: [n] sums over one round's `steps` steps}].
    Returns the slots dropped in all, the slots landed a round (all expert
    layers together), the fullest expert's tokens over the emptiest's (mean
    over the layers of the window's means) and, `by_layer`, each layer's
    slots landed a step and its ratio."""
    if not rounds:
        return {}
    i = {n: names.index(n) for n in names}
    per_layer = {}
    for blob in rounds[0]:
        v = np.mean([r[blob] for r in rounds], axis=0) / steps  # a step's
        per_layer[blob] = {
            "slots_landed_per_step": float(v[i["slots_landed"]]),
            "fullest_over_emptiest": float(
                v[i["expert_tokens_max"]] / max(v[i["expert_tokens_min"]], 1e-9))}
    return {
        "slots_dropped": float(sum(r[b][i["slots_dropped"]]
                                   for r in rounds for b in r)),
        "slots_landed_per_round": float(np.mean(
            [sum(r[b][i["slots_landed"]] for b in r) for r in rounds])),
        "load_max_over_min": float(np.mean(
            [p["fullest_over_emptiest"] for p in per_layer.values()])),
        "by_layer": per_layer}


def run(ctx):
    common, seeded = ctx.load("common.py"), ctx.load("seeded.py")
    spans = common.Spans()
    prog = Program(ctx)
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

    # the reference and the routing comparison, once the program's state is
    # freed: row by row, so they add seconds and fit beside nothing
    t_ref = time.perf_counter()
    reference = prog.reference_round(step_rows)
    ref_s = time.perf_counter() - t_ref
    routing = prog.routing_diff(step_rows(0), reference["chosen"])
    ctx.phase("reference")

    w_stamps, w_losses = common.window_rounds(stamps, losses, t_open,
                                              ctx.seconds)
    in_window = [c for t, c in zip(stamps, counters)
                 if t_open < t <= t_open + ctx.seconds]
    names = list(next(iter(trainer.net.counter_blobs().values()), ()))
    moe = counter_summary(names, in_window, prog.tau)
    math = ctx.load("metric_math.py")
    rate = math.window_rate(w_stamps, prog.samples_per_round_per_chip)
    return ctx.result(
        setup_s=t_open - ctx.t0, round_done_s=w_stamps,
        samples_per_round_per_chip=prog.samples_per_round_per_chip,
        losses=w_losses, checks=prog.checks(program, reference, routing),
        compiles_in_window=compiles, device_kind=prog.device_kind,
        spans=spans.spans, trace=tracer.reduce(),
        notes={"reference_s": ref_s, "compiled_variants": variants,
               "tokens_per_s_per_chip": None if rate is None
               else rate * prog.positions,
               "round_losses": [w_losses[0], w_losses[-1]] if w_losses else None,
               "moe": moe})
