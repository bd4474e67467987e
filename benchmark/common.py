"""What the drivers share: building the system under test from a configuration
file, the first round's readings and their comparison with the reference,
the benchmark's own spans and compile count, and the traced stretch.

From the program this takes the trainer (`build_trainer`, `train_round`,
`state_from_params`) and nothing else; weights, rows, keys, spans and every
number compared are the benchmark's.
"""
from __future__ import annotations

import contextlib
import glob
import os
import threading
import time

import numpy as np

# -- compiles, process-wide --------------------------------------------------

class CompileCounter:
    """Every executable jax builds or fetches, on any thread (the program's
    `track_compiles` counts one thread; the loop cell has three)."""
    _lock = threading.Lock()
    _count = 0
    _listening = False

    @classmethod
    def _on(cls, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with cls._lock:
                cls._count += 1

    @classmethod
    def now(cls) -> int:
        import jax.monitoring
        with cls._lock:
            if not cls._listening:
                jax.monitoring.register_event_duration_secs_listener(cls._on)
                cls._listening = True
            return cls._count


# -- spans -------------------------------------------------------------------

class Spans:
    """The benchmark's own host spans, around its calls into each layer:
    kept in memory as (start, end) on `time.perf_counter()`, and written into
    the profiler's trace too while one is being taken."""

    def __init__(self):
        self.spans = {}
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax.profiler
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                with self._lock:
                    self.spans.setdefault(name, []).append(
                        (t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped


# -- the system under test ---------------------------------------------------

class Program:
    """The trainer of a cell, built as `train()` builds it, holding the
    benchmark's weights."""

    def __init__(self, ctx, **run_config_over):
        import jax
        from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
        from sparknet_tpu.parallel import make_mesh
        from sparknet_tpu.utils.config import RunConfig

        c = ctx.config
        self.ctx = ctx
        self.chips = ctx.cell["chips"]
        self.tau, self.batch = c["tau"], c["local_batch"]
        self.crop, self.n_classes = c["crop"], c["n_classes"]
        self.check_lr_scale = float(c["check_lr_scale"])
        self.cfg = RunConfig.from_dict({
            "model": c["model"], "n_classes": self.n_classes,
            "crop": self.crop, "tau": self.tau, "local_batch": self.batch,
            "precision": c["precision"], "solver": dict(c["solver"]),
            "n_devices": self.chips, "seed": ctx.seed,
            **c.get("run_config", {}), **run_config_over})
        spec = resolve_spec(self.cfg,
                            data=(self.batch, 3, self.crop, self.crop),
                            label=(self.batch, 1))
        self.trainer = build_trainer(self.cfg, spec, make_mesh(self.chips))
        self.devices = list(self.trainer.mesh.devices.flat)
        self.device_kind = self.devices[0].device_kind
        self.params0 = ctx.reference.init_params(ctx.seed, self.crop,
                                                 self.n_classes)
        jax.block_until_ready(self.params0)

    def fresh_state(self):
        return self.trainer.state_from_params(self.params0)

    def stack_makers(self):
        """(make_stack(round_index) -> a whole round's stack placed as the
        trainer takes it, step_rows(t, w) -> worker w's rows of round 0's
        step t alone): the device-made traffic, jitted once each."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        seeded, seed = self.ctx.load("seeded.py"), self.ctx.seed
        n = self.batch * self.chips
        shape = dict(global_batch=n, tau=self.tau, crop=self.crop,
                     n_classes=self.n_classes,
                     dtype=jnp.dtype(self.ctx.config["precision"]))
        mesh = self.trainer.mesh
        sharding = NamedSharding(mesh, P(None, mesh.axis_names[0]))

        @jax.jit
        def make_stack(round_index):
            data, label = seeded.stack_slice(seed, round_index, 0, self.tau,
                                             0, n, **shape)
            return {"data": jax.lax.with_sharding_constraint(data, sharding),
                    "label": jax.lax.with_sharding_constraint(label, sharding)}

        rows = jax.jit(lambda t, row0: jax.tree.map(
            lambda x: x[0], seeded.stack_slice(seed, 0, t, 1, row0,
                                               self.batch, **shape)))
        return (lambda r: make_stack(jnp.uint32(r)),
                lambda t, w: rows(jnp.uint32(t), jnp.uint32(w * self.batch)))

    @property
    def samples_per_round_per_chip(self) -> float:
        return float(self.tau * self.batch)

    def round_readings(self, state, loss) -> dict:
        """The first round's numbers on the program's side: its loss, and the
        per-leaf norms of its momentum (every worker's) and of its
        parameters' change, worked out on the device from the state the
        round returned. Also how far the replicas are apart after the
        boundary average (0 is the guarantee)."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def norms(params, momentum, params0):
            f32 = lambda x: x.astype(jnp.float32)
            axes = lambda x: tuple(range(1, x.ndim))
            upd = jax.tree.map(
                lambda p, p0: jnp.sqrt(jnp.sum(jnp.square(
                    jnp.mean(f32(p), axis=0) - p0))), params, params0)
            mom = jax.tree.map(
                lambda m: jnp.sqrt(jnp.sum(jnp.square(f32(m)), axis=axes(m))),
                momentum)
            spread = jax.tree.map(
                lambda p: jnp.max(jnp.abs(p - p[:1])), params)
            return upd, mom, spread

        upd, mom, spread = jax.tree.map(
            np.asarray, norms(state.params, state.momentum, self.params0))
        flat = lambda tree, pick: {f"{ln}/{pn}": float(pick(x))
                                   for ln, lp in tree.items()
                                   for pn, x in lp.items()}
        layer, leaf = self.ctx.reference.PROBE_LEAF
        return {"loss": float(loss),
                "probe": list(np.asarray(state.momentum[layer][leaf])),
                "update_norms": flat(upd, lambda x: x),
                "momentum_norms": [flat(mom, lambda x, w=w: x[w])
                                   for w in range(self.chips)],
                "replica_spread": max(flat(spread, lambda x: x).values())}

    def check_round(self, stack) -> dict:
        """Round 0 through the window's own call, on the program the window
        runs, from the benchmark's weights, at the configuration's
        `check_lr_scale` (the round's runtime lr input, so the same
        executable): the readings `correct` compares with the reference.
        `stack` is consumed."""
        seeded = self.ctx.load("seeded.py")
        state, loss = self.trainer.train_round(
            self.fresh_state(), stack, seeded.round_key(self.ctx.seed, 0),
            lr_scale=self.check_lr_scale)
        return self.round_readings(state, loss)

    def reference_round(self, rows, precision: str = "float32") -> dict:
        """The configuration's plain reference over the check round: `rows(t,
        w)` gives worker w's rows of step t. One worker's round on each
        chip."""
        seeded = self.ctx.load("seeded.py")
        solver = dict(self.ctx.config["solver"])
        solver["base_lr"] *= self.check_lr_scale
        return self.ctx.reference.round_reference(
            self.params0, rows, seeded.round_key(self.ctx.seed, 0),
            tau=self.tau, solver=solver, n_workers=self.chips,
            precision=precision, devices=self.devices)

    def checks(self, program: dict, reference: dict) -> list:
        compare = self.ctx.load("compare.py")
        out = compare.first_round_checks(program, reference,
                                         self.ctx.reference.LIMITS)
        if self.chips > 1:
            out.append(compare.exact("replica_spread",
                                     program["replica_spread"]))
        return out


# -- the traced stretch ------------------------------------------------------

class Tracer:
    """With `--trace 1`, a few rounds in the middle of the window are traced
    (a trace of the whole window would be gigabytes): start after
    `skip` window rounds, stop `rounds` + 1 completions later, so the trace
    holds `rounds` whole periods of the round program."""

    def __init__(self, ctx, skip: int, rounds: int):
        self.ctx = ctx
        self.dir = os.path.join(ctx.tmp, "trace") if ctx.trace else None
        self.skip, self.rounds = skip, rounds
        self.state = "idle" if ctx.trace else "off"
        self._seen = 0

    def round_completed(self) -> None:
        """Call once for every completion inside the window."""
        import jax
        if self.state == "off" or self.state == "done":
            return
        self._seen += 1
        if self.state == "idle" and self._seen > self.skip:
            # device events and the benchmark's own annotations only: the
            # Python tracer (every call of every thread) fills the host's
            # memory in a loop cell's thirty traced seconds
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.state, self._seen = "tracing", 0
        elif self.state == "tracing" and self._seen > self.rounds + 1:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self):
        """trace_reduce's numbers for the traced stretch, or None."""
        if self.state != "done":
            return None
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        tr = self.ctx.load("trace_reduce.py")
        try:
            return tr.reduce(tr.read(max(files, key=os.path.getsize)))
        except ValueError as e:  # no device plane, or no whole period in it:
            print(f"trace not reduced: {e}", flush=True)  # the trace metrics
            return None                                   # are then left out


def window_rounds(stamps: list, losses: list, t_open: float, seconds: float):
    """The completions inside the window [t_open, t_open + seconds]: the
    window opens AT a completion (kept as the first stamp, the zero of the
    first interval) and every later completion up to its close counts.
    Returns (stamps, losses of the rounds after the first stamp)."""
    keep = [i for i, t in enumerate(stamps) if t_open <= t <= t_open + seconds]
    return [stamps[i] for i in keep], [losses[i] for i in keep[1:]]
