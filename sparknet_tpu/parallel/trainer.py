"""Distributed data-parallel trainer: τ-local-step parameter averaging on-mesh.

This is the TPU-native re-design of the reference's whole training loop
(reference `apps/CifarApp.scala:100-149`):

    reference (Spark)                        here (one XLA program)
    -----------------------------------     ---------------------------------
    sc.broadcast(netWeights)            →   nothing: params live per-device
    foreach{ setWeights(bcast.value) }  →   (already there after pmean)
    foreachPartition{ τ × solver.step } →   lax.scan of τ jitted SGD steps
    map(getWeights).reduce(add)         →   lax.pmean over the mesh axis
    netWeights.scalarDivide(n) (driver) →   (pmean is already the mean)

Semantics preserved exactly (SURVEY.md §7 "hard parts" #2):
  - τ local SGD steps between averagings, each worker on its own data shard;
  - only the *net weights* are averaged; solver momentum stays worker-local
    and stale across syncs (reference `libs/CaffeNet.scala:123-137` — only
    net blobs cross the wire);
  - τ=1 `sync_sgd` mode averages gradients instead: classic synchronous SGD.

State layout: every leaf of params/momentum carries a leading device axis of
size mesh.n_devices, sharded over the data axis — i.e. each device holds
exactly its own (possibly diverged) replica. After a round the replicas are
numerically identical, but keeping the axis makes divergence-during-τ a
first-class, inspectable thing instead of hidden executor state.

The whole round (τ steps + averaging) is ONE compiled executable: no host
round-trips, weights never leave the devices, the driver only gets scalars.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..model.layers import tp_shards_layer
from ..ops.lrn import pallas_backend
from ..model.net import CompiledNet, PyTree
from ..obs import device as obs_device
from ..obs import trace as obs_trace
from ..solver import SgdSolver, SolverConfig, SolverState
from .mesh import (DATA_AXIS, MODEL_AXIS, local_device_rows, make_mesh,
                   place_global_state, put_device_axis, scan_unroll,
                   shard_map, shard_map_unchecked)


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    """Replicated-per-device training state. Leaves have a leading
    [n_devices] axis sharded over the data mesh axis."""

    params: PyTree
    momentum: PyTree
    it: jnp.ndarray  # [n_devices] int32 (same value everywhere)


class ParallelTrainer:
    """Data-parallel (optionally DPxTP hybrid) trainer.

    mode: "local_sgd" (τ steps then weight pmean — the reference's scheme) or
          "sync_sgd" (per-step gradient pmean, τ must be 1).

    Tensor parallelism (beyond reference parity): pass a 2-D
    ("data", "model") mesh. InnerProduct layers whose num_output is
    divisible by the model-axis size hold column shards of their weights
    (Megatron-style column-parallel + feature all_gather over ICI); conv
    layers are replicated across the model axis. Within a model group every device
    sees the same batch and rng, so replicated params evolve identically;
    weight averaging stays a pmean over the DATA axis only — shard
    identity is preserved. TP is numerically exact: the (data=N, model=M)
    trajectory equals the (data=N) one (oracle-tested).
    """

    def __init__(self, net: CompiledNet, solver_cfg: SolverConfig, mesh: Mesh,
                 tau: int = 10, mode: str = "local_sgd",
                 loss_blob: str = "loss", acc_blob: Optional[str] = None,
                 compute_health: bool = True, elastic_tau: bool = False,
                 donate_batches: bool = False,
                 interpret: bool = False,
                 fused_boundary: bool = False):
        assert mode in ("local_sgd", "sync_sgd")
        if mode == "sync_sgd":
            assert tau == 1, "sync_sgd averages every step; tau must be 1"
        if elastic_tau and mode != "local_sgd":
            raise ValueError("elastic_tau (per-worker local steps) only "
                             "makes sense in local_sgd mode")
        if solver_cfg.iter_size != 1:
            raise ValueError(
                "iter_size > 1 is a single-net accumulation feature "
                "(SgdSolver.step); in the distributed trainer scale "
                "local_batch or tau instead — failing loudly rather than "
                "silently ignoring it")
        assert set(mesh.axis_names) <= {DATA_AXIS, MODEL_AXIS}, (
            f"ParallelTrainer meshes use ('{DATA_AXIS}',) or "
            f"('{DATA_AXIS}', '{MODEL_AXIS}'), got {mesh.axis_names}")
        assert DATA_AXIS in mesh.axis_names, mesh.axis_names
        self.net = net
        self.solver = SgdSolver(net, solver_cfg, loss_blob=loss_blob)
        self.mesh = mesh
        self.tau = tau
        self.mode = mode
        self.loss_blob = loss_blob
        self.acc_blob = acc_blob
        self.n_devices = int(np.prod(mesh.devices.shape))
        self.n_local_devices = len(local_device_rows(mesh))
        self.tp = (int(mesh.shape[MODEL_AXIS])
                   if MODEL_AXIS in mesh.axis_names else 1)
        self.n_data = self.n_devices // self.tp
        self._tp_axis = MODEL_AXIS if self.tp > 1 else None
        if self.tp > 1 and jax.process_count() > 1:
            raise NotImplementedError(
                "multi-host TP: per-host rng/data row slicing assumes a "
                "1-D data mesh — keep the model axis within one host")

        # leading device axis covers the WHOLE mesh (data-major, model-minor
        # — matches mesh.devices.flat for a ("data","model") mesh)
        dev = (P((DATA_AXIS, MODEL_AXIS)) if self.tp > 1 else P(DATA_AXIS))
        self._dev_spec = dev

        # compute_health=False compiles the ORIGINAL round — no isfinite
        # passes over the state, no per-step grad-norm reduction, no extra
        # scalar collectives (for runs that disable the supervisor, e.g.
        # deliberate-divergence fixtures or wire-byte-pinned benchmarks)
        self.compute_health = bool(compute_health)
        # elastic_tau compiles the round with ONE extra traced input: a
        # replicated [n_data] int32 vector of per-worker local-step
        # budgets (heterogeneous pods — the elastic layer shortens a
        # chronically slow worker's τ instead of stalling the barrier).
        # Steps at index >= tau_i are masked no-ops for that worker, so
        # changing the vector NEVER recompiles; a full-τ vector computes
        # the legacy round (the selects pick the updated operand — any
        # residual difference is XLA fusion reassociation at the last
        # ulp, pinned by tests/test_elastic.py). Trainers built without
        # the flag compile the byte-identical legacy round.
        self.elastic_tau = bool(elastic_tau)
        self._tau_vec_dev: Optional[Tuple[Tuple[int, ...], jax.Array]] = None
        #: run Pallas kernels under the Pallas interpreter (CPU parity
        #: tests of the layer path the TPU runs), threaded into every
        #: loss/eval apply
        self.interpret = bool(interpret)
        # donate_batches additionally donates the [tau, global_batch, ...]
        # input buffers to the compiled round: XLA reuses their HBM for
        # round intermediates instead of holding batch + intermediates
        # live simultaneously (lower peak HBM, less allocator churn). The
        # CONTRACT: the caller hands each round a FRESH batch pytree and
        # never touches it again after train_round — device placement
        # (put_device_axis) always allocates new buffers, so the two-slot
        # rotation the train loop runs (round R donated to the executable
        # while the prefetch thread places round R+1) can never write
        # into a buffer the device still owns. Bench/test callers that
        # re-feed one batches dict across rounds must leave this off.
        self.donate_batches = bool(donate_batches)
        # fused_boundary (r8): peel the FINAL τ step out of the scan so
        # the boundary weight-averaging pmean (and the ZeRO momentum
        # average + at-rest re-shard under ShardedTrainer) traces in the
        # SAME region as the last optimizer update. On TPU the rolled
        # scan's while-loop boundary otherwise forces the full-params
        # all-reduce to start strictly after every local step retired;
        # peeled, XLA's latency-hiding scheduler can overlap the early
        # layers' boundary collective with the tail of the final update.
        # Scanned and peeled steps alike read their rows by STEP INDEX
        # out of the whole [tau, ...] stack (`rows_at` in _round_math):
        # the scan runs indices 0..τ-2 and the peeled step reads τ-1, so
        # the stack is never sliced along tau (a `x[:-1]` of it is a copy
        # of τ-1 steps' rows that lives as long as the round does).
        # The peeled round runs the SAME ops on the same values in the
        # same order — pinned bitwise against the unfused two-step
        # (scan-then-average) on the TINY_MLP multi-round trajectory
        # under BOTH trainer impls, health scalars included
        # (tests/test_round_pipeline.py), so the shard_map trainer's
        # semantics are preserved. On conv nets the changed program
        # SHAPE can shift XLA's fusion tiling at the last ulp (the same
        # caveat elastic_tau documents) — the loop-level pin holds at
        # ulp tolerance there. Default OFF for direct-API callers (the
        # donate_batches rule); RunConfig.fused_boundary (default ON)
        # flips it for the train loop.
        self.fused_boundary = bool(fused_boundary)
        # a pallas_call traced inside shard_map has no replication rule,
        # so replication checking goes off exactly where ops/ may route a
        # layer to a Pallas kernel
        self._smap = (shard_map_unchecked if pallas_backend(self.interpret)
                      else shard_map)
        #: first-call-validated batch signatures: `_check_batch` asserts
        #: the tau/divisibility invariants once per (input, shape, dtype,
        #: placement) and steady-state rounds skip straight past them
        self._batch_sigs: set = set()
        self._local_data_groups = max(1, self.n_local_devices // self.tp)
        #: device scalars from the LAST train_round (fetch with float()):
        #: {"grad_norm": sqrt of the psum over workers of each worker's
        #: WORST-step squared grad norm (max-over-τ runs before the psum,
        #: so the wire cost is one scalar; can exceed the true per-step
        #: global norm by up to sqrt(n_data) when workers peak on
        #: different steps), "nonfinite": count of data groups whose
        #: PRE-AVERAGE local round state (τ losses, pre-pmean params,
        #: momentum) went NaN/Inf — floored at 1.0 when only the
        #: post-average params are poisoned (unattributable), "nonfinite_
        #: by_worker": the [n_data] per-worker breakdown (the same psum
        #: carries a one-hot vector instead of a scalar, so the wire cost
        #: is n_data f32 — attribution of a consistently bad host/feed is
        #: argmax of this vector, logged by the train loop on nonfinite
        #: rounds; all-zero when the anomaly has no owner)}. None when
        #: compute_health=False. Kept OFF the train_round return so
        #: existing (state, loss) callers are untouched; the train loop
        #: reads them at its log_every flush — no extra per-round host
        #: sync.
        self.last_health: Optional[Dict[str, jax.Array]] = None
        #: the counters the net's layers return beside their results (an
        #: expert layer's routed slots landed / dropped, its fullest and
        #: emptiest expert: `CompiledNet.counter_blobs`), summed on the
        #: device over the round's steps and over the workers and returned
        #: with the round's other scalars: {blob: [n] f32} DEVICE arrays of
        #: the newest round (`last_counters`) and of the one before it
        #: (`counter_values()` reads that one: done, or all but, whenever it
        #: is asked). None for a net without such layers, whose compiled
        #: round is the one it always was.
        self.counter_blobs = net.counter_blobs()
        self.last_counters: Optional[Dict[str, jax.Array]] = None
        self._settled_counters: Optional[Dict[str, jax.Array]] = None
        self._lr_scale_dev: Optional[Tuple[float, jax.Array]] = None
        #: optional PhaseTimers (utils/metrics.py): when the train loop
        #: installs one, train_round's phases — "round_keys", "h2d" (the
        #: host->device batch placement in _shard_batches) and "dispatch"
        #: (the compiled round's enqueue) — also accumulate there (the
        #: step-time breakdown's t_h2d_ms column, /status phase_means).
        #: The spans themselves need no timers: see `_phase`.
        self.phase_timers = None
        #: rounds dispatched by this trainer: the `step` its spans carry
        self._dispatched = 0
        #: argument shapes + shardings of the first dispatch, remembered
        #: once for `program_report` (obs/device.py) — nothing per round
        self._round_avals = None
        self._report = None
        self._compile()
        # the newest trainer answers `program_report("train_round")`: a
        # reader of a trace holds no trainer (strong: the reader may run
        # when its caller has let the trainer go), and stamps the round's
        # entries of the compile log with the step that compiled
        obs_device.register_program(
            "train_round", self.program_report,
            stamp=lambda: {"step": self._dispatched - 1})

    #: checkpoint/state-layout tag ("replica": every leaf carries the
    #: leading [n_devices] axis; the NamedSharding trainer overrides with
    #: "logical") — stamped into checkpoint `extra` so restore can route
    #: between the layouts
    state_layout = "replica"

    def _health_specs(self):
        """out_specs of the round's scalars besides the loss: the health
        scalars and, for a net that has them, the layers' counters."""
        specs = ({"grad_norm": P(), "nonfinite": P(),
                  "nonfinite_by_worker": P()}
                 if self.compute_health else {})
        if self.counter_blobs:
            specs["counters"] = P()
        return specs

    def _compile(self) -> None:
        """Build the jitted round + eval executables. The state lives on
        the mesh as [n_devices]-leading-axis leaves sharded over the whole
        device axis; batches are [tau, global_batch, ...] sharded over
        data only (TP replicas consume identical examples). Subclasses
        with a different state layout override this (and only this plus
        the state-construction methods) — the round MATH is shared via
        `_round_math`."""
        dev = self._dev_spec
        state_specs = TrainState(params=dev, momentum=dev, it=dev)
        extra_specs = (P(),) if self.elastic_tau else ()
        # stable program names: the modules are `jit_train_round` and
        # `jit_eval_round` in a device trace and in the compiled text
        self._round = jax.jit(
            named("train_round", self._smap(
                self._round_impl, mesh=self.mesh,
                in_specs=(state_specs, P(None, DATA_AXIS),
                          P(DATA_AXIS), P()) + extra_specs,
                out_specs=(state_specs, P(), self._health_specs()))),
            donate_argnums=(0, 1) if self.donate_batches else (0,))
        self._eval = jax.jit(named("eval_round", self._smap(
            self._eval_impl, mesh=self.mesh,
            in_specs=(dev, P(DATA_AXIS)), out_specs=P())))

    def compiled_variants(self) -> int:
        """Entries in the jitted round's executable cache — 1 in steady
        state; growth means something keeps retriggering XLA compilation
        (a drifting batch shape/dtype, a layout change). The train loop
        exports this as the `sparknet_train_round_compiled_variants`
        gauge so jit-cache churn shows up on a scrape instead of as an
        unexplained slow round."""
        return int(self._round._cache_size())

    # -- state construction --------------------------------------------------

    def _tp_sharded_layers(self) -> set:
        """Layer names whose params are column-sharded across the model
        axis (the shared `tp_shards_layer` convention)."""
        return {l.name for l in self.net.spec.layers
                if tp_shards_layer(l, self.tp)}

    def init_state(self, key: jax.Array) -> TrainState:
        """Identical initial params on every device (the reference seeds all
        workers from worker-0's weights, `apps/CifarApp.scala:98`)."""
        return self.state_from_params(self.net.init_params(key))

    @obs_trace.startup_span("state_from_params")
    def state_from_params(self, params: PyTree,
                          momentum: Optional[PyTree] = None,
                          it: int = 0) -> TrainState:
        """Build a device TrainState from ONE logical (full, unsharded)
        copy of the params — tiled across data groups and column-sharded
        per the TP convention. `momentum`/`it` seed the optimizer state
        (zeros / 0 for a fresh run; a reassembled average for elastic
        resume)."""
        tp_layers = self._tp_sharded_layers()

        def expand(lname: str, pname: str, x: jnp.ndarray) -> jnp.ndarray:
            x = jnp.asarray(x)
            if lname in tp_layers:
                # device row d = (data d//tp, model d%tp): model rank takes
                # its column shard, repeated across the data groups
                axis = 1 if pname == "w" else 0
                shards = jnp.split(x, self.tp, axis=axis)
                return jnp.stack([shards[d % self.tp]
                                  for d in range(self.n_devices)])
            return jnp.broadcast_to(x[None], (self.n_devices,) + x.shape)

        def expand_tree(tree):
            return {l: {p: expand(l, p, x) for p, x in lp.items()}
                    for l, lp in tree.items()}

        params_dev = expand_tree(params)
        vdt = jnp.dtype(self.solver.cfg.velocity_dtype)
        state = TrainState(
            params=params_dev,
            momentum=(expand_tree(momentum) if momentum is not None
                      else jax.tree.map(
                          lambda w: jnp.zeros(w.shape, vdt), params_dev)),
            it=jnp.full((self.n_devices,), int(it), jnp.int32))
        return self.place(state)

    def adapt_state(self, flat: Dict[str, np.ndarray],
                    old_tp: int = 1,
                    momentum_policy: str = "norm_rescale",
                    old_layout: str = "replica") -> TrainState:
        """ELASTIC resume: rebuild a TrainState for THIS topology from a
        checkpoint taken on a different one (`checkpoint.restore_flat`
        output; keys 'params/<layer>/<blob>', 'momentum/...', 'it').

        `old_layout="logical"` accepts a ShardedTrainer checkpoint
        (logical full params, momentum as [n_data] worker rows or one
        ZeRO-averaged tree): params re-tile exactly; worker momentum rows
        map 1:1 onto devices when the data-group count matches (tp == 1),
        else reconstruct per `momentum_policy`.

        Params are exact — post-round replicas are identical, so data
        group 0's (reassembled) copy IS the model. Momentum is worker-
        local state with no continuity across a topology change (the
        reference had no resume at all, and momentum is stale-by-design
        across rounds anyway, SURVEY §7 hard-part #2); `momentum_policy`
        picks the reconstruction:

          norm_rescale (default)  mean over the old data groups, rescaled
                                  back to the average per-worker norm
                                  (averaging k decorrelated velocities
                                  shrinks the norm ~1/sqrt(k))
          average                 plain mean (the r4 default)
          zero                    fresh zeros

        A/B'd (r5, `scripts/elastic_momentum_ab.py`, ELASTIC_AB_r05.json:
        3 seeds x {8->4, 8->2} x 8 post-resume rounds, TINY_MLP scale):
        norm_rescale edged out averaging in all 6 cells, but the margins
        are sub-point (8->4 max 9.9% vs 10.5%; 8->2 30.8% vs 31.2%) and
        the evidence is small-model-only — treat the two as roughly
        equivalent until the A/B is rerun at CaffeNet shapes
        (scripts/parity_caffenet.py infra exists; ADVICE r5 #5).
        Zero-reset was uniformly WORST (8->4 max 31%, 8->2 38% —
        restarting momentum costs more than averaging's blur), which is
        the one solid conclusion. Measured band for the default:
        <=10% loss inflation at 8->4, <=31% at 8->2, asserted at 15%/40%
        by tests/test_apps.py::test_elastic_resume_momentum_trajectory_band.
        A same-topology pass bypasses the policy entirely: every worker's
        own momentum row is restored as written, so a non-elastic resume
        through this path is exact."""
        assert momentum_policy in ("average", "zero", "norm_rescale"), (
            momentum_policy)
        if old_layout == "logical":
            return self._adapt_logical(flat, momentum_policy)
        old_tp_layers = {l.name for l in self.net.spec.layers
                         if tp_shards_layer(l, old_tp)}

        def reduce_momentum(rows: np.ndarray) -> np.ndarray:
            return reduce_momentum_rows(rows, momentum_policy)

        def reassemble(kind: str, lname: str, pname: str,
                       x: np.ndarray) -> np.ndarray:
            reduce = ((lambda rows: rows[0]) if kind == "params"
                      else reduce_momentum)
            if lname in old_tp_layers:
                axis = 1 if pname == "w" else 0
                return np.concatenate(
                    [reduce(x[j::old_tp]) for j in range(old_tp)],
                    axis=axis)
            return reduce(x)

        old_n_dev = next((np.asarray(a).shape[0] for k, a in flat.items()
                          if not k.startswith("it")), None)
        same_topology = (old_n_dev == self.n_devices and old_tp == self.tp)
        trees: Dict[str, PyTree] = {"params": {}, "momentum": {}}
        it = 0
        for key, arr in flat.items():
            parts = key.split("/")
            if parts[0] == "it":
                it = int(np.asarray(arr).reshape(-1)[0])
                continue
            kind, lname, pname = parts
            # SAME topology: every worker's own momentum row survives as
            # written — no reconstruction policy applies, the resume is
            # exact (the r5 A/B made the elastic policy norm-rescaling,
            # which must never perturb a non-elastic resume) and the
            # reassembly (f32 means + norms over every row) is skipped
            trees[kind].setdefault(lname, {})[pname] = (
                jnp.asarray(arr) if same_topology
                else reassemble(kind, lname, pname, arr))
        if same_topology:
            return self.place(TrainState(
                params=trees["params"], momentum=trees["momentum"],
                it=jnp.full((self.n_devices,), it, jnp.int32)))
        return self.state_from_params(trees["params"],
                                      momentum=trees["momentum"], it=it)

    def _adapt_logical(self, flat: Dict[str, np.ndarray],
                       momentum_policy: str) -> TrainState:
        """adapt_state's logical-layout branch (see its docstring)."""
        params: PyTree = {}
        mom_rows: PyTree = {}
        it = 0
        for key, arr in flat.items():
            parts = key.split("/")
            if parts[0] == "it":
                it = int(np.asarray(arr).reshape(-1)[0])
                continue
            kind, lname, pname = parts
            (params if kind == "params"
             else mom_rows).setdefault(lname, {})[pname] = np.asarray(arr)
        rows_exact = self.tp == 1 and mom_rows and all(
            m.ndim == np.asarray(params[l][p]).ndim + 1
            and m.shape[0] == self.n_devices
            for l, lp in mom_rows.items() for p, m in ((p, lp[p])
                                                       for p in lp))
        if rows_exact:
            # each logical worker row IS that device's momentum (tp == 1:
            # data groups == devices) — the exact, policy-free mapping
            return self.place(TrainState(
                params={l: {p: jnp.broadcast_to(
                    jnp.asarray(x)[None], (self.n_devices,) + x.shape)
                    for p, x in lp.items()} for l, lp in params.items()},
                momentum={l: {p: jnp.asarray(m) for p, m in lp.items()}
                          for l, lp in mom_rows.items()},
                it=jnp.full((self.n_devices,), it, jnp.int32)))
        momentum = {l: {p: (reduce_momentum_rows(m, momentum_policy)
                            if m.ndim == np.asarray(params[l][p]).ndim + 1
                            else m)
                        for p, m in lp.items()}
                    for l, lp in mom_rows.items()} or None
        return self.state_from_params(params, momentum=momentum, it=it)

    def place(self, state: TrainState) -> TrainState:
        """Re-place a (possibly host/numpy) TrainState onto the mesh sharding
        the jitted round expects — required after checkpoint restore, else
        every subsequent round recompiles for the foreign layout. Leaves
        carry the GLOBAL device axis; under multi-host each process
        contributes its own devices' rows.

        The momentum dtype is part of that layout: a checkpoint taken under
        a different SolverConfig.velocity_dtype would otherwise ride along
        uncast and silently override the configured knob for the rest of
        the run, so it is cast here (both the same-topology and the
        elastic-resume path funnel through place)."""
        vdt = jnp.dtype(self.solver.cfg.velocity_dtype)
        if any(x.dtype != vdt for x in jax.tree.leaves(state.momentum)):
            state = dataclasses.replace(
                state, momentum=jax.tree.map(
                    lambda x: jnp.asarray(x).astype(vdt)
                    if x.dtype != vdt else x, state.momentum))
        return place_global_state(state, self.mesh, self._dev_spec)

    def averaged_params(self, state: TrainState) -> PyTree:
        """Single logical copy of the (already synchronized) params. Under
        TP, the column shards of data group 0 are concatenated back into
        full weights (export/checkpoint-compat view)."""
        if self.tp == 1:
            return jax.tree.map(lambda x: x[0], state.params)
        tp_layers = self._tp_sharded_layers()
        out: PyTree = {}
        for lname, lp in state.params.items():
            out[lname] = {}
            for pname, x in lp.items():
                if lname in tp_layers:
                    axis = 1 if pname == "w" else 0
                    out[lname][pname] = jnp.concatenate(
                        [x[j] for j in range(self.tp)], axis=axis)
                else:
                    out[lname][pname] = x[0]
        return out

    # -- one training round (runs INSIDE shard_map; axis = DATA_AXIS) --------

    def _round_impl(self, state: TrainState, batches, rng, lr_scale,
                    tau_vec=None):
        # shapes here are per-device: params [1, ...]; batches [tau, local_b, ...]
        params = jax.tree.map(lambda x: x[0], state.params)
        momentum = jax.tree.map(lambda x: x[0], state.momentum)
        it = state.it[0]
        rng = rng[0]
        # heterogeneous τ: THIS worker's local-step budget out of the
        # replicated per-worker vector (elastic_tau trainers only)
        my_tau = (tau_vec[lax.axis_index(DATA_AXIS)]
                  if tau_vec is not None else None)
        params, sstate, mean_loss, health = self._round_math(
            params, momentum, it, batches, rng, lr_scale, my_tau)
        new_state = TrainState(
            params=jax.tree.map(lambda x: x[None], params),
            momentum=jax.tree.map(lambda x: x[None], sstate.momentum),
            it=sstate.it[None],
        )
        return new_state, mean_loss, health

    def _round_math(self, params, momentum, it, batches, rng, lr_scale,
                    my_tau):
        """The round's MATH on per-device logical views (params/momentum
        without any device axis): τ local SGD steps, weight averaging over
        the data axis, health scalars. Runs INSIDE shard_map; shared
        verbatim by both state layouts (ParallelTrainer's [n_devices]
        replica rows and ShardedTrainer's NamedSharding-placed logical
        state) so the parity suite can pin them bitwise. Returns (params,
        SolverState, mean_loss, health)."""
        loss_fn = self.net.loss_fn(self.loss_blob, tp_axis=self._tp_axis,
                                   tp_size=self.tp,
                                   interpret=self.interpret)
        tp_layers = self._tp_sharded_layers()

        def fix_tp_grads(grads):
            """SPMD autodiff of the replicated-downstream TP program sums
            every replica's (identical) loss: column-shard grads come back
            x tp (the gather's psum-scatter transpose), and each replica's
            backbone grad carries ONLY its own shard's term (x tp). The
            exact logical gradient is shards / tp and backbone pmean'd over
            the model axis (= sum of per-shard terms / tp)."""
            if self._tp_axis is None:
                return grads
            return {l: (jax.tree.map(lambda g: g / self.tp, lp)
                        if l in tp_layers
                        else lax.pmean(lp, self._tp_axis))
                    for l, lp in grads.items()}

        @jax.named_scope("tau_step")
        def local_step(carry, inputs):
            # `tau_step` (scanned and peeled alike) is the scope
            # obs.device.program_report splits into forward / backward /
            # optimizer; what the round runs outside it is `outside_step`
            params, sstate = carry
            if my_tau is None:
                batch, step_rng = inputs
            else:
                batch, step_rng, step_idx = inputs
            (loss, blobs), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, step_rng),
                has_aux=True)(params)
            counters = {b: blobs[b] for b in self.counter_blobs}
            grads = fix_tp_grads(grads)
            # health signal: this step's LOCAL squared gradient norm (a
            # per-leaf reduction fused into the compiled step, no host
            # sync). Taken BEFORE the sync_sgd pmean so the later psum
            # yields the true concatenated-across-workers norm in both
            # modes — post-pmean it would inflate by sqrt(n_data).
            grad_sq = (sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in jax.tree.leaves(grads))
                       if self.compute_health else jnp.zeros((), jnp.float32))
            if self.mode == "sync_sgd":
                grads = lax.pmean(grads, DATA_AXIS)
                loss = lax.pmean(loss, DATA_AXIS)
            new_params, new_sstate = self.solver.update(
                params, sstate, grads, lr_scale=lr_scale)
            if my_tau is not None:
                # heterogeneous τ: steps past THIS worker's budget are
                # no-ops — params/momentum carry through unchanged and
                # the step's loss/grad_sq leave the health statistics
                # (a full-τ vector selects the updated operand on every
                # step, reproducing the unmasked round to the last ulp
                # of XLA's fusion choices). The `it` schedule clock
                # still advances by the nominal τ on every worker: the
                # LR policy must not diverge across the pod.
                active = step_idx < my_tau

                def keep(n, o):
                    return jnp.where(active, n, o)

                new_params = jax.tree.map(keep, new_params, params)
                new_sstate = SolverState(
                    momentum=jax.tree.map(keep, new_sstate.momentum,
                                          sstate.momentum),
                    it=new_sstate.it)
                loss = jnp.where(active, loss, 0.0)
                grad_sq = jnp.where(active, grad_sq, 0.0)
            return (new_params, new_sstate), (loss, grad_sq, counters)

        def rows_at(i):
            # the ONLY place the round reads its rows: step i's
            # [local_b, ...] slab, indexed out of the closed-over
            # (donated) [tau, ...] stack. Never slice the stack along
            # tau: all-but-one step's rows is a copy (3.96 GB of a
            # 4.04 GB CaffeNet stack at tau=50) that lives as long as
            # the round and holds the HBM the next round needs. Stays
            # outside `tau_step`: a row read is the round's work, not
            # the step's.
            return jax.tree.map(
                lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                batches)

        def step_at(carry, step_idx, step_rng, rows):
            return local_step(
                carry, ((rows, step_rng) if my_tau is None
                        else (rows, step_rng, step_idx)))

        def scanned_step(carry, idx_rng):
            step_idx, step_rng = idx_rng
            return step_at(carry, step_idx, step_rng, rows_at(step_idx))

        step_rngs = jax.random.split(rng, self.tau)
        # fused τ-boundary (ctor comment): scan steps 0..τ-2, then run
        # step τ-1 PEELED inline so the boundary average below shares
        # its trace region — same math, same order, bitwise. Unfused,
        # the scan runs all τ steps.
        n_scanned = self.tau - 1 if self.fused_boundary else self.tau
        carry = (params, SolverState(momentum=momentum, it=it))
        step_idxs = jnp.arange(n_scanned)
        if self.fused_boundary:
            # the peeled step's rows are read BEFORE the scan and pinned
            # there (the barrier ties them to the indices the scan runs
            # on). Left to itself XLA sinks this read below the loop, and
            # with it there the TPU compiler fills the loop body's VMEM
            # with other prefetches: every scanned step measured 0.5 ms
            # slower on a v5e (PERF.md §6, PR 26). Costs one step's rows
            # (79 MB at CaffeNet's size) held through the scan.
            last_rows = rows_at(self.tau - 1)
            step_idxs, last_rows = lax.optimization_barrier(
                (step_idxs, last_rows))
        outs = []  # (losses, grad_sqs, counters) of each stretch of steps
        if n_scanned:  # τ=1 fused: the whole round is scan-free
            carry, scanned = lax.scan(
                scanned_step, carry, (step_idxs, step_rngs[:n_scanned]),
                unroll=scan_unroll(n_scanned))
            outs.append(scanned)
        if self.fused_boundary:
            carry, last = step_at(
                carry, self.tau - 1, step_rngs[-1], last_rows)
            outs.append(jax.tree.map(lambda x: x[None], last))
        params, sstate = carry
        losses, grad_sqs, counters = jax.tree.map(
            lambda *xs: jnp.concatenate(xs), *outs)
        return self._tau_boundary(params, sstate, losses, grad_sqs, my_tau,
                                  counters)

    @jax.named_scope("tau_boundary")
    def _tau_boundary(self, params, sstate, losses, grad_sqs, my_tau,
                      counters=None):
        """What the round does once the τ steps are done: the weight
        average over the data axis, the round's mean loss and the health
        reductions — one scope (`tau_boundary`) in the compiled program."""
        # pre-average view: after the pmean one poisoned worker's NaN is
        # every worker's NaN, so ATTRIBUTION must read the worker-local
        # state (τ-step losses, pre-average params, momentum) first
        local_params = params
        if self.mode == "local_sgd":
            # THE sync: weight averaging as an in-pod allreduce OVER THE
            # DATA AXIS ONLY — under TP each model rank averages its own
            # column shard with its peers. Momentum is deliberately NOT
            # averaged (reference parity, SURVEY §7).
            params = lax.pmean(params, DATA_AXIS)
        if my_tau is None:
            mean_loss = lax.pmean(jnp.mean(losses), DATA_AXIS)
        else:
            # masked steps contributed zero loss: average over the steps
            # THIS worker actually ran, then equal-weight across workers
            # (each worker's own-trajectory mean, the τ-averaging view)
            mean_loss = lax.pmean(
                jnp.sum(losses)
                / jnp.maximum(my_tau.astype(jnp.float32), 1.0),
                DATA_AXIS)

        # -- on-device health scalars (utils/health.py is the host half) --
        # global gradient norm: each worker's WORST-step squared norm,
        # summed across workers (the max-over-τ runs BEFORE the psum so
        # the wire cost is one f32 scalar, tau-invariant — the collective
        # pins in tests/test_collectives.py hold). NaN/Inf detection runs
        # on the round's OUTPUTS (losses + post-averaging params/momentum):
        # a nonfinite gradient necessarily poisons the updated params, so
        # one reduction per leaf per ROUND suffices — no per-step isfinite.
        health = {}
        if self.compute_health:
            grad_norm = jnp.sqrt(lax.psum(jnp.max(grad_sqs), DATA_AXIS))
            # per-worker attribution rides the SAME psum: each data group
            # contributes a one-hot [n_data] row instead of a scalar, so
            # one all-reduce yields both the breakdown (which worker's
            # shard went nonfinite — a consistently bad host/feed shows
            # up as a hot index) and, by summing, the scalar count. The
            # flag is computed over the PRE-average local state (losses,
            # pre-pmean params, worker-local momentum): post-average
            # params are replica-identical, so they can flag a round but
            # never localize it. Wire cost grows 4 B -> 4*n_data B,
            # still noise next to the param all-reduce.
            finite_local = jnp.all(jnp.isfinite(losses))
            for leaf in (jax.tree.leaves(local_params)
                         + jax.tree.leaves(sstate.momentum)):
                finite_local &= jnp.all(
                    jnp.isfinite(leaf.astype(jnp.float32)))
            my_row = (jnp.arange(self.n_data)
                      == lax.axis_index(DATA_AXIS)).astype(jnp.float32)
            # post-average params stay the AUTHORITY for the scalar: a
            # poisoned average over clean local state (an overflow born
            # in the pmean itself) must still trip the supervisor, just
            # without a worker index to blame. The flag rides the SAME
            # psum as slot [n_data] (a separate scalar collective would
            # both add an op to the pinned wire profile and — in
            # sync_sgd, where no pmean touches the params — leave
            # shard_map unable to infer its replication).
            finite_avg = jnp.asarray(True)
            for leaf in jax.tree.leaves(params):
                finite_avg &= jnp.all(
                    jnp.isfinite(leaf.astype(jnp.float32)))
            summed = lax.psum(jnp.concatenate([
                my_row * (~finite_local).astype(jnp.float32),
                (~finite_avg).astype(jnp.float32)[None]]), DATA_AXIS)
            by_worker = summed[:-1]
            nonfinite = jnp.maximum(jnp.sum(by_worker),
                                    jnp.minimum(summed[-1], 1.0))
            if self._tp_axis is not None:
                # numerically (near-)no-ops — TP replicas compute identical
                # flags; clears the model-axis vma so P() typechecks
                grad_norm = lax.pmean(grad_norm, self._tp_axis)
                nonfinite = lax.pmean(nonfinite, self._tp_axis)
                by_worker = lax.pmean(by_worker, self._tp_axis)
            health = {"grad_norm": grad_norm, "nonfinite": nonfinite,
                      "nonfinite_by_worker": by_worker}
        if counters:
            # the layers' counters: each step's [n] vector summed over the
            # round's steps and over the workers
            total = lax.psum(jax.tree.map(lambda c: jnp.sum(c, axis=0),
                                          counters), DATA_AXIS)
            if self._tp_axis is not None:
                total = lax.pmean(total, self._tp_axis)
            health = dict(health, counters=total)
        if self._tp_axis is not None:
            # numerically a no-op (TP replicas compute identical losses);
            # clears the model-axis vma so the P() out_spec typechecks
            mean_loss = lax.pmean(mean_loss, self._tp_axis)
        return params, sstate, mean_loss, health

    # -- distributed eval ----------------------------------------------------

    def _eval_impl(self, params, batch):
        params = jax.tree.map(lambda x: x[0], params)
        blobs = self.net.apply(params, batch, train=False,
                               tp_axis=self._tp_axis, tp_size=self.tp,
                               interpret=self.interpret)
        acc_blob = self.acc_blob or _find_accuracy_blob(self.net)
        n = next(iter(batch.values())).shape[0]
        correct = blobs[acc_blob] * n
        total_correct = lax.psum(correct, DATA_AXIS)
        total_n = lax.psum(jnp.asarray(n, jnp.float32), DATA_AXIS)
        acc = total_correct / total_n
        if self._tp_axis is not None:
            acc = lax.pmean(acc, self._tp_axis)  # replicas agree; clears vma
        return acc

    # -- public API ----------------------------------------------------------

    #: run_loop keys LR backoff on this: the layer-IR solver takes a
    #: runtime lr_scale; the graph backend's in-graph optimizer does not
    supports_lr_scale = True

    def train_round(self, state: TrainState, batches: Dict[str, np.ndarray],
                    rng: jax.Array, lr_scale: float = 1.0,
                    tau_by_worker=None) -> Tuple[TrainState, float]:
        """One outer round: τ local steps per device + averaging.

        `batches[input]` has shape [tau, host_batch, ...] with host_batch =
        (locally-addressable devices) × per-device batch; sharded over
        devices along axis 1. Single-process, host_batch == the global
        batch; multi-host, each process passes only its own hosts' examples
        (disjoint data — the reference's per-executor partitions). Values
        may instead be PRE-PLACED device arrays from `place_batches` (the
        explicit contract documented there): the `h2d` phase then costs
        nothing at dispatch. With `donate_batches`, this call CONSUMES the
        batch buffers — feed fresh ones each round.

        `lr_scale` multiplies the lr-policy rate for this round (health
        supervisor backoff; a traced input, so changing it does not
        recompile). Health scalars from the round land in `last_health`
        as device scalars — see its comment.

        `tau_by_worker` (elastic_tau trainers only): per-data-group
        local-step budgets, clipped to [1, tau] — worker i executes its
        first tau_i scan steps and carries its state unchanged through
        the rest (heterogeneous pods; a traced input like lr_scale, so
        adapting never recompiles). None = full τ everywhere, which is
        numerically identical to a non-elastic trainer's round.
        """
        step = self._dispatched
        self._dispatched += 1
        with obs_trace.span("train_round", step=step):
            with self._phase("round_keys", step):
                # one rng row per DATA group, same on every host; TP
                # replicas in a model group share the row (dropout masks
                # must agree on the gathered activations)
                rngs = jax.random.split(rng, self.n_data)
                rngs = place_global_state(rngs, self.mesh, P(DATA_AXIS))
            if self._lr_scale_dev is None or \
                    self._lr_scale_dev[0] != float(lr_scale):
                self._lr_scale_dev = (float(lr_scale),
                                      jnp.asarray(lr_scale, jnp.float32))
            if self.elastic_tau:
                vec = (tuple(int(min(self.tau, max(1, t)))
                             for t in tau_by_worker)
                       if tau_by_worker is not None
                       else (self.tau,) * self.n_data)
                assert len(vec) == self.n_data, (
                    f"tau_by_worker has {len(vec)} entries for "
                    f"{self.n_data} data groups")
                if self._tau_vec_dev is None or self._tau_vec_dev[0] != vec:
                    self._tau_vec_dev = (vec, jnp.asarray(vec, jnp.int32))
                extra = (self._tau_vec_dev[1],)
            else:
                if tau_by_worker is not None:
                    raise ValueError("tau_by_worker requires a trainer "
                                     "built with elastic_tau=True")
                extra = ()
            with self._phase("h2d", step):
                sharded = self._shard_batches(batches)
            args = (state, sharded, rngs, self._lr_scale_dev[1]) + extra
            if self._round_avals is None:
                # an uncommitted scalar (lr_scale) goes wherever jit puts
                # it: no sharding of its own
                self._round_avals = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=x.sharding if x.committed else None),
                    args)
            with self._phase("dispatch", step):
                new_state, loss, health = self._round(*args)
        if self.counter_blobs:
            health = dict(health)
            self._settled_counters = self.last_counters
            self.last_counters = health.pop("counters")
        self.last_health = health or None  # {} when compute_health=False
        return new_state, loss

    def counter_values(self) -> Dict[str, Dict[str, float]]:
        """{layer blob: {counter: value}} of the last round but one (the
        newest whose numbers are on the device for certain, so reading them
        waits for nothing: a scrape must be cheap), else of the only round
        there has been; {} for a net without counters or before a round.
        Sums over the round's steps and workers."""
        dev = self._settled_counters or self.last_counters
        if not dev:
            return {}
        return {blob: dict(zip(self.counter_blobs[blob],
                               map(float, np.asarray(vec))))
                for blob, vec in dev.items()}

    def _phase(self, name: str, step: int):
        """One phase of `train_round`: the installed PhaseTimers' phase
        (which emits the span itself) or a plain span — the same names
        either way, one code path."""
        timers = self.phase_timers
        return (timers.phase(name, step=step) if timers is not None
                else obs_trace.span(name, step=step))

    def program_report(self) -> Optional[Dict[str, Any]]:
        """The compiled round's account of itself (`obs.device.
        program_report("train_round")`): lowered and compiled again for the
        argument shapes and shardings of the first dispatch (a persistent-
        cache hit where the cache is on), its text parsed once. None before
        the first dispatch. Never on the round path."""
        if self._report is None and self._round_avals is not None:
            traced = self._round.trace(*self._round_avals)
            self._report = obs_device.report_of_compiled(
                traced.lower().compile(), self.net.kept_makers(),
                traced.jaxpr.jaxpr, self.net.attention_scopes(),
                self.net.routing_scopes(), self.net.delta_scopes(),
                self.net.eva_scopes(), self.net.ssd_scopes(),
                self.net.window_scopes(), self.net.ssd_kernels())
        return self._report

    def resized(self, n_devices: int) -> "ParallelTrainer":
        """A NEW trainer over the first `n_devices` visible devices — the
        elastic resize: same net, solver, τ, mode, and health layout,
        fresh mesh and compiled round. The health psum's
        `[n_data+1]`-vector layout follows the new worker count because
        the round is rebuilt, so attribution indexes always match the
        live membership. The old trainer's executables are dropped with
        the old object. TP pods cannot resize live (the column-shard
        assignment itself would change — relaunch instead)."""
        if self.tp != 1:
            raise NotImplementedError(
                "elastic resize with tensor parallelism: the shard "
                "assignment changes with the mesh — checkpoint and "
                "relaunch at the new size instead")
        return type(self)(
            self.net, self.solver.cfg, make_mesh(n_devices), tau=self.tau,
            mode=self.mode, loss_blob=self.loss_blob, acc_blob=self.acc_blob,
            compute_health=self.compute_health, elastic_tau=self.elastic_tau,
            donate_batches=self.donate_batches,
            interpret=self.interpret,
            fused_boundary=self.fused_boundary,
            **self._ctor_extra())

    def _ctor_extra(self) -> Dict[str, Any]:
        """Subclass-specific constructor kwargs `resized()` must carry to
        the replacement trainer (e.g. ShardedTrainer.state_sharding)."""
        return {}

    def evaluate(self, state: TrainState, batch: Dict[str, np.ndarray]) -> float:
        """Distributed accuracy over one global batch (psum of correct/count —
        reference's eval reduce, `apps/CifarApp.scala:107-124`)."""
        from .. import precision

        sharded = {
            k: put_device_axis(np.asarray(v), self.mesh, P(DATA_AXIS))
            for k, v in precision.cast_host_inputs(batch).items()}
        return float(self._eval(state.params, sharded))

    def place_batches(self, batches, compute_dt=None):
        """Pre-place one round's batches on device — the H2D half of the
        round, runnable OFF the dispatch path (the train loop's prefetch
        thread calls this for round R+1 while round R computes, driving
        train_round's `h2d` phase to ~0).

        THE PLACEMENT CONTRACT (train_round / _shard_batches): a batch
        value that is a `jax.Array` is treated as ALREADY PLACED — cast to
        the compute dtype and sharded P(None, data) exactly as this method
        produces — and passes through untouched; anything else is a host
        array [tau, host_batch, ...] that gets cast + placed at dispatch.
        Mixing is allowed per input. `compute_dt` must be passed when
        calling from a worker thread: the precision policy is thread-local
        (same rule as `precision.cast_host_inputs`).

        With `donate_batches`, the returned arrays are CONSUMED by the
        next train_round — place fresh ones each round (placement always
        allocates new device buffers, so a pre-placed round R+1 can never
        alias the donated round-R buffers the device still owns)."""
        from .. import precision

        dt = (compute_dt if compute_dt is not None
              else precision.compute_dtype())
        out = {}
        for k, v in precision.cast_host_inputs(batches, dt).items():
            if isinstance(v, jax.Array) and not isinstance(v, np.ndarray):
                self._check_batch(k, v, placed=True, dt=dt)
                out[k] = v
            else:
                arr = np.asarray(v)
                self._check_batch(k, arr, placed=False)
                # the batch shards over the DATA axis only (TP replicas
                # share rows)
                out[k] = put_device_axis(arr, self.mesh, P(None, DATA_AXIS))
        return out

    def _check_batch(self, k: str, arr, placed: bool, dt=None) -> None:
        """Batch invariants, hoisted to first sight of each (input, shape,
        dtype, placement[, sharding]) signature — steady-state rounds take
        one set lookup instead of re-asserting shapes and re-deriving the
        local-group split every round."""
        sig = (k, tuple(arr.shape), str(arr.dtype), placed,
               str(dt) if placed else None,
               arr.sharding if placed else None)
        if sig in self._batch_sigs:
            return
        assert arr.shape[0] == self.tau, (
            f"{k}: leading dim {arr.shape[0]} != tau {self.tau}")
        if placed:
            # pre-placed arrays carry the GLOBAL batch; they must split
            # over every data group (their sharding was fixed at placement)
            assert arr.shape[1] % max(1, self.n_data) == 0, (
                f"{k}: global batch {arr.shape[1]} not divisible by "
                f"{self.n_data} data-parallel groups")
            # the dtype half of the placement contract, enforced: a float
            # batch a caller placed WITHOUT the compute-dtype cast
            # (cast_host_inputs skips device arrays) would otherwise
            # silently diverge from the host-array path — a second jit
            # executable and non-pinned numerics (same f32/bf16 rule as
            # precision.cast_in)
            if arr.dtype in (jnp.float32, jnp.bfloat16):
                assert arr.dtype == dt, (
                    f"{k}: pre-placed array has dtype {arr.dtype}, but the "
                    f"compute dtype is {dt} — place via place_batches (it "
                    f"casts), or cast before placing")
            # the sharding half of the contract: a caller-placed array must
            # already be P(None, data) over THIS mesh — a plain device_put'd
            # array would pass the shape/dtype checks and then be silently
            # resharded inside every dispatch, a real per-round copy hidden
            # behind the t_h2d_ms ~ 0 the passthrough reports
            want = NamedSharding(self.mesh, P(None, DATA_AXIS))
            assert arr.sharding.is_equivalent_to(want, arr.ndim), (
                f"{k}: pre-placed array sharding {arr.sharding} is not "
                f"P(None, '{DATA_AXIS}') over the trainer mesh — place via "
                f"place_batches")
        else:
            assert arr.shape[1] % self._local_data_groups == 0, (
                f"{k}: host batch {arr.shape[1]} not divisible by "
                f"{self._local_data_groups} local data-parallel groups")
        self._batch_sigs.add(sig)

    def _shard_batches(self, batches):
        return self.place_batches(batches)


def named(name: str, fn):
    """`fn` under `name`: `jax.jit` names its module after the function it
    is given, and a `shard_map` wrapper has no name of its own."""
    @functools.wraps(fn)
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


def reduce_momentum_rows(rows: np.ndarray, policy: str) -> np.ndarray:
    """Reconstruct ONE momentum from k per-worker velocity rows — the
    elastic-resume reconstruction (see ParallelTrainer.adapt_state for the
    r5 A/B evidence behind the policies). f32 accumulator: a bf16 velocity
    (SolverConfig.velocity_dtype) must not be averaged in bf16."""
    avg = rows.mean(axis=0, dtype=np.float32)
    if policy == "zero":
        return np.zeros_like(avg).astype(rows.dtype)
    if policy == "norm_rescale":
        # averaging k partially-decorrelated velocities shrinks the norm
        # ~1/sqrt(k); rescale the mean back to the average per-worker norm
        # so the first post-resume steps keep their step size
        target = float(np.mean([np.linalg.norm(
            r.astype(np.float32)) for r in rows]))
        cur = float(np.linalg.norm(avg))
        if cur > 0:
            avg = avg * (target / cur)
    return avg.astype(rows.dtype)


def _find_accuracy_blob(net: CompiledNet) -> str:
    for layer in net.spec.layers:
        if layer.type == "Accuracy":
            return layer.tops[0]
    raise ValueError("net has no Accuracy layer; pass acc_blob=")
