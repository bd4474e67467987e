"""NamedSharding-founded trainer: logical state over the named (data, model)
mesh (ROADMAP item 2 / the r7 tentpole).

Where ParallelTrainer's TrainState tiles every leaf with a leading
[n_devices] replica axis (device i holds row i of a stacked array), this
trainer keeps the LOGICAL state and lets `NamedSharding` place it
(SNIPPETS.md [2]: Mesh + NamedSharding + shard_map-under-jit):

  params    full logical shapes, replicated across the data axis BY SPEC;
            tensor-parallel layers hold the full logical weight,
            column-sharded over the model axis by spec (`P(None, "model")`)
            instead of pre-split stacked rows — `averaged_params` becomes
            the identity and a checkpoint always stores full weights, which
            is what lets serve load tp>1 checkpoints without reassembly.
  momentum  `[n_data, ...]` rows sharded over the data axis — each data
            group holds exactly its own worker-local velocity (reference
            semantics preserved; same per-device bytes as the replica
            layout, none of its bookkeeping).
  it        one replicated scalar.

The whole round — τ local SGD steps, the weight-averaging pmean, and the
next round's bookkeeping (iteration counter, momentum/storage re-sharding)
— is ONE jitted executable: the τ boundary never round-trips the host.
The per-worker scan runs inside `shard_map` under that jit, and its math
is shared line for line with `ParallelTrainer._round_math`, which is what
lets tests/test_sharded.py pin the two trainers BITWISE on the f32
TINY_MLP round.

state_sharding — the ZeRO-1-style HBM lever (requires tp == 1):

  "replicated"  exact legacy semantics (worker-local momentum, replicated
                params). Per-device state bytes match the shard_map
                trainer's.
  "momentum"    ONE logical momentum, STORED sharded over the data axis
                (per-device momentum bytes / n_data — the ZeRO-1 split of
                optimizer state across data-parallel workers). Each round
                gathers it at the shard_map boundary, runs the τ
                worker-local steps, then averages the workers' velocities
                back into the shard (a pmean the storage constraint lets
                XLA lower as reduce-scatter). Momentum is therefore
                cross-worker AVERAGED once per round — a semantic opt-in:
                the r5 momentum-policy A/B (ELASTIC_AB_r05.json) measured
                plain averaging within sub-point noise of the best policy
                and far ahead of zeroing, and this mode exists exactly for
                nets whose optimizer state does not fit one chip's HBM
                (PR 5's HBM gauges are the decision input, BENCH_r07 the
                proof).
  "full"        "momentum" plus params stored sharded over the data axis
                at rest (gathered per round the same way): at-rest state
                HBM ~ (params + momentum) / n_data per device.

Multi-host: state placement uses `jax.make_array_from_callback`, so every
process must hold the full logical value when constructing/restoring state
(true for init and checkpoint restore). The τ-boundary round itself is
unchanged multi-host SPMD.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..model.layers import tp_shards_layer
from ..model.net import CompiledNet, PyTree
from ..obs import trace as obs_trace
from ..solver import SolverConfig
from .mesh import DATA_AXIS, MODEL_AXIS, shard_map_unchecked
from .trainer import (ParallelTrainer, TrainState, _find_accuracy_blob,
                      named, reduce_momentum_rows)

STATE_SHARDINGS = ("replicated", "momentum", "full")


def _put(x, sharding: NamedSharding):
    """Place one logical array. Single-process: device_put. Multi-host:
    every process holds the full logical value and contributes its own
    devices' shards via make_array_from_callback."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


class ShardedTrainer(ParallelTrainer):
    """Drop-in ParallelTrainer replacement with NamedSharding-placed
    logical state (module docstring). The public surface — train_round /
    place_batches / evaluate / resized / adapt_state / averaged_params /
    last_health / compiled_variants — is the ParallelTrainer contract;
    RunConfig.trainer_impl="named" selects it in the train loop."""

    state_layout = "logical"

    def __init__(self, net: CompiledNet, solver_cfg: SolverConfig,
                 mesh: Mesh, tau: int = 10, mode: str = "local_sgd",
                 loss_blob: str = "loss", acc_blob: Optional[str] = None,
                 compute_health: bool = True, elastic_tau: bool = False,
                 donate_batches: bool = False,
                 interpret: bool = False,
                 fused_boundary: bool = False,
                 state_sharding: str = "replicated"):
        if state_sharding not in STATE_SHARDINGS:
            raise ValueError(f"unknown state_sharding {state_sharding!r}: "
                             f"expected one of {STATE_SHARDINGS}")
        tp = (int(mesh.shape[MODEL_AXIS])
              if MODEL_AXIS in mesh.axis_names else 1)
        if state_sharding != "replicated" and tp != 1:
            raise NotImplementedError(
                "ZeRO-style state sharding splits over the DATA axis; "
                "combining it with tensor parallelism is future work — "
                "use state_sharding='replicated' with tp > 1")
        self.state_sharding = state_sharding
        super().__init__(net, solver_cfg, mesh, tau=tau, mode=mode,
                         loss_blob=loss_blob, acc_blob=acc_blob,
                         compute_health=compute_health,
                         elastic_tau=elastic_tau,
                         donate_batches=donate_batches,
                         interpret=interpret,
                         fused_boundary=fused_boundary)

    def _ctor_extra(self) -> Dict[str, Any]:
        return {"state_sharding": self.state_sharding}

    # -- sharding specs ------------------------------------------------------

    def _model_dims(self, lname: str, pname: str, ndim: int) -> tuple:
        """Per-dim model-axis placement of one param leaf: TP layers hold
        the full logical weight column-sharded over the model axis (w on
        its output dim, b on dim 0); everything else replicated."""
        if lname in self._tp_layers:
            axis = 1 if pname == "w" else 0
            return tuple(MODEL_AXIS if i == axis else None
                         for i in range(ndim))
        return (None,) * ndim

    def _zero1_dims(self, dims: tuple, shape: tuple) -> tuple:
        """Insert the DATA axis on the first free dim divisible by n_data
        — the at-rest ZeRO split. An indivisible leaf stays whole (logged
        nowhere: tiny biases dominate that set; the BENCH_r07 measurement
        reports the realized per-device bytes, not the ideal)."""
        for i, (d, s) in enumerate(zip(dims, shape)):
            if d is None and s % self.n_data == 0 and s > 0:
                return dims[:i] + (DATA_AXIS,) + dims[i + 1:]
        return dims

    def _build_specs(self) -> None:
        self._tp_layers = self._tp_sharded_layers()
        shapes = jax.eval_shape(self.net.init_params, jax.random.PRNGKey(0))
        compute, p_store, m_store, m_in, m_out = {}, {}, {}, {}, {}
        for lname, lp in shapes.items():
            compute[lname], p_store[lname] = {}, {}
            m_store[lname], m_in[lname], m_out[lname] = {}, {}, {}
            for pname, leaf in lp.items():
                dims = self._model_dims(lname, pname, len(leaf.shape))
                compute[lname][pname] = P(*dims)
                p_store[lname][pname] = P(*(
                    self._zero1_dims(dims, leaf.shape)
                    if self.state_sharding == "full" else dims))
                if self.state_sharding == "replicated":
                    # [n_data, ...] worker rows, one per data group
                    m_store[lname][pname] = P(DATA_AXIS, *dims)
                    m_in[lname][pname] = P(DATA_AXIS, *dims)
                    m_out[lname][pname] = P(DATA_AXIS, *dims)
                else:
                    # ZeRO-1: logical momentum sharded at rest, gathered
                    # to the full value at the shard_map boundary and
                    # pmean'd (replicated) back out — the jit-level
                    # storage constraint re-shards it
                    m_store[lname][pname] = P(
                        *self._zero1_dims(dims, leaf.shape))
                    m_in[lname][pname] = P(*dims)
                    m_out[lname][pname] = P(*dims)
        self._pspec_compute = compute
        self._pspec_store = p_store
        self._mspec_store = m_store
        self._mspec_in = m_in
        self._mspec_out = m_out

    def _store_shardings(self) -> TrainState:
        """Per-leaf storage NamedShardings as a TrainState of trees."""
        sh = lambda spec: NamedSharding(self.mesh, spec)  # noqa: E731
        return TrainState(
            params=jax.tree.map(sh, self._pspec_store,
                                is_leaf=lambda x: isinstance(x, P)),
            momentum=jax.tree.map(sh, self._mspec_store,
                                  is_leaf=lambda x: isinstance(x, P)),
            it=sh(P()))

    # -- compiled round ------------------------------------------------------

    def _compile(self) -> None:
        self._build_specs()
        state_in = TrainState(params=self._pspec_compute,
                              momentum=self._mspec_in, it=P())
        state_out = TrainState(params=self._pspec_compute,
                               momentum=self._mspec_out, it=P())
        extra_specs = (P(),) if self.elastic_tau else ()
        # sync_sgd: every worker applies the same pmean'd gradient to the
        # same params, so the output params ARE replicated — but they mix
        # with the device-varying momentum rows, which shard_map's
        # replication tracker cannot see through. The values are equal by
        # construction (classic synchronous SGD); check off, like the
        # Pallas case.
        smap = (shard_map_unchecked if self.mode == "sync_sgd"
                else self._smap)
        smapped = smap(
            self._round_impl, mesh=self.mesh,
            in_specs=(state_in, P(None, DATA_AXIS), P(DATA_AXIS), P())
            + extra_specs,
            out_specs=(state_out, P(), self._health_specs()))
        if self.state_sharding == "replicated":
            # compute layout == storage layout: no constraint, and the
            # traced program stays the shared round math verbatim (the
            # bitwise-parity pin against ParallelTrainer depends on it)
            round_fn = smapped
        else:
            store = self._store_shardings()

            def round_fn(state, batches, rngs, lr_scale, *extra):
                new_state, loss, health = smapped(state, batches, rngs,
                                                  lr_scale, *extra)
                # re-shard to the at-rest ZeRO layout INSIDE the jit: the
                # boundary pmean + this constraint is the reduce-scatter;
                # state never materializes unsharded between rounds
                new_state = jax.tree.map(
                    lax.with_sharding_constraint, new_state, store)
                return new_state, loss, health

        self._round = jax.jit(
            named("train_round", round_fn),
            donate_argnums=(0, 1) if self.donate_batches else (0,))
        self._eval = jax.jit(named("eval_round", self._smap(
            self._eval_impl, mesh=self.mesh,
            in_specs=(self._pspec_compute, P(DATA_AXIS)), out_specs=P())))

    def _round_impl(self, state: TrainState, batches, rng, lr_scale,
                    tau_vec=None):
        # per-device views: params are the logical value (TP: this rank's
        # column shard) with NO replica axis to squeeze; momentum is this
        # worker's [1, ...] row (replicated mode) or the gathered logical
        # momentum (ZeRO modes)
        # both arrive replicated over the data axis and leave the τ scan
        # worker-local: typed device-varying up front, so the scan carry's
        # type is the same going in and coming out (shard_map vma typing)
        params = lax.pcast(state.params, (DATA_AXIS,), to="varying")
        momentum = (jax.tree.map(lambda x: x[0], state.momentum)
                    if self.state_sharding == "replicated"
                    else lax.pcast(state.momentum, (DATA_AXIS,),
                                   to="varying"))
        it = state.it
        rng = rng[0]
        my_tau = (tau_vec[lax.axis_index(DATA_AXIS)]
                  if tau_vec is not None else None)
        params, sstate, mean_loss, health = self._round_math(
            params, momentum, it, batches, rng, lr_scale, my_tau)
        mom = sstate.momentum
        if self.state_sharding == "replicated":
            mom = jax.tree.map(lambda x: x[None], mom)
        else:
            # ZeRO-1 semantic: the workers' post-round velocities average
            # into the ONE logical momentum (replicated here; the jit-level
            # storage constraint shards it at rest)
            mom = lax.pmean(mom, DATA_AXIS)
        return (TrainState(params=params, momentum=mom, it=sstate.it),
                mean_loss, health)

    def _eval_impl(self, params, batch):
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
            acc = lax.pmean(acc, self._tp_axis)  # replicas agree
        return acc

    # -- state construction --------------------------------------------------

    def _momentum_rows(self, mom: PyTree, params: PyTree,
                       policy: str = "norm_rescale") -> PyTree:
        """Normalize an incoming momentum tree to THIS trainer's layout.
        A leaf with one more dim than its param is a per-worker row stack:
        kept exactly when it matches n_data (replicated mode), else
        policy-reduced (reduce_momentum_rows). A logical leaf broadcasts
        to rows (replicated) or passes through (ZeRO modes)."""

        def adapt(lname, pname, m):
            m = np.asarray(m)
            p_ndim = len(np.shape(params[lname][pname]))
            rows = m if m.ndim == p_ndim + 1 else None
            if self.state_sharding == "replicated":
                if rows is not None and rows.shape[0] == self.n_data:
                    return jnp.asarray(rows)
                if rows is not None:
                    m = reduce_momentum_rows(rows, policy)
                return jnp.broadcast_to(
                    jnp.asarray(m)[None], (self.n_data,) + m.shape)
            if rows is not None:
                m = reduce_momentum_rows(rows, policy)
            return jnp.asarray(m)

        return {l: {p: adapt(l, p, m) for p, m in lp.items()}
                for l, lp in mom.items()}

    @obs_trace.startup_span("state_from_params")
    def state_from_params(self, params: PyTree,
                          momentum: Optional[PyTree] = None,
                          it: int = 0) -> TrainState:
        """Build device state from ONE logical params copy. `momentum`
        may be a logical tree (broadcast per the layout), a [n_data]-row
        stack, or None (zeros)."""
        params = {l: {p: jnp.asarray(x) for p, x in lp.items()}
                  for l, lp in params.items()}
        vdt = jnp.dtype(self.solver.cfg.velocity_dtype)
        if momentum is None:
            zeros = jax.tree.map(lambda w: jnp.zeros(w.shape, vdt), params)
            momentum = (jax.tree.map(
                lambda z: jnp.broadcast_to(z[None],
                                           (self.n_data,) + z.shape), zeros)
                if self.state_sharding == "replicated" else zeros)
        else:
            momentum = self._momentum_rows(momentum, params)
        return self.place(TrainState(
            params=params, momentum=momentum,
            it=jnp.asarray(int(it), jnp.int32)))

    def place(self, state: TrainState) -> TrainState:
        """Place a (possibly host) logical-layout TrainState onto the
        mesh's storage shardings. Casts momentum to the configured
        velocity dtype (same rule as ParallelTrainer.place)."""
        vdt = jnp.dtype(self.solver.cfg.velocity_dtype)
        if any(x.dtype != vdt for x in jax.tree.leaves(state.momentum)):
            state = dataclasses.replace(
                state, momentum=jax.tree.map(
                    lambda x: jnp.asarray(x).astype(vdt)
                    if x.dtype != vdt else x, state.momentum))
        store = self._store_shardings()
        return jax.tree.map(_put, state, store)

    def averaged_params(self, state: TrainState) -> PyTree:
        """The logical params ARE the single synchronized copy — no
        replica row to select, and under TP the NamedSharding-placed
        leaves are logically full already (materializing one gathers its
        column shards)."""
        return state.params

    def adapt_state(self, flat: Dict[str, np.ndarray], old_tp: int = 1,
                    momentum_policy: str = "norm_rescale",
                    old_layout: str = "replica") -> TrainState:
        """Resume from a flat checkpoint taken on ANY topology/layout.

        `old_layout="replica"`: the shard_map trainer's [old_n_devices]
        leading-axis layout — params take data group 0's (reassembled
        across old TP column shards) copy, momentum rows collapse to one
        per old data group. `"logical"`: this trainer's own layout —
        params as stored; momentum rows or logical per the saved
        state_sharding. Either way `_momentum_rows` then maps the rows to
        THIS trainer's layout: exact when the data-group count is
        unchanged (replicated mode), policy-reconstructed otherwise
        (`momentum_policy`, the r5 A/B knob)."""
        old_tp_layers = {l.name for l in self.net.spec.layers
                         if tp_shards_layer(l, old_tp)}
        params: PyTree = {}
        momentum: PyTree = {}
        it = 0
        for key, arr in flat.items():
            parts = key.split("/")
            if parts[0] == "it":
                it = int(np.asarray(arr).reshape(-1)[0])
                continue
            kind, lname, pname = parts
            arr = np.asarray(arr)
            if old_layout == "replica":
                # [old_n_devices, ...] rows, device d = (data d//tp,
                # model d%tp): params take data group 0's copy (post-
                # round replicas are identical), reassembled across the
                # old model ranks' column shards; momentum collapses to
                # one logical row PER old data group
                axis = 1 if pname == "w" else 0
                if kind == "params":
                    if lname in old_tp_layers:
                        arr = np.concatenate(
                            [arr[j] for j in range(old_tp)], axis=axis)
                    else:
                        arr = arr[0]
                elif lname in old_tp_layers:
                    groups = arr.reshape((-1, old_tp) + arr.shape[1:])
                    arr = np.concatenate(
                        [groups[:, j] for j in range(old_tp)],
                        axis=axis + 1)  # +1: leading data-group dim
            (params if kind == "params"
             else momentum).setdefault(lname, {})[pname] = arr
        if not momentum:
            return self.state_from_params(params, it=it)
        return self.place(TrainState(
            params={l: {p: jnp.asarray(x) for p, x in lp.items()}
                    for l, lp in params.items()},
            momentum=self._momentum_rows(momentum, params,
                                         policy=momentum_policy),
            it=jnp.asarray(int(it), jnp.int32)))

    def adapt_live(self, state: TrainState,
                   momentum_policy: str = "norm_rescale") -> TrainState:
        """Elastic resize as RE-PLACEMENT: adopt the PREVIOUS logical-
        layout trainer's live state onto THIS trainer's mesh without the
        checkpoint round-trip the replica layout needs (its stacked rows
        are keyed to the old device count; logical params are topology-
        free). Params move exactly; momentum rows map through
        `_momentum_rows` (exact when the data-group count is unchanged,
        policy-reconstructed otherwise — same rule as adapt_state)."""
        params = jax.tree.map(np.asarray, state.params)
        momentum = jax.tree.map(np.asarray, state.momentum)
        it = int(np.asarray(state.it).reshape(-1)[0])
        return self.place(TrainState(
            params={l: {p: jnp.asarray(x) for p, x in lp.items()}
                    for l, lp in params.items()},
            momentum=self._momentum_rows(momentum, params,
                                         policy=momentum_policy),
            it=jnp.asarray(it, jnp.int32)))
