"""The canonical training driver: the reference's app loop, mesh-native.

Reference shape (`apps/CifarApp.scala:100-149`):
    while true:
      broadcast weights; set on workers        -> (free: device-resident)
      every Nth round: distributed eval        -> trainer.evaluate (psum)
      foreachPartition: τ local solver steps   -> trainer.train_round (scan)
      collect + average weights on driver      -> (inside round: pmean)
      log conv1[0] divergence probe            -> probe_value()

Additions the reference lacked (SURVEY §5.3-5.5): checkpoint/resume of the
full TrainState + round counter — saved through a TWO-STAGE async pipeline
(stage 1 blocks only for the device->host fetch; a background writer
serializes, digests, and persists to a local dir or natively to a
gs://|s3:// bucket, at most one snapshot in flight), metrics JSONL,
per-phase timing, a termination condition (max_rounds instead of
`while(true)`), and the training health supervisor: on-device anomaly
signals classified per flush,
skip-and-continue for isolated loss spikes, rollback to the newest verified
checkpoint (with LR backoff and an advanced data order for the retried
window) for nonfinite rounds or repeated spikes, and a loud hard-fail once
the rollback budget is spent (utils/health.py).
"""
from __future__ import annotations

import json
import math
import time
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..model.net import CompiledNet
from ..model.spec import NetSpec
from ..obs import (MetricsRegistry, StatusServer, register_build_info,
                   trace as obs_trace)
from ..obs import device as obs_device
from ..obs import pod as obs_pod
from ..parallel.elastic import ElasticRelaunch, MembershipController
from ..parallel.mesh import fetch_global, make_mesh
from ..parallel.sharded import ShardedTrainer
from ..parallel.trainer import ParallelTrainer, TrainState
from ..data.dataset import ArrayDataset, RoundSampler
from ..utils import checkpoint as ckpt
from ..utils import profiling
from ..utils.config import RunConfig
from ..utils.health import (HealthConfig, HealthMonitor, TrainingHealthError,
                            poison_batch)
from ..utils.heartbeat import HeartbeatWriter
from ..utils.logger import Logger, default_logger
from ..utils.metrics import PhaseTimers, ThroughputMeter
from .. import precision

def _hb_float(v: float):
    """Heartbeat-safe float: NaN/Inf -> None (RFC 8259, like the JSONL)."""
    return float(v) if math.isfinite(v) else None


#: retried rounds sample a disjoint deterministic data window: round R on
#: rollback generation g draws as logical round R + g * _RETRY_DATA_OFFSET
#: (stateless samplers only — a streaming source simply continues forward,
#: which advances the data order by construction)
_RETRY_DATA_OFFSET = 1 << 20


@obs_trace.startup_span("resolve_spec")
def resolve_spec(cfg: RunConfig, **input_shapes) -> NetSpec:
    """cfg.model -> NetSpec: a zoo builder name, a .prototxt path
    (capability parity: the reference's apps loaded prototxt data files,
    `apps/CifarApp.scala:83-88`), or a .json path: a sequence model's
    published config.json keys as run here plus the `share` block that says
    which part of an expert-parallel deployment this worker holds (the
    file's `model_type` picks the builder: `zoo.SEQUENCE_MODELS`, whose
    decoders' layer kinds follow the file's own keys). Rows a step are
    cfg.local_batch; positions the
    `tokens` shape given here, else the file's `seq_len`."""
    from .. import zoo
    from ..model.prototxt import net_from_prototxt_file
    if cfg.model.endswith(".prototxt"):
        return net_from_prototxt_file(
            cfg.model, input_shapes=input_shapes or None)
    if cfg.model.endswith(".json"):
        with open(cfg.model) as f:
            config = json.load(f)
        kind = config.get("model_type")
        if kind not in zoo.SEQUENCE_MODELS:
            raise ValueError(
                f"{cfg.model}: model_type {kind!r} is not one of "
                f"{sorted(zoo.SEQUENCE_MODELS)}")
        positions = (input_shapes["tokens"][1] if "tokens" in input_shapes
                     else config["seq_len"])
        return zoo.SEQUENCE_MODELS[kind](config, rows=cfg.local_batch,
                                         positions=int(positions))
    builders = {
        "cifar10_quick": lambda: zoo.cifar10_quick(batch=cfg.local_batch),
        "caffenet": lambda: zoo.caffenet(batch=cfg.local_batch,
                                         crop=cfg.crop or 227,
                                         n_classes=cfg.n_classes),
        "lenet": lambda: zoo.lenet(batch=cfg.local_batch),
        "adult_mlp": lambda: zoo.adult_mlp(batch=cfg.local_batch),
    }
    if cfg.model not in builders:
        raise ValueError(f"unknown model {cfg.model!r}: expected a .prototxt "
                         f"path or one of {sorted(builders)}")
    return builders[cfg.model]()


def resolve_trainer_impl(cfg: RunConfig) -> str:
    """cfg.trainer_impl -> the concrete layer-IR trainer implementation.
    "auto" defers to $SPARKNET_TRAINER_IMPL (the CI matrix leg runs the
    whole suite with it set to "named") and falls back to "shard_map",
    today's default. Validated here — trainer BUILD time, the
    ElasticConfig rule — so a typo'd knob cannot silently train on the
    wrong implementation."""
    import os
    impl = cfg.trainer_impl
    if impl == "auto":
        impl = os.environ.get("SPARKNET_TRAINER_IMPL", "shard_map")
    if impl not in ("shard_map", "named"):
        raise ValueError(f"unknown trainer_impl {impl!r}: expected "
                         f"'auto', 'shard_map', or 'named'")
    if impl != "named" and cfg.state_sharding != "replicated":
        raise ValueError(
            f"state_sharding={cfg.state_sharding!r} needs the NamedSharding "
            f"trainer — set trainer_impl='named' (resolved: {impl!r})")
    return impl


def resolve_solver(cfg: RunConfig):
    """Apply cfg.solver_prototxt over cfg.solver if set."""
    if cfg.solver_prototxt:
        from ..model.prototxt import solver_from_prototxt_file
        from ..solver import SolverConfig
        cfg.solver = SolverConfig.from_dict(
            solver_from_prototxt_file(cfg.solver_prototxt))
    return cfg.solver


def probe_value(state: TrainState, net: CompiledNet):
    """First scalar of the first parametric layer's weights — the reference's
    divergence probe (`apps/CifarApp.scala:147` logged conv1 weight [0]).

    Single-process: returns a 0-d DEVICE scalar (an async slice — the loop
    fetches it one round later, so the probe never stalls the pipeline; the
    slice is enqueued before the next round's donation invalidates the
    state buffers). Multi-host: reads a locally-addressable shard to a host
    float (post-round params are replica-identical, any shard's value is
    THE value)."""
    leaf = state.params[net.param_layers()[0]]["w"]
    if hasattr(leaf, "addressable_shards") and not getattr(
            leaf, "is_fully_addressable", True):
        arr = np.asarray(leaf.addressable_shards[0].data)
        return float(arr.reshape(-1)[0])
    if hasattr(leaf, "devices"):
        return leaf[(0,) * leaf.ndim]
    return float(np.asarray(leaf).reshape(-1)[0])


@obs_trace.startup_span("build_trainer")
def build_trainer(cfg: RunConfig, spec: NetSpec, mesh=None):
    """cfg + spec -> the layer-IR trainer `train()` runs: the trainer
    implementation and the round-pipeline levers come from `cfg`, over
    `mesh` (default: the data mesh of cfg.n_devices).
    Sets the precision policy and resolves the solver first — both shape
    the compiled round. Building is cheap: nothing compiles until the
    first round (whose compile is the `train_round` entry of the compile
    log). Separate from `train()` so a caller can look at the
    program the loop will run (`chip_smoke.py` lowers it to check the
    Pallas kernels are in it). A kept start-up span, with `compile_net`
    and `trainer_init` (the mesh and the trainer's construction) inside
    it."""
    precision.set_policy(cfg.precision)
    resolve_solver(cfg)
    compute_health = cfg.health is not None and cfg.health.enabled
    elastic_tau = (cfg.elastic is not None and cfg.elastic.enabled
                   and cfg.elastic.tau_adapt)
    trainer_kw: Dict[str, Any] = {}
    trainer_cls = ParallelTrainer
    if resolve_trainer_impl(cfg) == "named":
        trainer_cls = ShardedTrainer
        trainer_kw["state_sharding"] = cfg.state_sharding
    with obs_trace.startup_span("compile_net"):
        net = CompiledNet.compile(spec)
    with obs_trace.startup_span("trainer_init"):
        return trainer_cls(net, cfg.solver,
                           mesh if mesh is not None
                           else make_mesh(cfg.n_devices), tau=cfg.tau,
                           mode=cfg.mode, compute_health=compute_health,
                           elastic_tau=elastic_tau,
                           donate_batches=cfg.donate_batches,
                           fused_boundary=cfg.fused_boundary,
                           interpret=cfg.ops_interpret,
                           **trainer_kw)


def train(cfg: RunConfig, spec: NetSpec, train_ds: ArrayDataset,
          test_ds: Optional[ArrayDataset] = None,
          logger: Optional[Logger] = None,
          round_hook: Optional[Callable[[int, TrainState], None]] = None,
          batch_transform=None, eval_transform=None) -> TrainState:
    """Run the full distributed training loop per cfg (layer-IR backend).
    Returns final state."""
    log = logger or default_logger(cfg.workdir)
    # persistent compile cache (process-global): the initial round
    # compile AND every elastic trainer_factory rebuild hit it — a
    # relaunched/resized worker with a warm cache skips XLA entirely
    from ..utils.compile_cache import init_compile_cache
    log.log(f"persistent compile cache: "
            f"{init_compile_cache(cfg.compile_cache_dir)}")
    trainer = build_trainer(cfg, spec)
    net = trainer.net
    impl = resolve_trainer_impl(cfg)
    log.log(f"mesh: {trainer.n_devices} devices; tau={cfg.tau} "
            f"mode={cfg.mode} "
            f"local_batch={cfg.local_batch} precision={cfg.precision} "
            f"trainer={impl}"
            + (f" state_sharding={cfg.state_sharding}"
               if impl == "named" else ""))
    if batch_transform is None:
        train_ds = _to_device_layout(train_ds, net)
    if test_ds is not None and eval_transform is None:
        test_ds = _to_device_layout(test_ds, net)
    return run_loop(cfg, trainer, train_ds, test_ds, log,
                    batch_transform=batch_transform,
                    eval_transform=eval_transform,
                    probe=lambda s: probe_value(s, net),
                    round_hook=round_hook,
                    # ParallelTrainer.resized carries the whole trainer
                    # configuration (net/solver/τ/mode/health/elastic_tau)
                    # to the new mesh — the one resize construction path
                    trainer_factory=trainer.resized)


def prepare_round_batches(source, rnd: int, tau: int, seed: int,
                          batch_transform, compute_dt, retry: int = 0,
                          health: Optional[HealthConfig] = None,
                          first_pass: bool = True) -> Dict[str, Any]:
    """One round's host-side work: sample -> per-τ-slice preprocessing
    (e.g. fresh random crops; rng keyed (seed, round, slice) so resume
    reproduces identical crops) -> compute-dtype cast. The cast happens
    here, on the prefetch thread — at dispatch time it would serialize a
    full-batch astype into the pipelined path (`compute_dt` must be
    captured on the MAIN thread; the precision policy is thread-local).
    Module-level so `bench.py --e2e` times exactly this code path.

    `retry` is the health supervisor's rollback generation: a retried
    window must be deterministic-but-DIFFERENT, so stateless samplers
    (RoundSampler) draw from an offset logical round and the per-slice
    transform rng is re-keyed. Stateful streaming sources keep their true
    round index (their cursor bookkeeping is keyed on it) — continuing the
    stream already advances the data order. `health` enables the
    deterministic fault-injection hooks: on the FIRST pass over a
    configured round (`first_pass` — the loop tracks the highest round
    already executed, so a retried window is clean but LATER configured
    rounds still fire after an earlier rollback) the prepared batch is
    poisoned before the precision cast, so chaos tests exercise
    detect -> rollback -> recover without flakiness."""
    stateless = isinstance(source, RoundSampler) or \
        getattr(source, "stateless_rounds", False)
    data_rnd = rnd + retry * _RETRY_DATA_OFFSET if retry and stateless else rnd
    # the four spans below are the host work of one round as the prefetch
    # thread does it, in turn, inside `round_prep`: with `trace_out` they
    # need no device profiler, in a `profile_dir` capture they sit beside
    # the device's ops
    with obs_trace.span("sample", round=rnd):
        batches = source.next_round(round_index=data_rnd)
    if batch_transform is not None:
        with obs_trace.span("preprocess", round=rnd):
            slices = [batch_transform.convert_batch(
                {k: v[t] for k, v in batches.items()}, train=True,
                rng=np.random.default_rng((seed, data_rnd, retry, t)
                                          if retry else (seed, rnd, t)))
                for t in range(tau)]
        with obs_trace.span("stack", round=rnd):
            batches = {k: np.stack([s[k] for s in slices])
                       for k in slices[0]}
    if health is not None and health.enabled and first_pass:
        # injection is inert when the supervisor is off: poisoning a run
        # with nothing watching would recreate exactly the silent-NaN
        # failure mode this subsystem exists to prevent
        if rnd in health.inject_nan_rounds:
            batches = poison_batch(batches, "nan")
        elif rnd in health.inject_spike_rounds:
            batches = poison_batch(batches, "spike",
                                   scale=health.inject_spike_scale)
    with obs_trace.span("cast", round=rnd):
        return precision.cast_host_inputs(batches, compute_dt)


def run_loop(cfg: RunConfig, trainer, train_ds: ArrayDataset,
             test_ds: Optional[ArrayDataset], log: Logger,
             batch_transform=None, eval_transform=None,
             probe: Optional[Callable[[Any], float]] = None,
             round_hook=None, trainer_factory=None):
    """The reference app loop, generic over the trainer backend: any object
    with init_state/place/train_round/evaluate + n_devices (ParallelTrainer
    for the layer IR, GraphTrainer for serialized graphs — the same way
    CaffeSolver and TensorFlowNet sat behind one loop in the reference).

    Multi-host: `train_ds`/`test_ds` are this HOST's shards (apps key them
    on jax.process_index/process_count); the sampler draws windows for the
    locally-addressable devices only, and checkpointing allgathers the
    worker-local state so process 0 writes the global checkpoint (resume
    expects checkpoint_dir on a filesystem all hosts can read). Eval is a
    collective: all hosts must agree on test_ds presence and SIZE
    (ArrayDataset.host_shard splits are exactly equal; uneven sources must
    reconcile first — see imagenet_app._agree_eval_dataset).

    `train_ds` may instead be any round SOURCE — an object with
    `next_round(round_index=...)` (e.g. `data.streaming.StreamingRoundSource`
    for corpora larger than host RAM); sampling/decoding then happens in the
    source's own pipeline. Either way, host-side round preparation (sampling
    + `batch_transform` preprocessing) for round R+1 is overlapped with
    round R's device compute via a one-deep prefetch thread — the reference
    prepared batches inline on each executor and stalled the GPU every
    round.

    `trainer_factory(n_devices)` builds a replacement trainer over a
    resized mesh — the elastic-membership path (cfg.elastic +
    cfg.pod_dir): when the MembershipController declares a worker dead or
    adopts a joiner, the loop checkpoints at the τ boundary, rebuilds the
    compiled round via the factory, restores through the newest verified
    snapshot, and reshards the data. Without a factory (GraphTrainer
    callers) a single-host membership change checkpoints then raises
    ElasticRelaunch (exit 75) so the launcher relaunches at the new size;
    multi-host loops raise without the boundary save (see
    ElasticRelaunch) and resume from the newest periodic checkpoint."""
    n_dev = trainer.n_devices
    n_local = getattr(trainer, "n_local_devices", n_dev)
    # validated at LOOP ENTRY, not at the first save 25 rounds in — the
    # ElasticConfig fail-at-build rule: a typo'd knob must not
    # cost a run its work (or, with checkpointing off, go unreported)
    if str(getattr(cfg, "checkpoint_sharded", "auto")) not in (
            "auto", "on", "off"):
        raise ValueError(
            f"checkpoint_sharded={cfg.checkpoint_sharded!r}: expected "
            f"'auto', 'on', or 'off'")
    if getattr(log, "worker", None) is None and jax.process_count() > 1:
        # stamp this process's JSONL records with its worker id so the
        # pod summary view can merge the N per-host files
        log.worker = jax.process_index()
    if hasattr(train_ds, "next_round"):
        source = train_ds
        log.log(f"train source: streaming ({n_dev} devices / {n_local} "
                f"local)" + (f"; test examples: {len(test_ds)}"
                             if test_ds else ""))
    else:
        source = RoundSampler(train_ds, n_local, cfg.local_batch, cfg.tau,
                              seed=cfg.seed)
        log.log(f"train examples: {len(train_ds)} on this host "
                f"({len(train_ds) // n_local} per worker; "
                f"{n_dev} devices / {n_local} local)"
                + (f"; test examples: {len(test_ds)}" if test_ds else ""))

    state = trainer.init_state(jax.random.PRNGKey(cfg.seed))
    start_round = 0
    resumed_extra: Dict[str, Any] = {}
    if cfg.checkpoint_dir and cfg.resume:
        last = ckpt.latest_step(cfg.checkpoint_dir)
        if last is not None:
            with obs_trace.startup_span("restore"):
                flat, start_round, extra = ckpt.restore_flat(
                    cfg.checkpoint_dir)
                state, same_topo = _restore_state(trainer, state, flat,
                                                  extra)
            if same_topo:
                log.log(f"resumed from checkpoint round {start_round}")
            else:
                log.log(f"ELASTIC resume from round {start_round}: "
                        f"{extra.get('n_devices', '?')} devices (tp="
                        f"{extra.get('tp', 1)}) -> {trainer.n_devices} "
                        f"(tp={getattr(trainer, 'tp', 1)})")
            _seek_stream(source, extra, log)
            resumed_extra = extra

    # unified telemetry: one per-run registry every meter/supervisor/
    # writer below registers into; the training process's own /metrics
    # (status server) and the per-round step-time breakdown render from
    # it. cfg.telemetry=False restores the pre-obs loop (the bench.py
    # --obs "disabled" arm measures exactly this switch) — unless a
    # status_port is also set, which is an explicit ask for the scrape
    # surface and therefore forces the registry (an empty /metrics would
    # silently betray the documented contract).
    registry = (MetricsRegistry()
                if cfg.telemetry or cfg.status_port is not None else None)
    g_round = g_loss = c_rounds = None
    g_round_s = g_wait_s = dev_tel = g_variants = None
    if registry is not None:
        register_build_info(registry)
        g_round = registry.gauge("sparknet_train_round",
                                 "last flushed round index")
        g_loss = registry.gauge("sparknet_train_loss",
                                "last flushed round loss")
        c_rounds = registry.counter("sparknet_train_rounds_total",
                                    "rounds dispatched")
        # per-worker straggler-attribution inputs: THIS worker's last
        # round wall time and residual data wait — the pod aggregator
        # compares them across workers (median+MAD) to name the slow host
        g_round_s = registry.gauge(
            "sparknet_train_round_seconds",
            "last round wall time on this worker")
        g_wait_s = registry.gauge(
            "sparknet_train_data_wait_seconds",
            "last round's residual data wait on this worker")
        # device telemetry (obs/device.py): HBM + live arrays sampled at
        # the flush cadence, compile events replayed + followed, and the
        # jitted round's cache size (churn = recompiles) live-read
        dev_tel = obs_device.DeviceTelemetry(registry)
        obs_device.attach_compile_metrics(registry)
        # sparknet_train_round_{temp,argument,output}_bytes,
        # ..._recompute_core_forward_in_backward and
        # ..._attention_moves_*, once a profile_dir run
        # has asked the round program for its report
        obs_device.attach_program_gauges(registry)
        # sparknet_moe_*{layer}: the expert layers' counters of the last
        # finished round (a net without such layers adds none)
        obs_device.attach_round_counter_gauges(registry, trainer)
        if hasattr(trainer, "compiled_variants"):
            g_variants = registry.gauge(
                "sparknet_train_round_compiled_variants",
                "jit-cache entries for the compiled round (1 = steady "
                "state; growth = recompiles)")
            g_variants.set_fn(trainer.compiled_variants)
    timers = PhaseTimers(registry=registry)
    if cfg.telemetry and hasattr(trainer, "phase_timers"):
        # h2d / dispatch split from inside train_round (ParallelTrainer).
        # Gated on telemetry so the disabled arm really is the pre-obs
        # round path (bench.py --obs compares against it).
        trainer.phase_timers = timers
    meter = ThroughputMeter(n_chips=n_dev, registry=registry)
    # round-keyed rngs: resume at round R reproduces the uninterrupted
    # schedule exactly (reference had no resume at all, SURVEY §5.3)
    base_rng = jax.random.PRNGKey(cfg.seed ^ 0xABCD)

    # capture on the MAIN thread: the precision policy is thread-local and
    # the prefetch thread would otherwise see the default
    compute_dt = precision.compute_dtype()

    # cfg.health=None means NO supervisor — same reading the trainer
    # construction sites use (compute_health=False), so the monitor and
    # the compiled round can't disagree about whether health is on
    health_cfg = (cfg.health if cfg.health is not None
                  else HealthConfig(enabled=False))
    monitor = (HealthMonitor(health_cfg, registry=registry)
               if health_cfg.enabled else None)
    # stage-2 background checkpoint writer (serialize+digest+persist off
    # the round loop's critical path; at most one snapshot in flight).
    # None = fully synchronous saves (cfg.checkpoint_async=False).
    ck_writer = (ckpt.AsyncCheckpointWriter(registry=registry)
                 if cfg.checkpoint_dir and cfg.checkpoint_async else None)
    # liveness heartbeat (process 0 writes; the launcher's watch probes
    # worker 0): one atomic JSON at the flush cadence — "slow vs sick"
    # without log parsing. Every beat is best-effort: a full disk must
    # degrade observability, not kill the run.
    heartbeat = (HeartbeatWriter(cfg.heartbeat_path, role="train",
                                 interval_s=cfg.heartbeat_every_s,
                                 registry=registry)
                 if cfg.heartbeat_path and jax.process_index() == 0
                 else None)
    # pod-scope telemetry (obs/pod.py): EVERY worker rewrites its own
    # heartbeat under the shared pod_dir prefix (local/NFS or gs://|s3://
    # — single small atomic object PUTs), carrying the per-worker round
    # wall time + data wait the aggregator's straggler attribution needs.
    # registry=None: the primary heartbeat above already owns the
    # sparknet_heartbeat_* counters; double-registering would double-count.
    pod_hb = (HeartbeatWriter(
        obs_pod.worker_heartbeat_path(cfg.pod_dir, jax.process_index()),
        role="train", interval_s=cfg.heartbeat_every_s)
        if cfg.pod_dir else None)
    # elastic membership (parallel/elastic.py): watch the pod heartbeats,
    # declare workers dead (stale + full-jitter re-probes, never one
    # missed beat) or joined, and drive a resize at the τ boundary. The
    # heartbeat prefix IS the liveness channel and the verified
    # checkpoint store IS the recovery channel, so both are required.
    elastic_cfg = (cfg.elastic
                   if cfg.elastic is not None and cfg.elastic.enabled
                   else None)
    membership = None
    if elastic_cfg is not None:
        if not cfg.pod_dir:
            raise ValueError(
                "cfg.elastic.enabled requires cfg.pod_dir: the per-worker "
                "heartbeats under it are how membership is observed")
        if not cfg.checkpoint_dir:
            raise ValueError(
                "cfg.elastic.enabled requires cfg.checkpoint_dir: a "
                "resize restores workers from the newest verified "
                "checkpoint")
        membership = MembershipController(
            elastic_cfg, cfg.pod_dir, self_worker=jax.process_index(),
            expected_workers=jax.process_count(), registry=registry)
    # host-side span capture (--trace-out): spans from the round loop,
    # the round-prep prefetch thread and the ckpt-write thread land on
    # per-thread lanes of ONE Chrome-trace timeline (obs/trace.py) —
    # written at loop exit, loadable in Perfetto next to the
    # cfg.profile_dir device trace
    tracer = (obs_trace.start_tracing()
              if cfg.trace_out and jax.process_index() == 0 else None)
    # live vitals for /healthz + /status on the training status server.
    # round_s / data_wait_s are the per-worker straggler inputs — the pod
    # aggregator reads them straight off /status without parsing metrics.
    # beat_ts is the LOOP's own freshness stamp (updated at each flush):
    # a hung round loop whose HTTP daemon thread still answers must read
    # as stale to the pod aggregator, not as alive-and-fresh
    # `time.perf_counter()` when this loop's first round completed (its
    # loss was fetched): what came before is start-up (`/status` `startup`,
    # the `start-up:` log line), what compiles after is a recompile
    first_round_t: List[Optional[float]] = [None]
    vitals: Dict[str, Any] = {"role": "train", "round": start_round,
                              "status": "ok", "loss": None,
                              "worker": jax.process_index(),
                              "round_s": None, "data_wait_s": None,
                              "beat_ts": round(time.time(), 3)}
    # every process serves its own /metrics since the pod PR: each worker
    # is a scrape surface (the raw feed pod aggregation merges); on a
    # shared host use port 0 — each process binds its own ephemeral port
    status_srv = None
    if cfg.status_port is not None:
        try:
            status_srv = StatusServer(
                cfg.status_port, registry, host=cfg.status_host,
                healthz=lambda: (vitals["status"] not in ("nonfinite",),
                                 {k: v for k, v in vitals.items()}),
                status=lambda: {**vitals,
                                "rollbacks": (monitor.rollbacks
                                              if monitor else 0),
                                "phase_means": timers.summary(),
                                # the kept start-up spans and the compile
                                # log up to the first completed round, the
                                # count and the newest of later compiles
                                "startup": obs_device.startup_report(
                                    first_round_t[0]),
                                # {} until a profile_dir run has asked
                                **{f"program_{part}":
                                   obs_device.program_part(part)
                                   for part in obs_device.REPORT_PARTS},
                                # {} for a net whose layers count nothing
                                "round_counters": (
                                    trainer.counter_values()
                                    if hasattr(trainer, "counter_values")
                                    else {})})
        except OSError as e:
            # a taken port (co-located processes sharing a fixed
            # status_port) degrades observability, never training —
            # use port 0 for one-ephemeral-port-per-process instead
            warnings.warn(f"status server failed to bind port "
                          f"{cfg.status_port}: {e}; continuing without",
                          RuntimeWarning)
        if status_srv is not None:
            cfg.status_address = status_srv.address
            if jax.process_index() == 0:
                log.log(f"train status server at "
                        f"http://{status_srv.address[0]}:"
                        f"{status_srv.address[1]}/metrics")
    # the SLO ledger's history sampler: the training process gets the
    # same /timeseries surface serve and router processes get, plus
    # JSONL shards for `sparknet-slo` retrospective reports
    history = None
    if cfg.history and registry is not None:
        from ..obs.history import HistoryConfig, MetricsHistory
        history = MetricsHistory(
            registry,
            HistoryConfig(sample_interval_s=cfg.history_interval_s,
                          persist_dir=cfg.history_dir),
            logger=log).start()
        if status_srv is not None:
            history.attach_http(status_srv)
    # worker 0 additionally serves the POD view over the shared heartbeat
    # prefix: merged /metrics + /pod/status with straggler attribution
    pod_srv = None
    if cfg.pod_port is not None and cfg.pod_dir and \
            jax.process_index() == 0:
        try:
            # one staleness rule: the aggregator's down/stale verdicts use
            # the SAME threshold the elastic controller evicts on
            pod_srv = obs_pod.PodAggregator(
                pod_dir=cfg.pod_dir,
                stale_after_s=(elastic_cfg.stale_after_s
                               if elastic_cfg is not None else 120.0)).serve(
                cfg.pod_port, host=cfg.status_host)
        except OSError as e:
            warnings.warn(f"pod status server failed to bind port "
                          f"{cfg.pod_port}: {e}; continuing without",
                          RuntimeWarning)
        else:
            cfg.pod_address = pod_srv.address
            log.log(f"pod status server at http://{pod_srv.address[0]}:"
                    f"{pod_srv.address[1]}/pod/status")

    def beat(step: int, status: str, force: bool = False, **kv) -> None:
        rollbacks = monitor.rollbacks if monitor is not None else 0
        if membership is not None:
            # membership epoch rides every beat so the pod view (and a
            # joiner reading the prefix) sees resizes without scraping
            kv.setdefault("membership_epoch", membership.epoch)
            kv.setdefault("n_members", len(membership.members))
        for hb, extra in ((heartbeat, kv),
                          (pod_hb, {**kv,
                                    "worker": jax.process_index(),
                                    "n_workers": jax.process_count(),
                                    "round_s": vitals.get("round_s"),
                                    "data_wait_s": vitals.get(
                                        "data_wait_s")})):
            if hb is None:
                continue
            try:
                hb.beat(step, status=status, force=force,
                        rollbacks=rollbacks, **extra)
            except OSError as e:
                warnings.warn(f"heartbeat write failed: {e}",
                              RuntimeWarning)

    def ckpt_barrier() -> None:
        """Settle the store before READING it: drain the in-flight write
        (re-raising its failure), and on a pod make every process wait for
        process 0's writer — a rollback target chosen while the newest
        snapshot is still uploading would diverge across hosts."""
        if ck_writer is not None:
            ck_writer.wait()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ckpt_write_barrier")
    # rollback generation: bumped per recovery; folds into the round rng
    # and the sampler's logical round so the retried window is
    # deterministic-but-different. retry == 0 reproduces the legacy
    # schedule bit-exactly (resume/replay invariants depend on that).
    # Recovery state RESUMES from the checkpoint: a preemption after a
    # rollback must not silently revert the LR backoff / retried data
    # order / rollback budget the supervisor configured.
    saved_health = resumed_extra.get("health", {})
    retry = int(saved_health.get("retry", 0))
    lr_scale = float(saved_health.get("lr_scale", 1.0))
    if monitor is not None:
        monitor.rollbacks = int(saved_health.get("rollbacks", 0))
    if retry or lr_scale != 1.0:
        log.log(f"health state resumed: retry={retry} "
                f"lr_scale={lr_scale} rollbacks="
                f"{saved_health.get('rollbacks', 0)}")
    supports_lr = bool(getattr(trainer, "supports_lr_scale", False))
    # highest round already dispatched THIS process: rounds at or below it
    # are retries/replays (fault injection only fires above it, so a
    # retried window is clean but later configured rounds still fire)
    high_water = start_round - 1
    # elastic bootstrap: seed membership from the heartbeats already on
    # the prefix (fresh ones only — leftovers of a previous incarnation
    # never count) and pin the devices-per-worker ratio every resize
    # preserves. An indivisible mesh disables LIVE resizing (membership
    # changes then checkpoint-and-relaunch), it never disables watching.
    devices_per_worker = None
    if membership is not None:
        membership.poll(start_round, force=True)
        # the SEEDED membership, not expected_workers: an extra worker
        # with a fresh beat at the first poll is a member from round 0,
        # and the devices-per-worker ratio pinned here must match the
        # membership the later resize events count against
        n_members = max(1, len(membership.members))
        if n_members < max(1, elastic_cfg.min_workers):
            # guard the relaunch loop: a pod relaunched (exit 75) at a
            # size already below min_workers must halt loudly HERE, not
            # bounce between relaunches forever
            raise TrainingHealthError(
                f"elastic: launched with {n_members} worker(s), below "
                f"min_workers={elastic_cfg.min_workers} — refusing to "
                f"start; the newest verified checkpoint resumes once "
                f"capacity returns.")
        if n_dev % n_members == 0:
            devices_per_worker = n_dev // n_members
        else:
            warnings.warn(
                f"elastic: {n_dev} devices over {n_members} workers is "
                f"not an integer devices-per-worker split — membership "
                f"changes will relaunch instead of resizing live",
                RuntimeWarning)
        vitals["membership_epoch"] = membership.epoch
        log.log(f"elastic membership: {sorted(membership.members)} "
                f"({n_members} worker(s), "
                f"{devices_per_worker or '?'} device(s)/worker; "
                f"stale_after={elastic_cfg.stale_after_s}s "
                f"min_workers={elastic_cfg.min_workers})")

    # double-buffered H2D: the prefetch stage not only samples/preprocesses
    # round R+1 but also PLACES it on device (same cast + sharding the
    # dispatch-time path applies — trainer.place_batches' documented
    # contract) while round R's XLA program runs, so train_round's `h2d`
    # phase measures ~0 in steady state. Gated on the knob AND trainer
    # capability (GraphTrainer places at dispatch, as before).
    h2d_prefetch = bool(getattr(cfg, "h2d_prefetch", False)
                        and hasattr(trainer, "place_batches"))

    def prepare_round(rnd: int, retry_: int,
                      first_pass: bool) -> Dict[str, Any]:
        # span: host-side round prep runs on the `round-prep_0` prefetch
        # thread — its own lane in the trace timeline, visualizing the
        # overlap with the device round
        with obs_trace.span("round_prep", round=rnd):
            batches = prepare_round_batches(source, rnd, cfg.tau, cfg.seed,
                                            batch_transform, compute_dt,
                                            retry=retry_, health=health_cfg,
                                            first_pass=first_pass)
            if h2d_prefetch:
                # compute_dt rides along: the precision policy is
                # thread-local and this runs on the round-prep thread
                with obs_trace.span("h2d_prefetch", round=rnd):
                    batches = trainer.place_batches(batches, compute_dt)
            return batches

    # step-time breakdown bookkeeping: per-round deltas of the phase
    # timers (data wait / H2D / compiled-round dispatch / checkpoint
    # stage-1 fetch), plus the collect (deferred loss fetch) and log
    # durations measured at flush. `_last_flush_ms[0]` carries the
    # previous flush's own cost into the next record — a flush cannot
    # time itself into the row it is writing.
    _last_flush_ms = [0.0]

    # async collect (r8): with cfg.collect_async the deferred fetch below
    # runs on a dedicated single-thread collector, so the round loop
    # NEVER blocks on boundary results — t_collect_ms in the breakdown
    # reads ~0 (the loop only enqueues a record) and the real off-thread
    # wait lands as t_collect_bg_ms. FIFO order preserves the JSONL/log
    # row ordering; every boundary (eval, checkpoint, recovery, resize,
    # loop exit) drains the queue first, so supervisor decisions and
    # row ordering are exactly the synchronous loop's, one cadence late
    # at worst — which the deferred fetch already was.
    collect_async = bool(getattr(cfg, "collect_async", False))

    def flush_round_log(rec) -> None:
        """Emit round R's metrics. `float(loss)` here is the pipeline's
        REAL synchronization — deferred one round so round R+1's dispatch
        overlaps round R's device execution (the reference fetched loss
        synchronously every round and stalled the accelerator; on a TPU the
        dispatch+fetch round trip is a large fraction of a round), and
        since r8 dispatched onto the collector thread (collect_async) so
        the loop never blocks on it at all. The health scalars ride the
        same deferred fetch: classification happens here, so anomaly
        detection costs no extra per-round sync and latches a recovery
        decision at the same log_every cadence."""
        t_flush0 = time.perf_counter()
        rnd_, loss_, probe_, health_, breakdown_ = rec
        t_c0 = time.perf_counter()
        loss_ = float(loss_)
        t_collect = time.perf_counter() - t_c0
        if first_round_t[0] is None:
            first_round_t[0] = time.perf_counter()
            log.log(obs_device.startup_line(
                obs_device.startup_report(first_round_t[0])), rnd_)
        kv: Dict[str, Any] = {}
        if breakdown_ is not None:
            if collect_async:
                # the round loop's blocking share is the enqueue: ~0.
                # The fetch above still happened — on THIS collector
                # thread, overlapped with the device round — and is
                # attributed separately so a slow store of health
                # scalars stays visible.
                breakdown_["collect"] = 0.0
                breakdown_["collect_bg"] = t_collect
            else:
                breakdown_["collect"] = t_collect
            breakdown_["log"] = _last_flush_ms[0] / 1e3
            kv.update({f"t_{k}_ms": round(v * 1e3, 3)
                       for k, v in breakdown_.items()})
            # per-worker straggler-attribution feed: /status vitals, the
            # worker's own gauges, and (via beat below) the pod heartbeat
            vitals["round_s"] = round(breakdown_["round"], 6)
            vitals["data_wait_s"] = round(breakdown_["data"], 6)
            if g_round_s is not None:
                g_round_s.set(breakdown_["round"])
                g_wait_s.set(breakdown_["data"])
        if dev_tel is not None:
            dev_tel.sample()  # HBM + live arrays at the log_every cadence
        gnorm = nonf = None
        worker_txt = ""
        if health_ is not None:
            gnorm = float(health_["grad_norm"])
            nonf = float(health_["nonfinite"])
            kv["grad_norm"] = gnorm
            by_worker = health_.get("nonfinite_by_worker")
            if nonf and by_worker is not None:
                # attribution: which data-parallel worker's shard tripped
                # the flag — a consistently bad host/feed shows up as the
                # same index round after round (the [n_data] vector rides
                # the existing psum; see ParallelTrainer.last_health).
                # An all-zero vector means the anomaly has no owner (only
                # the post-average state is poisoned): flag, don't blame.
                vec = np.asarray(by_worker)
                if vec.max() > 0:
                    worst = int(np.argmax(vec))
                    kv["worst_worker"] = worst
                    kv["nonfinite_workers"] = int((vec > 0).sum())
                    worker_txt = (f"  worst worker: {worst} "
                                  f"({int(vec[worst])} flag(s), "
                                  f"{int((vec > 0).sum())}/{vec.size} "
                                  f"workers)")
        cls = None
        if monitor is not None:
            cls = monitor.observe(rnd_, loss_, grad_norm=gnorm,
                                  nonfinite_count=nonf or 0.0)
            if cls != "ok":
                kv["health"] = cls
        probe_txt = (f"  probe: {float(probe_):.6f}"
                     if probe_ is not None else "")
        health_txt = f"  HEALTH: {cls}" if cls not in (None, "ok") else ""
        log.log(f"round loss: {loss_:.4f}{probe_txt}{health_txt}"
                f"{worker_txt}", rnd_)
        log.metrics(rnd_, loss=loss_, images_per_sec_per_chip=round(
            meter.images_per_sec_per_chip(), 2), **kv)
        vitals["round"] = rnd_
        vitals["loss"] = _hb_float(loss_)
        vitals["status"] = cls or "ok"
        vitals["beat_ts"] = round(time.time(), 3)
        if g_round is not None:
            g_round.set(rnd_)
            if math.isfinite(loss_):
                g_loss.set(loss_)
        if tracer is not None:
            tracer.instant("flush", round=rnd_, loss=_hb_float(loss_))
        beat(rnd_, status=cls or "ok", force=(cls not in (None, "ok")),
             last_loss=_hb_float(loss_))
        if cls == "spike" and not monitor.rollback_needed:
            # every supervisor DECISION is an event record: this spike was
            # skipped (excluded from the stats window, training continues)
            log.event(rnd_, "spike_skip", loss=loss_)
        _last_flush_ms[0] = (time.perf_counter() - t_flush0) * 1e3

    # one-deep host prefetch: round R+1 is sampled/decoded/preprocessed on
    # this thread pool while round R's XLA program runs. The "sample" phase
    # then measures only the residual WAIT — ~0 when prep fully overlaps.
    prefetch = ThreadPoolExecutor(1, thread_name_prefix="round-prep")
    pending: Optional[Any] = None
    # pending (rnd, device_loss, device_probe, device_health) records,
    # flushed (= the loop's host sync) every cfg.log_every rounds —
    # holding device scalars is free; fetching one costs a full round trip.
    # A deque: list.pop(0) is O(n) per drain step, O(n^2) per flush — at
    # log_every=1 it is noise, but a high-K flush (or the abort-path drain
    # of a long deferred backlog) must not pay quadratic host time.
    deferred: deque = deque()
    collector = (ThreadPoolExecutor(1, thread_name_prefix="collect")
                 if collect_async else None)
    collect_pending: deque = deque()  # in-flight collector futures (FIFO)

    def flush_deferred(wait: bool = True) -> None:
        """Flush every deferred record: inline (synchronous collect), or
        by handing them to the collector thread. `wait=False` — the
        in-round path only — returns without joining, so the loop never
        blocks on a boundary result; every other call site drains (the
        deferred fetch's ordering/decision points), re-raising a
        collector failure loudly. A bounded in-flight window keeps a
        slow store from piling up device-scalar records."""
        if collector is None:
            while deferred:
                flush_round_log(deferred.popleft())
            return
        while deferred:
            collect_pending.append(
                collector.submit(flush_round_log, deferred.popleft()))
            while len(collect_pending) > max(4, 2 * log_every):
                collect_pending.popleft().result()
        if wait:
            while collect_pending:
                collect_pending.popleft().result()

    def recover(state):
        """Roll back to the newest VERIFIED non-anomalous checkpoint.
        Returns (restored_state, restored_round). Deterministic across
        hosts: the trigger scalars are mesh-reduced (identical on every
        process) and the checkpoint dir is shared, so every process picks
        the same target with no extra communication. Raises
        TrainingHealthError when the rollback budget is exhausted or no
        verified checkpoint exists to roll back to."""
        nonlocal retry, lr_scale, pending
        flush_deferred()  # drain in-flight records of the same incident
        reason = monitor.consume_rollback()  # raises once budget is spent
        if not cfg.checkpoint_dir:
            raise TrainingHealthError(
                f"training health: {reason} detected but no checkpoint_dir "
                f"is configured — nothing to roll back to. Enable "
                f"checkpointing or disable cfg.health.")
        ckpt_barrier()  # the in-flight write may BE the rollback target
        found = ckpt.restore_newest_verified(cfg.checkpoint_dir,
                                             skip_anomalous=True)
        if found is None:
            raise TrainingHealthError(
                f"training health: {reason} detected and no verified "
                f"non-anomalous checkpoint exists under "
                f"{cfg.checkpoint_dir!r} — cannot recover.")
        flat, ck_round, extra = found
        target = ck_round
        try:
            # the verified target may predate an elastic relaunch (old
            # topology): the shared dispatch re-tiles it like resume would
            state, _ = _restore_state(trainer, state, flat, extra)
        except ValueError as e:
            raise TrainingHealthError(
                f"training health: rollback target step {target} cannot "
                f"be loaded — {e}") from e
        retry += 1
        if supports_lr and health_cfg.lr_backoff != 1.0:
            lr_scale *= health_cfg.lr_backoff
        if pending is not None:
            if not pending.cancel():
                try:  # already running: WAIT — the prep thread must not
                    pending.result()  # race the retried round's inline
                except Exception:  # prep on the shared (streaming) source
                    pass
            pending = None
        log.event(ck_round, "rollback", reason=reason, target_step=target,
                  rollbacks=monitor.rollbacks, retry=retry,
                  lr_scale=round(lr_scale, 6))
        beat(ck_round, status="rollback", force=True, reason=reason)
        return state, ck_round

    def apply_resize(state, ev, rnd):
        """Membership changed: drive the safe resize at this τ boundary.

        Order matters: (1) drain the pipeline (deferred fetches, the
        prefetched next round, the in-flight checkpoint write), (2) write
        the boundary snapshot — BOTH the resize restore and the
        min_workers halt must leave a verified checkpoint behind, (3)
        halt loudly if the pod is too small, (4) rebuild the compiled
        round over the new worker set and restore every worker — survivor
        or joiner alike — from the newest verified checkpoint (params
        exact, momentum per the A/B-validated policy), (5) reshard the
        data partitions. Single-host loops that cannot resize (no
        factory / non-reshardable source) do (1)-(3) then raise
        ElasticRelaunch (exit 75) for the launcher. MULTI-HOST loops
        raise ElasticRelaunch before ANY of it: membership is observed
        per process, so the boundary save's collective could hang on a
        split membership view — the relaunch resumes from the newest
        periodic checkpoint instead. Degrade loudly, never hang on a
        collective a dead worker will not join. Returns (state, round)
        like recover()."""
        nonlocal trainer, trainer_factory, source, n_dev, n_local, pending
        flush_deferred()
        if pending is not None:
            if not pending.cancel():
                try:  # already running: wait it out (same rule recover
                    pending.result()  # applies — never race the source)
                except Exception:
                    pass
            pending = None
        if jax.process_count() > 1:
            # membership is observed PER PROCESS (jittered re-probes):
            # processes reach this decision at different rounds, so
            # entering a collective (the boundary checkpoint's
            # allgather) here could hang — the exact failure mode this
            # layer exists to prevent. Exit 75 instead; the launcher
            # relaunches the whole pod at the new size and resume picks
            # up the last periodic checkpoint.
            log.event(rnd, "resize", epoch=ev.epoch, dead=list(ev.dead),
                      joined=list(ev.joined), reasons=ev.reasons,
                      n_workers=ev.n_workers, relaunch=True)
            beat(rnd, status="resize", force=True,
                 dead=list(ev.dead), joined=list(ev.joined))
            if ev.n_workers < max(1, elastic_cfg.min_workers):
                # below min_workers, exit 75 would BOUNCE: the launcher
                # relaunches without a strike, the dead worker is still
                # dead, and the relaunched pod re-evicts its way back
                # here forever. Halt loudly instead — still no boundary
                # save (its collective could hang on a split membership
                # view); the newest periodic checkpoint is the resume
                # point.
                raise TrainingHealthError(
                    f"elastic: pod fell to {ev.n_workers} worker(s) "
                    f"(dead: {list(ev.dead)}), below min_workers="
                    f"{elastic_cfg.min_workers}. Resume from the newest "
                    f"periodic checkpoint under {cfg.checkpoint_dir!r} "
                    f"once capacity returns.")
            raise ElasticRelaunch(
                f"membership epoch {ev.epoch}: {ev.n_workers} worker(s) "
                f"(dead {list(ev.dead)}, joined {list(ev.joined)}); "
                f"multi-host pod relaunches at the new size")
        ckpt_barrier()
        with timers.phase("checkpoint"):
            _save_checkpoint(cfg, trainer, state, rnd, source=source,
                             last_round=rnd - 1,
                             anomalous=(monitor is not None and
                                        monitor.recently_anomalous(rnd)),
                             health_state=_health_state(retry, lr_scale,
                                                        monitor))
        log.event(rnd, "resize", epoch=ev.epoch, dead=list(ev.dead),
                  joined=list(ev.joined), reasons=ev.reasons,
                  n_workers=ev.n_workers)
        vitals["membership_epoch"] = ev.epoch
        beat(rnd, status="resize", force=True,
             dead=list(ev.dead), joined=list(ev.joined))
        if ev.n_workers < max(1, elastic_cfg.min_workers):
            raise TrainingHealthError(
                f"elastic: pod fell to {ev.n_workers} worker(s) "
                f"(dead: {list(ev.dead)}), below min_workers="
                f"{elastic_cfg.min_workers}. A verified checkpoint at "
                f"round {rnd} is saved under {cfg.checkpoint_dir!r} — "
                f"relaunch with capacity to continue.")
        new_n_dev = (devices_per_worker or 0) * ev.n_workers
        can_resize_live = (
            jax.process_count() == 1 and trainer_factory is not None
            and devices_per_worker is not None
            # TP shard assignment changes with the mesh: resized() would
            # raise — take the checkpoint-and-relaunch path instead
            and getattr(trainer, "tp", 1) == 1
            and 0 < new_n_dev <= len(jax.devices())
            and hasattr(source, "reshard"))
        if not can_resize_live:
            raise ElasticRelaunch(
                f"membership epoch {ev.epoch}: {ev.n_workers} worker(s) "
                f"(dead {list(ev.dead)}, joined {list(ev.joined)}); "
                f"checkpointed round {rnd}")
        old_trainer, old_state = trainer, state
        trainer = trainer_factory(new_n_dev)
        if hasattr(trainer, "resized"):
            # rebind the factory: the old one is a bound method of the
            # PREVIOUS trainer and would pin it (and its compiled round
            # executable) alive for the rest of the run
            trainer_factory = trainer.resized
        replaced_live = (hasattr(trainer, "adapt_live") and
                         getattr(old_trainer, "state_layout", "")
                         == "logical")
        if replaced_live:
            # NamedSharding trainer: the resize is a RE-PLACEMENT — the
            # live logical state (params topology-free, momentum rows
            # policy-mapped) moves straight onto the new mesh; the
            # boundary checkpoint just written stays the durable record
            # but the store is never read back
            state = trainer.adapt_live(
                old_state, momentum_policy=elastic_cfg.momentum_policy)
            ck_round = rnd
        else:
            found = ckpt.restore_newest_verified(cfg.checkpoint_dir)
            if found is None:
                raise TrainingHealthError(
                    f"elastic: membership changed but no verified "
                    f"checkpoint exists under {cfg.checkpoint_dir!r} to "
                    f"resize from.")
            flat, ck_round, extra = found
            state = trainer.adapt_state(
                flat, old_tp=int(extra.get("tp", 1)),
                momentum_policy=elastic_cfg.momentum_policy,
                old_layout=extra.get("layout", "replica"))
        del old_trainer, old_state
        source = source.reshard(trainer.n_local_devices)
        n_dev = trainer.n_devices
        n_local = trainer.n_local_devices
        meter.n_chips = n_dev
        if cfg.telemetry and hasattr(trainer, "phase_timers"):
            trainer.phase_timers = timers
        if g_variants is not None and hasattr(trainer, "compiled_variants"):
            g_variants.set_fn(trainer.compiled_variants)
        log.log(f"elastic resize: epoch {ev.epoch} -> {ev.n_workers} "
                f"worker(s) on {n_dev} device(s); "
                + (f"re-placed live state at round {ck_round}"
                   if replaced_live else
                   f"restored verified round {ck_round}")
                + (f"; evicted {list(ev.dead)}" if ev.dead else "")
                + (f"; joined {list(ev.joined)}" if ev.joined else ""))
        return state, ck_round

    def expand_tau(by_worker: Optional[Dict[str, int]]):
        """Per-worker τ budgets -> the per-DATA-GROUP vector the trainer
        takes (a worker may own several device groups). Multi-host: a
        group's owner is the process owning its devices (mesh order,
        model-minor under TP). Single process — the virtual-pod
        simulation, where every device belongs to process 0 — members
        own contiguous blocks of groups in sorted-id order, matching the
        devices-per-worker resize math. Unknown owners run full τ."""
        if not by_worker:
            return None
        from ..parallel.elastic import worker_sort_key
        n_data = getattr(trainer, "n_data", n_dev)
        tp = getattr(trainer, "tp", 1)
        if jax.process_count() > 1:
            flat = list(trainer.mesh.devices.flat)
            return [by_worker.get(str(flat[g * tp].process_index), cfg.tau)
                    for g in range(n_data)]
        order = sorted(membership.members, key=worker_sort_key)
        m = max(1, len(order))
        # balanced contiguous blocks (sizes differ by <= 1): identical to
        # the devices-per-worker split when n_data % m == 0, and never
        # lumps every remainder group onto the LAST worker's budget when
        # the mesh is indivisible
        return [by_worker.get(order[min(g * m // n_data, m - 1)], cfg.tau)
                for g in range(n_data)]

    # per-round phase deltas for the step-time breakdown rows: the phase
    # timers accumulate forever; this tracks the last-seen totals so each
    # round's record carries only its own share
    last_tot: Dict[str, float] = {}

    def _phase_delta(name: str) -> float:
        cur = timers.total.get(name, 0.0)
        d = cur - last_tot.get(name, 0.0)
        last_tot[name] = cur
        return d

    log_every = max(1, cfg.log_every)
    rnd = start_round
    loop_completed = False  # set on the normal exit path only: the
    # finally block must re-raise a failed background checkpoint write on
    # a clean run, but never mask the exception of an aborted one
    try:
        while rnd < cfg.max_rounds:
            if monitor is not None and monitor.rollback_needed:
                state, rnd = recover(state)
                continue
            if membership is not None:
                # the τ boundary: between rounds every worker's params
                # are synchronized, so this is the one safe resize point
                ev = membership.poll(rnd)
                if ev is not None:
                    state, rnd = apply_resize(state, ev, rnd)
                    continue
            if test_ds is not None and cfg.eval_every and \
                    rnd % cfg.eval_every == 0:
                # keep log/JSONL round-ordered: earlier loss rows must
                # precede round R's eval row (eval blocks on the in-flight
                # round anyway, so this costs no overlap)
                flush_deferred()
                if monitor is not None and monitor.rollback_needed:
                    continue  # don't eval a poisoned state
                with timers.phase("eval"):
                    acc = _evaluate(trainer, state, test_ds, cfg.eval_batch,
                                    n_local, transform=eval_transform)
                log.log(f"test accuracy: {acc:.4f}", rnd)
                log.metrics(rnd, test_accuracy=acc)

            # trace ONE steady-state round (the first holds the round's
            # compile, which needs no profiler: its stages, the cache's
            # verdict and this step are the round's entry of the compile
            # log, `utils/compile_cache.py`): the wait for its rows, the
            # prefetch thread preparing the next
            # (`round_prep` and its phases), and the dispatch
            profile_this = cfg.profile_dir and rnd == start_round + 1
            with profiling.maybe_trace(cfg.profile_dir if profile_this
                                       else None):
                with timers.phase("sample"):
                    batches = (pending.result() if pending is not None
                               else prepare_round(rnd, retry,
                                                  rnd > high_water))
                pending = None
                if rnd + 1 < cfg.max_rounds:
                    pending = prefetch.submit(prepare_round, rnd + 1, retry,
                                              rnd + 1 > high_water)
                high_water = max(high_water, rnd)
                sub = jax.random.fold_in(base_rng, rnd)
                if retry:  # deterministic-but-different retried window
                    sub = jax.random.fold_in(sub, retry)
                before = timers.total.get("train_round", 0.0)
                with timers.phase("train_round"):
                    tr_kw: Dict[str, Any] = {}
                    if supports_lr and lr_scale != 1.0:
                        tr_kw["lr_scale"] = lr_scale
                    if getattr(trainer, "elastic_tau", False) and \
                            membership is not None:
                        # heterogeneous pods: per-worker local-step
                        # budgets from the heartbeat round times (a
                        # traced input — adapting never recompiles),
                        # expanded to one entry per data group
                        tr_kw["tau_by_worker"] = expand_tau(
                            membership.tau_by_worker(cfg.tau))
                    state, loss = trainer.train_round(state, batches, sub,
                                                      **tr_kw)
                    # async probe slice MUST precede the next dispatch
                    # (donation invalidates the old state buffers)
                    probe_val = probe(state) if probe else None
                    if len(deferred) >= log_every:
                        # collect_async: enqueue only — the collector
                        # thread syncs on rounds <= rnd-1 while this
                        # loop dispatches ahead. Sync mode blocks here
                        # (the pre-r8 pipeline's one-round overlap).
                        flush_deferred(wait=False)
            if profile_this:
                log.log(f"profiler trace written to {cfg.profile_dir}", rnd)
                # a profiled run is the one place the loop asks the round
                # program for its own account (a compile-cache hit, or a
                # second compile): never in an unprofiled run
                report = (obs_device.program_report("train_round")
                          if hasattr(trainer, "program_report") else None)
                if report is not None:
                    log.log("train_round program, bytes per device: "
                            + ", ".join(f"{k} {v}" for k, v in
                                        report["memory"].items()), rnd)
            # steady state (log_every=1), this measures one device round:
            # dispatch of rnd + wait for rnd-1 (overlap of exactly one
            # round); with log_every=K the sync cost amortizes over K
            round_dt = timers.total["train_round"] - before
            n_images = cfg.tau * cfg.local_batch * n_dev
            meter.add(n_images, round_dt)
            breakdown = None
            if cfg.telemetry:
                d_sample = _phase_delta("sample")
                d_h2d = _phase_delta("h2d")
                d_disp = _phase_delta("dispatch")
                # checkpoint stage-1 accrues AFTER the record is appended,
                # so the delta seen here is the PREVIOUS round's fetch —
                # honest attribution: that stall delayed THIS round
                d_ck = _phase_delta("checkpoint")
                breakdown = {
                    "data": d_sample, "h2d": d_h2d,
                    # trainers without the h2d/dispatch split (GraphTrainer)
                    # report the whole timed round
                    "round": d_disp if d_disp > 0 else round_dt,
                    "ckpt_fetch": d_ck}
            if c_rounds is not None:
                c_rounds.inc()
            deferred.append((rnd, loss, probe_val,
                             getattr(trainer, "last_health", None),
                             breakdown))

            if cfg.checkpoint_dir and cfg.checkpoint_every and \
                    (rnd + 1) % cfg.checkpoint_every == 0:
                flush_deferred()  # keep log rows round-ordered; the
                if monitor is not None and monitor.rollback_needed:
                    continue  # NEVER checkpoint over good state with a
                    #           poisoned one; loop top recovers instead
                anomalous = (monitor is not None
                             and monitor.recently_anomalous(rnd))
                # the timed phase is the loop's BLOCKING stall only: the
                # device->host fetch (+ waiting out a still-running
                # previous write); stage 2 persists in the background
                with timers.phase("checkpoint"):
                    _save_checkpoint(cfg, trainer, state, rnd + 1,
                                     source=source, last_round=rnd,
                                     anomalous=anomalous,
                                     health_state=_health_state(
                                         retry, lr_scale, monitor),
                                     writer=ck_writer)
                if anomalous:
                    log.event(rnd, "anomalous_checkpoint",
                              checkpoint_step=rnd + 1)
                log.log("checkpoint saved" if ck_writer is None else
                        "checkpoint snapshotted (async write)", rnd)
            if round_hook:
                round_hook(rnd, state)
            rnd += 1
            if rnd >= cfg.max_rounds:
                # the final rounds' health records are still on device:
                # flush so an anomaly in the tail window triggers recovery
                # BEFORE the loop exits and the final checkpoint is written
                flush_deferred()
                if monitor is not None and monitor.rollback_needed:
                    state, rnd = recover(state)
        loop_completed = True
    finally:
        if deferred:  # loop aborted: drain the pending fetches
            try:
                flush_deferred()
            except Exception:
                pass
        if pending is not None:
            pending.cancel()
        prefetch.shutdown(wait=False, cancel_futures=True)
        if collector is not None:
            # drain the collector (its queue may hold the abort-path
            # records just submitted above); a failed flush must not
            # mask the propagating exception
            try:
                while collect_pending:
                    collect_pending.popleft().result()
            except Exception:
                pass
            collector.shutdown(wait=True)
        if hasattr(source, "close"):
            source.close()
        try:
            if ck_writer is not None:
                # loop exit barriers on the in-flight write: a RUNNING
                # stage-2 write always completes (the final checkpoint
                # below, and any reader of the dir after train() returns,
                # must see a settled store). On the normal path a failed
                # background write raises here; when another exception is
                # already propagating (loop_completed is still False) it
                # must not be masked — log and let the original win.
                try:
                    ck_writer.close(wait=True)
                except Exception as e:
                    if loop_completed:
                        raise
                    log.log(f"background checkpoint write failed during "
                            f"abort: {e}")
        finally:
            # obs teardown runs EVEN when the writer's failure is
            # re-raising: the port must unbind and the process-global
            # tracer must uninstall (a leaked active tracer would keep
            # swallowing every later span in this process)
            if history is not None:
                history.stop()
            if status_srv is not None:
                status_srv.stop()
            if pod_srv is not None:
                pod_srv.stop()
            if tracer is not None:
                # stop AFTER the writer drained: the final
                # checkpoint_write span must land on its lane. Writing
                # the file is observability, not training — it degrades,
                # never raises.
                obs_trace.stop_tracing()
                try:
                    n_ev = tracer.write(cfg.trace_out)
                    log.log(f"host trace written to {cfg.trace_out} "
                            f"({n_ev} events; load in Perfetto or "
                            f"chrome://tracing)")
                except OSError as e:
                    log.log(f"host trace write failed: {e}")

    if cfg.checkpoint_dir and start_round < cfg.max_rounds:
        # start_round >= max_rounds means the loop ran ZERO rounds (a
        # relaunch of a completed run): the restored checkpoint is already
        # the final state, and re-saving would overwrite it with no stream
        # cursor (cursor_at has seen no rounds), destroying the resume
        # position a later extended run needs
        _save_checkpoint(cfg, trainer, state, cfg.max_rounds, retain=False,
                         source=source, last_round=cfg.max_rounds - 1,
                         anomalous=(monitor is not None and
                                    monitor.recently_anomalous(
                                        cfg.max_rounds - 1)),
                         health_state=_health_state(retry, lr_scale,
                                                    monitor))
    if monitor is not None and (monitor.counts["spike"]
                                or monitor.counts["nonfinite"]):
        log.log(f"health summary: {monitor.counts['spike']} spikes, "
                f"{monitor.counts['nonfinite']} nonfinite rounds, "
                f"{monitor.rollbacks} rollbacks")
    beat(rnd, status="done", force=True)
    for hb in (heartbeat, pod_hb):
        if hb is not None:
            hb.flush()  # bounded wait so the done beat lands on buckets
    log.log(f"done; phase means: {timers.summary()}")
    return state


def _restore_state(trainer, state, flat: Dict[str, np.ndarray],
                   extra: Dict[str, Any]):
    """Load a restored flat checkpoint into the trainer's state layout:
    same-topology place, or the elastic adapt_state path. Returns
    (state, same_topology). Shared by resume and health rollback so the
    two cannot drift.

    The elastic path is keyed on the SAVED topology, never on a shape
    error: an architecture change on the same topology must fail loudly
    through unflatten_like, not be silently adapted. Pre-topology-metadata
    checkpoints carry no n_devices/tp keys; infer the saved device count
    from the leading replica axis of the 'it' counter (every state layout
    tiles it [n_devices]) instead of assuming same-topology and dying in
    unflatten_like."""
    tp_now = getattr(trainer, "tp", 1)
    saved_dev = extra.get("n_devices")
    if saved_dev is None and "it" in flat:
        it_arr = np.asarray(flat["it"])
        if it_arr.ndim:
            saved_dev = it_arr.shape[0]
    # the state LAYOUT is part of the topology: a logical (NamedSharding
    # trainer) checkpoint under a replica-axis trainer — or the reverse,
    # or a different state_sharding mode (momentum shape changes) — must
    # take the adapt path, not unflatten_like
    saved_layout = extra.get("layout", "replica")
    t_layout = getattr(trainer, "state_layout", "replica")
    same_topo = (int(saved_dev or trainer.n_devices) == trainer.n_devices
                 and int(extra.get("tp", tp_now)) == tp_now
                 and saved_layout == t_layout
                 and (extra.get("state_sharding", "replicated")
                      == getattr(trainer, "state_sharding", "replicated")))
    if same_topo:
        return trainer.place(ckpt.unflatten_like(state, flat)), True
    if not hasattr(trainer, "adapt_state"):
        raise ValueError(
            f"checkpoint topology {extra} != current "
            f"({trainer.n_devices} devices, tp={tp_now}) and this trainer "
            f"cannot adapt — resume on the original topology")
    # ELASTIC / cross-layout: params re-tiled exactly, momentum
    # reconstructed (adapt_state; old_layout routes the parse). Only the
    # layer-IR trainers declare state_layout and accept old_layout= —
    # GraphTrainer.adapt_state(flat, old_tp) predates layouts, and a
    # logical checkpoint has no graph-backend reading anyway.
    kw = {"old_tp": int(extra.get("tp", 1))}
    if hasattr(trainer, "state_layout"):
        kw["old_layout"] = saved_layout
    elif saved_layout != "replica":
        raise ValueError(
            f"checkpoint layout {saved_layout!r} needs a layer-IR trainer "
            f"to adapt; {type(trainer).__name__} only reads replica "
            f"checkpoints")
    return trainer.adapt_state(flat, **kw), False


def _stream_rows(source, last_round: Optional[int]) -> Optional[list]:
    """Per-host stream cursors after `last_round`, allgathered so process
    0's checkpoint covers every host's stream position: one entry per host,
    each a [[shard, entry, epochs], ...] list with one row PER READER
    (ParallelStreamingSource runs N concurrent readers per host; a single
    StreamingRoundSource is the N=1 case). None when the source is not
    seekable or the cursor is no longer retained. Collective when
    multi-host — every process calls _save_checkpoint already."""
    if last_round is None or not hasattr(source, "cursor_at"):
        return None
    cur = source.cursor_at(last_round)
    if cur is None:
        return None
    if not isinstance(cur, list):  # single-reader source
        cur = [cur]
    rows = np.asarray([[s, e, ep] for (s, e), ep in cur], np.int64)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        rows = np.asarray(multihost_utils.process_allgather(rows))
    else:
        rows = rows[None]
    return rows.tolist()


def _seek_stream(source, extra: Dict[str, Any], log: Logger) -> None:
    """Resume the stream position recorded in the checkpoint (per host, one
    cursor row per reader). Host-count OR reader-count changes restart the
    stream from shard 0 — the shard assignment itself changed, so old
    cursors are meaningless. Accepts the pre-r4 flat [shard, entry,
    epochs]-per-host format as a 1-reader cursor."""
    rows = extra.get("stream")
    if rows is None:
        return
    if len(rows) != jax.process_count():
        log.log(f"stream cursor in checkpoint covers {len(rows)} hosts, "
                f"now {jax.process_count()}: restarting stream at shard 0")
        return
    host_rows = rows[jax.process_index()]
    if host_rows and not isinstance(host_rows[0], list):
        host_rows = [host_rows]  # legacy flat single-reader row
    if hasattr(source, "seek_rows"):
        if not source.seek_rows(host_rows):
            log.log(f"stream cursor in checkpoint covers {len(host_rows)} "
                    f"readers, source has a different count: restarting "
                    f"stream at shard 0")
            return
    elif hasattr(source, "seek") and len(host_rows) == 1:
        shard, entry, epochs = host_rows[0]
        source.seek((shard, entry), epochs)
    else:
        return
    pos = ", ".join(f"shard {s} entry {e} (epoch {ep})"
                    for s, e, ep in host_rows)
    log.log(f"stream resumed at {pos}")


def _health_state(retry: int, lr_scale: float,
                  monitor: Optional[HealthMonitor]) -> Optional[Dict[str,
                                                                     Any]]:
    """Supervisor recovery state for the checkpoint `extra` — only when it
    differs from a fresh run's (vanilla checkpoints stay byte-identical to
    the pre-health format)."""
    rollbacks = monitor.rollbacks if monitor is not None else 0
    if not retry and lr_scale == 1.0 and not rollbacks:
        return None
    return {"retry": int(retry), "lr_scale": float(lr_scale),
            "rollbacks": int(rollbacks)}


def _sharded_save_enabled(cfg: RunConfig, trainer, state) -> bool:
    """Resolve cfg.checkpoint_sharded for this trainer/state. "auto":
    sharded for multi-device layer-IR trainers (the state carries
    NamedShardings to key the piece plan on); monolithic for the graph
    backend and single-device runs, where there is nothing to split.
    "on" forces and fails loudly where the plan has no shardings to read;
    "off" restores the monolithic fetch_global path wholesale."""
    knob = str(getattr(cfg, "checkpoint_sharded", "off"))
    if knob not in ("auto", "on", "off"):
        raise ValueError(f"checkpoint_sharded={knob!r}: expected "
                         f"'auto', 'on', or 'off'")
    if knob == "off":
        return False
    placed = hasattr(trainer, "mesh") and all(
        isinstance(leaf, jax.Array)
        for leaf in jax.tree_util.tree_leaves(state))
    if knob == "on":
        if not placed:
            raise ValueError(
                "checkpoint_sharded='on' needs a mesh trainer with "
                "device-placed state (the shard plan is keyed on each "
                "leaf's NamedSharding) — the graph backend and host "
                "states save monolithically")
        return True
    return (placed and getattr(trainer, "state_layout", None) is not None
            and trainer.n_devices > 1)


def _save_checkpoint(cfg: RunConfig, trainer, state, step: int,
                     retain: bool = True, source=None,
                     last_round: Optional[int] = None,
                     anomalous: bool = False,
                     health_state: Optional[Dict[str, Any]] = None,
                     writer: Optional[ckpt.AsyncCheckpointWriter] = None
                     ) -> None:
    """Two-stage checkpoint save. Stage 1 (here, blocking — every host
    must call this): snapshot the state to host buffers and the stream
    cursors. Since r8 the default stage 1 is GATHER-FREE
    (`fetch_state_shards`): each worker materializes only the distinct
    state pieces its own devices hold — never the full state on one host
    — and stage 2 writes them as parallel per-shard files with a
    manifest commit marker (`ckpt.save_sharded`). The monolithic
    `fetch_global` allgather remains the fallback (graph backend, one
    device, cfg.checkpoint_sharded="off"); restores read both layouts
    bit-identically. Stage 2 (serialize + digest + persist) is inline
    when `writer` is None, else handed to the background writer thread
    so the round loop resumes as soon as the host buffers exist — the
    snapshot is immutable numpy, so later rounds can't tear it. The
    saved logical bytes, digests, and tagging are IDENTICAL in both
    modes.

    The saved topology (device count, tp) lets a differently-sized job
    resume elastically; streaming sources also record their per-host
    stream cursor so resume seeks instead of re-streaming from shard 0.
    `anomalous=True` tags a checkpoint taken during an unhealthy training
    window (recent spike/nonfinite rounds) so the health supervisor's
    rollback skips it."""
    sharded = _sharded_save_enabled(cfg, trainer, state)
    snapshot = host_state = None
    if sharded:
        if jax.process_count() > 1:
            # multi-process stage-1 cleanup (decommit an overwritten
            # step, clear the step's stale files + commit reports,
            # sweep orphans) fenced on BOTH sides: first every process
            # drains its own in-flight stage-2 write and barriers (the
            # previous step's uncommitted shard files must never read
            # as sweepable orphans mid-write — writer.submit would
            # have waited anyway, the backpressure just lands a beat
            # earlier), then process 0 cleans, then a second barrier
            # orders the cleanup before any peer's stage-2 writes
            from jax.experimental import multihost_utils
            if writer is not None:
                writer.wait()
            multihost_utils.sync_global_devices(
                f"sharded_ckpt_drain_{step}")
            if jax.process_index() == 0:
                ckpt.prepare_sharded_step(cfg.checkpoint_dir, step)
            multihost_utils.sync_global_devices(
                f"sharded_ckpt_prepare_{step}")
        # gather-free stage 1: per-shard host pieces, async D2H first;
        # own_data deep-copies any piece view still aliasing a device
        # buffer (donation may reuse it under the async stage 2)
        from ..parallel.mesh import fetch_state_shards
        snapshot = fetch_state_shards(state, trainer.mesh)
    else:
        host_state = fetch_global(state)
        if writer is not None:
            # the background writer must OWN its bytes: np.asarray on a
            # CPU-backend jax array can be a zero-copy VIEW of the device
            # buffer, and the next round's jitted step DONATES that
            # buffer — the sync path finished serializing before the
            # donation could reuse it, but stage 2 overlaps later rounds.
            # One defensive memcpy of any non-owning leaf (~50 ms for a
            # 244 MB state, still ~1000x under the sync stall);
            # real-device fetches already own their memory and copy
            # nothing here. (The sharded path owns its pieces already —
            # fetch_state_shards' own_data default.)
            host_state = jax.tree.map(
                lambda a: a if a.flags["OWNDATA"] else np.array(a),
                host_state)
    stream = _stream_rows(source, last_round) if source is not None else None
    if jax.process_index() != 0 and not sharded:
        return  # monolithic: process 0 is the only writer; sharded:
        #         every process persists its own shard files

    def persist() -> None:
        # publish instant from the TRAINING loop's side: persist() runs
        # at the head of stage 2 (inline or on the writer thread), so
        # this is when the weights left the round loop. checkpoint.py
        # re-stamps the authoritative top-level commit_ts at meta-write
        # time; the serve fleet's freshness metric keys off that one,
        # this tag survives in extra for commit-latency forensics.
        extra = {"n_devices": trainer.n_devices,
                 "tp": getattr(trainer, "tp", 1),
                 "publish_t": round(time.time(), 3)}
        layout = getattr(trainer, "state_layout", "replica")
        if layout != "replica":
            # NamedSharding trainer: logical leaves (no [n_devices] axis).
            # Stamped so restore routes between the layouts; the momentum
            # SHAPE additionally depends on the state_sharding mode
            # ([n_data] worker rows vs one ZeRO-averaged tree). Replica
            # checkpoints stay byte-identical to the pre-r7 format.
            extra["layout"] = layout
            extra["state_sharding"] = getattr(trainer, "state_sharding",
                                              "replicated")
        if stream is not None:
            extra["stream"] = stream
        if anomalous:
            extra["anomalous"] = True
        if health_state is not None:
            extra["health"] = health_state
        if sharded:
            ckpt.save_sharded(
                cfg.checkpoint_dir, snapshot, step=step, extra=extra,
                metrics=writer.note_write if writer is not None else None)
        else:
            ckpt.save(cfg.checkpoint_dir, host_state, step=step,
                      extra=extra)
        if retain and jax.process_index() == 0:
            try:
                ckpt.retain(cfg.checkpoint_dir, keep=3)
            except Exception as e:
                # retention is best-effort (its own delete paths already
                # warn-and-continue): a store blip during the protect
                # scan's reads must not surface as a FATAL writer error
                # when the checkpoint itself saved fine — the next save
                # re-runs retention. The propagation inside retain still
                # matters: it aborts the scan BEFORE deleting anything.
                warnings.warn(f"checkpoint retention failed (snapshot "
                              f"step-{step} saved OK): {e}",
                              RuntimeWarning)

    if writer is not None:
        writer.submit(persist)
    else:
        persist()


def _to_device_layout(ds: ArrayDataset, net: CompiledNet) -> ArrayDataset:
    """One-time NCHW -> NHWC conversion for 4D inputs that arrive in the
    reference's Caffe layout (same disambiguation as JaxNet input_layout
    'auto')."""
    arrays = dict(ds.arrays)
    for name, want in net.input_shapes.items():
        arr = arrays.get(name)
        if arr is None or arr.ndim != 4:
            continue
        want_el = tuple(want[1:])
        if tuple(arr.shape[1:]) != want_el and \
                (arr.shape[2], arr.shape[3], arr.shape[1]) == want_el:
            arrays[name] = np.ascontiguousarray(
                np.transpose(arr, (0, 2, 3, 1)))
    return ArrayDataset(arrays)


def _evaluate(trainer, state, test_ds: ArrayDataset, eval_batch: int,
              n_dev: int, transform=None) -> float:
    """Distributed eval (reference `CifarApp.scala:107-124`), covering every
    example except at most n_dev-1 trailing ones (batches must split evenly
    across devices): the tail past the last full eval_batch is evaluated as
    one smaller batch (a second compiled shape, amortized across rounds) and
    weighted by its real size.

    `transform` preprocesses each eval batch lazily (train=False — e.g.
    center crop + mean subtract on raw uint8 pixels), so only one batch of
    float32 pixels ever exists at a time — the whole-split float32
    materialization would be ~6x the uint8 corpus."""
    eval_batch = min(eval_batch, len(test_ds))
    eval_batch = max(n_dev, (eval_batch // n_dev) * n_dev)
    if len(test_ds) < eval_batch:
        raise ValueError(
            f"test set ({len(test_ds)}) smaller than {n_dev} devices' "
            f"minimum eval batch")

    def run(lo: int, n: int) -> float:
        batch = {k: v[lo:lo + n] for k, v in test_ds.arrays.items()}
        if transform is not None:
            batch = transform.convert_batch(batch, train=False)
        return trainer.evaluate(state, batch) * n

    total, count = 0.0, 0
    n_full = (len(test_ds) // eval_batch) * eval_batch
    for i in range(0, n_full, eval_batch):
        total += run(i, eval_batch)
        count += eval_batch
    tail = ((len(test_ds) - n_full) // n_dev) * n_dev
    if tail:
        total += run(n_full, tail)
        count += tail
    return total / max(count, 1)
