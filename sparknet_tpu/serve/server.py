"""The serving loop: bucket-padded jit forwards over dynamically formed
batches, hot-reload between batches, metrics + /healthz.

Shape buckets: requests are padded to the smallest configured bucket size
>= the formed batch (default: powers of two up to max_batch), so the jit
cache holds exactly len(buckets) compiled forwards — an arbitrary batch
size would compile a fresh XLA program per distinct size and the server
would spend its first hour tracing. Padding rows are zeros; de-padding
slices each request's own row back out. Within one compiled bucket the
padding is bitwise-lossless for these nets (every layer is row-independent
across the batch — conv/fc/relu/pool/lrn/softmax; tests pin this).
Across DIFFERENT buckets XLA may re-associate reductions, so outputs are
allclose-but-not-bitwise between e.g. the 1-bucket and 8-bucket of the
same example — same contract training accepts for different batch shapes.

Pad/de-pad is PRE-SIZED: each bucket owns one cached host buffer per net
input (allocated on first use, reused every batch), and request rows are
stacked straight into it — the per-batch Python cost is one buffer fill
per input, not an alloc-stack-alloc-pad-alloc-concat chain per request.
Safe because `net.forward` copies host->device synchronously before
returning, and exactly one thread drives a lane at a time (below).

One worker owns the net: batch forwards, weight swaps (between batches,
via ModelManager), and the canary all run on it, so no lock guards the
params. In the classic single-model deployment that worker is the lane's
own thread (`start()`); under the multi-model router the lane has NO
thread of its own — router pool threads call `serve_tick()` one at a
time under `lane_lock` (same single-writer guarantee, pooled across
models). The worker parks in the batcher's wake-on-submit wait; periodic
duties (hot-reload poll, heartbeat) run on their own cadence via the
`wake_at` alarm, not a fixed idle poll. Request futures are resolved from
the serving thread; client threads only enqueue and wait.

Requests are dicts of PER-EXAMPLE arrays (no batch dim). Missing net
inputs are zero-filled (nets from the zoo carry label-consuming loss/
accuracy heads; an inference client has no labels). An optional
`ImagePreprocessor` decodes raw request pixels batch-at-a-time with
`train=False` (deterministic center crop + mean subtract — the same
`data/preprocess.py` path eval uses, so served pixels match eval pixels).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..model.quant import QuantConfig
from ..obs import (MetricsRegistry, StatusServer, register_build_info,
                   trace as obs_trace)
from ..obs import device as obs_device
from ..obs import reqtrace
from ..utils.compile_cache import init_compile_cache, track_compiles
from ..utils.heartbeat import HeartbeatWriter
from ..utils.logger import Logger
from ..utils.metrics import FillMeter, LatencyStats
from .batcher import DynamicBatcher, ServeRequest
from .model_manager import ModelManager


def net_input_specs(net) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{input name: (per-example device-layout shape, dtype)} for either
    backend (JaxNet wraps a CompiledNet; GraphNet exposes introspection
    methods — the NetInterface split featurizer_app already bridges)."""
    if hasattr(net, "net"):  # JaxNet
        dtypes = {i.name: i.dtype for i in net.net.spec.inputs}
        return {name: (tuple(shape[1:]), dtypes.get(name, "float32"))
                for name, shape in net.net.input_shapes.items()}
    shapes, dtypes = net.input_shapes(), net.input_dtypes()
    return {name: (tuple(shapes[name][1:]),
                   dtypes.get(name, "float32")) for name in shapes}


def zeros_batch(net, n: int, float_dtype=None) -> Dict[str, np.ndarray]:
    """An all-zeros batch of n examples in the net's input schema — the
    canary forward's food, and the source of padding for absent inputs.
    `float_dtype` overrides the schema dtype for FLOATING inputs (the
    quantized serve path feeds bf16 activation buffers — half the
    host->device bytes; int/label inputs keep their schema dtype)."""
    out = {}
    for name, (shape, dtype) in net_input_specs(net).items():
        dt = np.dtype(dtype)
        if float_dtype is not None and np.issubdtype(dt, np.floating):
            dt = np.dtype(float_dtype)
        out[name] = np.zeros((n,) + shape, dtype=dt)
    return out


def parity_batch(net, n: int, seed: int = 0) -> Dict[str, np.ndarray]:
    """A deterministic RANDOM batch in the net's input schema — the
    quant parity canary's food. Zeros would vet only the bias path (a
    conv of zeros never touches w, so a corrupted weight SCALE would
    sail through); standard-normal pixels exercise every quantized
    weight."""
    r = np.random.default_rng(seed)
    out = {}
    for name, (shape, dtype) in net_input_specs(net).items():
        dt = np.dtype(dtype)
        if np.issubdtype(dt, np.floating):
            out[name] = r.standard_normal((n,) + shape).astype(dt)
        else:
            out[name] = np.zeros((n,) + shape, dtype=dt)
    return out


# Reserved payload key carrying a request's named output blobs across
# transports that only speak tensors (the binary wire, npz POST bodies).
# Encoded as a uint8 view of the comma-joined names so it rides the
# existing frame format — no wire VERSION bump, and a proxy hop that
# doesn't understand it forwards it untouched (the terminal frontend
# pops it before the tensors reach the net).
OUTPUTS_KEY = "__outputs__"


def encode_outputs(payload: Dict[str, Any],
                   outputs: Optional[Tuple[str, ...]]) -> Dict[str, Any]:
    """Return payload with the outputs request folded in as a tensor
    field (no-op when outputs is empty). Does not mutate the input."""
    if not outputs:
        return payload
    names = ",".join(outputs)
    out = dict(payload)
    out[OUTPUTS_KEY] = np.frombuffer(names.encode("utf-8"), dtype=np.uint8)
    return out


def pop_outputs(payload: Dict[str, Any]) -> Tuple[Dict[str, Any],
                                                  Optional[Tuple[str, ...]]]:
    """Split a payload into (tensors, requested output names). The
    inverse of encode_outputs; payloads without the key pass through."""
    if OUTPUTS_KEY not in payload:
        return payload, None
    out = dict(payload)
    raw = np.asarray(out.pop(OUTPUTS_KEY), dtype=np.uint8)
    names = raw.tobytes().decode("utf-8", errors="replace")
    parsed = tuple(n for n in (s.strip() for s in names.split(",")) if n)
    return out, (parsed or None)


def default_buckets(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to max_batch (max_batch itself always included)."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(out)


@dataclass
class ServeConfig:
    """Knobs for the inference server (the `sparknet-serve` CLI mirrors
    these 1:1)."""

    # identity: labels every serve metric family this lane registers
    # (the router shares one registry across models) and names the model
    # in /status, heartbeats, and the HTTP data plane's URL space
    model_name: str = "default"
    # batching policy
    max_batch: int = 8
    max_wait_ms: float = 5.0            # oldest-request deadline
    # batch-size buckets (None -> powers of 2 up to max_batch; or a
    # traffic-derived ladder from serve.buckets.derive_buckets /
    # `sparknet-serve --buckets-from`). Validated at CONSTRUCTION
    # (__post_init__, the ElasticConfig rule): strictly increasing,
    # positive, and the top rung must cover a full max_batch batch —
    # a bad ladder used to surface as a StopIteration inside the first
    # forward's bucket pick, long after the config typo that caused it.
    buckets: Optional[Tuple[int, ...]] = None
    max_queue: int = 1024               # backpressure threshold
    # weight-only quantized serving (model/quant.py): None = the f32
    # path exactly as before; "int8" (or a QuantConfig) = weights are
    # quantized per output channel at ModelManager load time, forwards
    # run int8-weight x bf16-activation, and every install is gated on
    # an allclose parity canary against the f32 forward — a bad
    # quantization (e.g. a corrupted scale) never serves.
    quant: Optional[Any] = None
    # persistent XLA compile cache (utils/compile_cache.py): directory
    # for jax's compilation cache, so replica cold-starts / hot-swap
    # retraces / bucket first-forwards re-use executables across
    # PROCESSES. $JAX_COMPILATION_CACHE_DIR, where set, wins over this
    # field; None = the fixed <checkout>/.cache/jax.
    compile_cache_dir: Optional[str] = None
    # per-model latency objective (ms). Advisory: stamped into /status
    # and BENCH_SERVE rows (p99 <= slo at the sustainable rate is the
    # open-loop acceptance); nothing enforces it at runtime.
    slo_p99_ms: Optional[float] = None
    # response content: blob names to return (None -> the net's output
    # schema, e.g. prob/accuracy/loss for zoo nets — pass ("prob",) to
    # skip the label-dependent heads)
    outputs: Optional[Tuple[str, ...]] = None
    # checkpoint hot-reload
    checkpoint_dir: Optional[str] = None
    poll_interval_s: float = 2.0
    # ± fraction of poll_interval_s each poll deadline is jittered by: a
    # fleet of replicas watching one bucket must not list it in lockstep
    # on every commit (thundering herd)
    poll_jitter: float = 0.1
    canary: bool = True                 # nonfinite-canary gate on swaps
    # fleet identity: the key this replica looks itself up under in the
    # rollout gate and the `replica` label on the freshness gauges
    # (providers pass their tag; a standalone server stays "local")
    replica_name: str = "local"
    # rollout gate path (fleet/rollout.py ROLLOUT.json): when set, this
    # replica only adopts checkpoint steps the fleet rollout duty
    # approved for it; missing gate = ungated independent polling
    rollout_gate: Optional[str] = None
    # observability. status_port serves /metrics (Prometheus text from
    # the shared obs registry — the SAME metric-name schema the training
    # process exports), /healthz and /status (the JSON vitals dict).
    # registry: pass a MetricsRegistry to share one registry across
    # co-located components; None = a fresh per-server instance.
    status_port: Optional[int] = None   # None = no HTTP; 0 = ephemeral
    status_host: str = "127.0.0.1"      # "0.0.0.0" for cross-host scrapes
    # SLO ledger (obs/history.py + obs/slo.py). history=True runs the
    # metrics-history sampler thread (multi-resolution rings, the
    # /timeseries route) and — when an objective is declared — the
    # burn-rate alerter (/slo/status, slo section in /status, fleet
    # page escalation). history_dir persists append-only JSONL shards
    # `sparknet-slo` reports from (None = rings only, no disk).
    history: bool = False
    history_dir: Optional[str] = None
    history_interval_s: float = 1.0
    # availability objective (fraction of requests answered "ok", e.g.
    # 0.999); pairs with slo_p99_ms (the latency objective) to form this
    # lane's SloSpec. slo_spec overrides both with a full obs.slo.SloSpec
    # (custom burn windows).
    slo_availability: Optional[float] = None
    slo_spec: Optional[Any] = None
    heartbeat_path: Optional[str] = None
    heartbeat_every_s: float = 10.0
    metrics_every_batches: int = 50     # JSONL cadence (0 = off)
    # DEPRECATED (wake-on-submit): the worker no longer idle-polls; it
    # parks in the batcher's condition wait and wakes on submit, with
    # periodic duties (reload poll, heartbeat) alarmed at their own
    # cadence. Kept so old configs still construct; only healthz's
    # freshness bound still glances at it.
    idle_poll_s: float = 0.05
    registry: Optional[MetricsRegistry] = None

    def __post_init__(self) -> None:
        # fail at construction, not at the first _pick_bucket next() —
        # the ElasticConfig rule
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1 "
                             f"(got {self.max_batch})")
        if self.buckets is not None:
            b = tuple(int(x) for x in self.buckets)
            if not b:
                raise ValueError("buckets must be None or non-empty")
            if any(x <= 0 for x in b):
                raise ValueError(f"buckets must be positive (got {b})")
            if any(y <= x for x, y in zip(b, b[1:])):
                raise ValueError(
                    f"buckets must be strictly increasing — sorted, no "
                    f"duplicates (got {b})")
            if b[-1] < self.max_batch:
                raise ValueError(
                    f"largest bucket {b[-1]} < max_batch "
                    f"{self.max_batch}: a full batch would have no "
                    f"bucket")
            self.buckets = b
        if self.history_interval_s <= 0:
            raise ValueError(f"history_interval_s must be > 0 "
                             f"(got {self.history_interval_s})")
        if self.slo_availability is not None \
                and not 0.0 < self.slo_availability < 1.0:
            raise ValueError(f"slo_availability must be in (0, 1) "
                             f"(got {self.slo_availability})")
        # "int8" / dict / QuantConfig -> QuantConfig (validates knobs)
        self.quant = QuantConfig.coerce(self.quant)


class InferenceServer:
    """Dynamic-batching inference over one NetInterface net (module doc)."""

    def __init__(self, net, cfg: Optional[ServeConfig] = None,
                 preprocessor=None, logger: Optional[Logger] = None):
        self.net = net
        self.cfg = cfg = cfg if cfg is not None else ServeConfig()
        self.model_name = cfg.model_name
        self.preprocessor = preprocessor
        self.log = logger
        # persistent compile cache: process-global, so first-server-wins
        # on the directory; a replica cold-start with a warm cache dir
        # re-uses every bucket executable instead of recompiling them.
        # Called UNCONDITIONALLY (the train loop's rule): the directory
        # is $JAX_COMPILATION_CACHE_DIR, else the knob, else the fixed
        # in-checkout default — with the cache-everything floors dropped
        init_compile_cache(cfg.compile_cache_dir)
        self.buckets = tuple(sorted(cfg.buckets or
                                    default_buckets(cfg.max_batch)))
        assert self.buckets[-1] >= cfg.max_batch, (
            f"largest bucket {self.buckets[-1]} < max_batch "
            f"{cfg.max_batch}: a full batch would have no bucket")
        # quantized serving: bf16 activation buffers (half the H2D bytes;
        # the schema dtype otherwise). The pad-buffer cache below is
        # keyed by dtype as well as bucket so a quant<->f32 transition
        # can never alias buffers of the wrong dtype.
        self.quant = cfg.quant
        self._float_dtype = None
        if self.quant is not None and self.quant.act == "bfloat16":
            import ml_dtypes
            self._float_dtype = np.dtype(ml_dtypes.bfloat16)
        # the shared-schema registry: every serve component registers into
        # it and /metrics renders it (one exporter for train AND serve);
        # under the router ALL lanes share one registry and the `model`
        # label keeps their families apart
        self.registry = cfg.registry or MetricsRegistry()
        register_build_info(self.registry)
        self._c_requests = self.registry.counter(
            "sparknet_serve_requests_total", "served requests by outcome",
            labels=("model", "outcome"))
        # jit-cache churn as a first-class metric: the FIRST forward of
        # each batch bucket is the one that builds that bucket's compiled
        # executable — count and time it. Steady state == len(buckets)
        # per model; growth past that means compile cliffs are back in
        # the tail.
        self._c_bucket_compiles = self.registry.counter(
            "sparknet_serve_bucket_compiles_total",
            "first forward per batch bucket (jit-cache entries built)",
            labels=("model",))
        self._h_bucket_compile = self.registry.histogram(
            "sparknet_serve_bucket_compile_seconds",
            "wall time of each bucket's first (compiling) forward",
            labels=("model",), buckets=obs_device.COMPILE_BUCKETS)
        self._compiled_buckets: set = set()
        self.batcher = DynamicBatcher(cfg.max_batch,
                                      max_wait_s=cfg.max_wait_ms / 1e3,
                                      max_queue=cfg.max_queue,
                                      registry=self.registry,
                                      model=cfg.model_name)
        hb = (HeartbeatWriter(cfg.heartbeat_path, role="serve",
                              interval_s=cfg.heartbeat_every_s,
                              registry=self.registry)
              if cfg.heartbeat_path else None)
        self.heartbeat = hb
        self.manager = ModelManager(
            net, checkpoint_dir=cfg.checkpoint_dir,
            poll_interval_s=cfg.poll_interval_s,
            canary_batch=(zeros_batch(net, self.buckets[0],
                                      float_dtype=self._float_dtype)
                          if cfg.canary else None),
            canary_outputs=cfg.outputs, logger=logger, heartbeat=hb,
            registry=self.registry, model=cfg.model_name,
            quant=self.quant,
            parity_batch=(parity_batch(net, self.buckets[0])
                          if self.quant is not None else None),
            replica=cfg.replica_name, poll_jitter=cfg.poll_jitter,
            rollout_gate=cfg.rollout_gate)
        # meters: worker-thread-written, internally locked — status() and
        # the HTTP scrape read consistent snapshots, never torn state
        self.latency = LatencyStats(registry=self.registry,
                                    model=cfg.model_name)
        self.fill = FillMeter(registry=self.registry,
                              model=cfg.model_name)
        self.requests_ok = 0
        self.requests_failed = 0
        self.batch_log: List[Tuple[int, int]] = []  # (n_real, bucket)
        self._t0 = time.time()
        self._images = 0
        # pre-sized pad buffers: {(bucket, float dtype): {input: zeros
        # host array}} plus the set of inputs a previous batch wrote real
        # rows into (those must be re-zeroed before a batch that doesn't
        # carry them). Keyed by DTYPE as well as bucket: the quantized
        # path fills bf16 activation buffers, and those must never alias
        # the f32 buffers a non-quant forward of the same bucket owns.
        self._bucket_buf: Dict[tuple, Dict[str, np.ndarray]] = {}
        self._bucket_dirty: Dict[tuple, set] = {}
        # router integration: exactly one thread may drive serve_tick at
        # a time (the lane's own worker, or one pool thread)
        self.lane_lock = threading.Lock()
        # periodic-duty cadence: the worker must surface at least this
        # often for reload polls / heartbeats / liveness ticks even with
        # an empty queue. Bounded by 1 s so /healthz freshness works.
        duties = [1.0]
        if cfg.checkpoint_dir:
            duties.append(cfg.poll_interval_s)
        if hb is not None:
            duties.append(cfg.heartbeat_every_s)
        self._duty_s = max(min(duties), 1e-3)
        self._worker: Optional[threading.Thread] = None
        self._http = None
        # SLO ledger handles (started with the server when cfg.history)
        self.history = None
        self.alerter = None
        # per-example input schema, resolved lazily at the submit door
        # (shape validation); None until the first submit
        self._input_specs = None
        self._running = False
        self._last_tick = 0.0

    # -- client API ----------------------------------------------------------

    def submit(self, payload: Dict[str, Any],
               deadline_s: Optional[float] = None,
               priority: Optional[str] = None,
               outputs: Optional[Tuple[str, ...]] = None,
               trace=None):
        """Enqueue one example (dict of per-example arrays); returns a
        Future resolving to {blob name: per-example array}. `deadline_s`
        threads the client's answer-by bound into batch formation: an
        expired request is shed (DeadlineExpiredError) instead of
        occupying a bucket slot. `outputs` names the blobs THIS request
        wants (the featurizer's embedding route) — validated here
        against the net's blob table because the forward's name filter
        silently drops unknowns, and a typo should be a loud error, not
        an empty response. `priority` tags the queued request so fleet
        signals can tell scavenger backlog from online demand."""
        payload, inline = pop_outputs(payload)
        if outputs is None:
            outputs = inline
        if outputs:
            known = self._known_blobs()
            if known is not None:
                bad = [o for o in outputs if o not in known]
                if bad:
                    raise ValueError(
                        f"unknown output blob(s) {bad!r} "
                        f"(net has {sorted(known)})")
        self._validate_payload(payload)
        return self.batcher.submit(payload, deadline_s=deadline_s,
                                   priority=priority, outputs=outputs,
                                   trace=trace)

    def _validate_payload(self, payload: Dict[str, Any]) -> None:
        """Reject a mis-shaped or unknown-field example AT THE DOOR with
        a ValueError (the frontends' typed-400 ladder), before it can
        enter a batch. Previously a wrong per-example shape survived to
        the pre-sized pad path, where `np.stack(rows, out=buf[:n])` blew
        up the WHOLE signature group with an opaque "Output array is the
        wrong shape" — a client bug surfacing as a server-side 500.
        Skipped when a preprocessor is configured: raw pixel shapes
        legitimately differ from the net's input schema until decode."""
        if self.preprocessor is not None:
            return
        specs = self._input_specs
        if specs is None:
            try:
                specs = net_input_specs(self.net)
            except Exception:
                specs = {}  # net without introspection: can't validate
            self._input_specs = specs
        if not specs:
            return
        for k, v in payload.items():
            spec = specs.get(k)
            if spec is None:
                raise ValueError(
                    f"request field {k!r} is not a net input "
                    f"(net has {sorted(specs)})")
            shape = tuple(np.shape(v))
            if shape != spec[0]:
                raise ValueError(
                    f"request field {k!r} has per-example shape "
                    f"{shape}, net input wants {spec[0]}")

    def _known_blobs(self) -> Optional[set]:
        """The net's nameable blobs, or None when the backend can't
        enumerate them (then unknown names fall back to the forward's
        silent-drop behavior)."""
        inner = getattr(self.net, "net", None)
        shapes = getattr(inner, "blob_shapes", None)
        if isinstance(shapes, dict) and shapes:
            return set(shapes)
        return None

    def infer(self, payload: Dict[str, Any], timeout: float = 30.0
              ) -> Dict[str, np.ndarray]:
        """Synchronous convenience wrapper over submit(). The timeout IS
        the request deadline: a request this client will have abandoned
        is shed from the queue (DeadlineExpiredError) rather than riding
        a bucket slot to produce an answer nobody reads. The wait itself
        gets a small grace past the deadline so the shed lands as its
        honest exception — worker truly wedged, a bare futures
        TimeoutError still bounds the hang."""
        fut = self.submit(payload, deadline_s=timeout)
        return fut.result(timeout=timeout + 5.0)

    # -- lifecycle -----------------------------------------------------------

    def start(self, thread: bool = True) -> "InferenceServer":
        """Load initial weights and begin serving. `thread=False` skips
        spawning the lane's own worker (router mode: the ModelRouter's
        shared pool drives `serve_tick` instead)."""
        assert self._worker is None and not self._running, "already started"
        self.manager.load_initial()
        self._running = True
        self._last_tick = time.monotonic()
        if thread:
            self._worker = threading.Thread(target=self._run,
                                            name="serve-worker",
                                            daemon=True)
            self._worker.start()
        if self.cfg.status_port is not None:
            self._start_http(self.cfg.status_port)
        if self.cfg.history:
            self._start_history()
        return self

    def _start_history(self) -> None:
        """The SLO ledger: history sampler (+ alerter when an objective
        is declared), attached to the status server when one is up."""
        from ..obs.history import HistoryConfig, MetricsHistory
        from ..obs.slo import SloSpec, BurnRateAlerter
        self.history = MetricsHistory(
            self.registry,
            HistoryConfig(sample_interval_s=self.cfg.history_interval_s,
                          persist_dir=self.cfg.history_dir),
            logger=self.log)
        spec = self.cfg.slo_spec
        if spec is None and (self.cfg.slo_p99_ms is not None
                             or self.cfg.slo_availability is not None):
            spec = SloSpec(model=self.model_name,
                           latency_ms=self.cfg.slo_p99_ms,
                           availability=self.cfg.slo_availability)
        if spec is not None:
            self.alerter = BurnRateAlerter(self.history, [spec],
                                           logger=self.log).attach()
        if self._http is not None:
            self.history.attach_http(self._http)
            if self.alerter is not None:
                self.alerter.attach_http(self._http)
        self.history.start()

    def stop(self, drain_s: float = 5.0) -> None:
        """Stop accepting work, serve what's already queued (bounded by
        drain_s), then stop the worker."""
        deadline = time.monotonic() + drain_s
        while self.batcher.depth() and time.monotonic() < deadline:
            time.sleep(0.01)
        self._running = False
        self.batcher.close()
        if self._worker is not None:
            self._worker.join(timeout=max(drain_s, 1.0))
            self._worker = None
        # one final metrics row with the worker quiesced: a short-lived
        # server (demo, bench arm) whose traffic never reached the
        # metrics cadence still leaves its batch_size_hist on disk —
        # the --buckets-from input must survive the process
        # (metrics_every_batches=0 keeps meaning "JSONL off")
        if self.log is not None and self.fill.batches and \
                self.cfg.metrics_every_batches:
            self._log_metrics_row()
        if self.history is not None:
            self.history.stop()
            self.history = None
            self.alerter = None
        if self._http is not None:
            self._http.stop()
            self._http = None
        if self.heartbeat is not None:
            try:
                self.heartbeat.beat(self.manager.step or 0, status="done",
                                    rollbacks=self.manager.swap_failures,
                                    force=True)
            except OSError:
                pass

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- status --------------------------------------------------------------

    def status(self) -> Dict[str, Any]:
        """The /status JSON: serving vitals in one flat dict. Every field
        comes from a locked snapshot (FillMeter.snapshot, LatencyStats.
        summary) or a single-writer attribute — the HTTP thread reading
        while the worker mutates sees one consistent moment, not a mix."""
        dt = max(time.time() - self._t0, 1e-9)
        m = self.manager
        real, padded, batches = self.fill.snapshot()
        out = {
            "role": "serve",
            "model": self.model_name,
            "uptime_s": round(dt, 1),
            "queue_depth": self.batcher.depth(),
            "requests_ok": self.requests_ok,
            "requests_failed": self.requests_failed,
            "requests_shed": self.batcher.shed,
            "images_per_sec": round(self._images / dt, 2),
            "batches": batches,
            "batch_fill_ratio": round(real / padded if padded else 0.0, 4),
            "buckets": list(self.buckets),
            "bucket_compiles": len(self._compiled_buckets),
            # formed-batch size distribution (string keys: JSON object),
            # the input `serve.buckets.derive_buckets` fits a ladder to
            "batch_size_hist": {str(s): c for s, c
                                in sorted(self.fill.size_hist().items())},
            "quant": None if self.quant is None else self.quant.mode,
            "model_step": m.step,
            "replica": m.replica,
            # train->serve freshness: age of the serving step's commit
            # (None until a commit_ts-stamped checkpoint installs) and
            # how many committed steps this replica trails by.
            # _log_metrics_row lifts the numeric fields into the JSONL
            # stream, which is what the sparknet-metrics freshness view
            # aggregates.
            "freshness_s": m.freshness_s(),
            "model_step_lag": m.step_lag(),
            "latest_step_seen": m.latest_seen,
            "swaps": m.swaps,
            "swap_failures": m.swap_failures,
            "last_error": m.last_error,
        }
        if self.cfg.slo_p99_ms is not None:
            out["slo_p99_ms"] = self.cfg.slo_p99_ms
        if self.alerter is not None:
            # the ledger's live slice: firing alerts + budget left
            out["slo"] = self.alerter.summary()
        out.update(self.latency.summary())
        # recent worst captured requests (trace_id, total ms, dominant
        # stage): "p99 is burning" -> the exact trace in two steps. Reads
        # a locked snapshot; absent entirely when tracing is off.
        rt = reqtrace.active()
        if rt is not None:
            ex = rt.exemplars().get(self.model_name)
            if ex:
                out["slow_requests"] = ex
            out["reqtrace"] = rt.stats()
        # per-model rows for the pod view (PodAggregator._collect_http
        # lifts this into WorkerView.models; the router emits one row per
        # lane here, a single-model server exactly one)
        out["models"] = {self.model_name: self.model_row()}
        return out

    def reset_counters(self) -> None:
        """Zero the windowed serving metrics (latency, fill, throughput
        clock) — between load levels in a bench, or after warmup. Model/
        request totals (swaps, requests_ok) are lifetime counters and
        keep counting."""
        self.latency.reset()
        self.fill.reset()
        self._images = 0
        self._t0 = time.time()

    def healthy(self) -> bool:
        """Liveness: the serving thread (own worker, or the router pool)
        ticked recently (a wedged forward or a dead thread must flip
        /healthz to 503, not hang it)."""
        alive = (self._worker.is_alive() if self._worker is not None
                 else self._running)
        fresh = (time.monotonic() - self._last_tick) < max(
            3 * self._duty_s, 10 * self.cfg.idle_poll_s, 2.0)
        return alive and fresh

    # -- worker loop ---------------------------------------------------------

    def _run(self) -> None:
        while self._running:
            with self.lane_lock:
                self.serve_tick()

    def serve_tick(self, wake_at: Optional[float] = None) -> bool:
        """One worker iteration: park for a batch (wake-on-submit; surface
        at `wake_at` — default: now + the duty cadence — for periodic
        duties), serve it, then run duties. Callers other than the lane's
        own thread MUST hold `lane_lock`. Returns True when a batch was
        served (the router's pool uses this to distinguish progress from
        an idle tick)."""
        self._last_tick = time.monotonic()
        if wake_at is None:
            wake_at = time.perf_counter() + self._duty_s
        reqs = self.batcher.next_batch(wake_at=wake_at)
        if reqs:
            # a formed batch has already waited out its deadline:
            # serve it FIRST — a multi-second checkpoint download
            # must never sit between batch formation and its forward
            self._serve_batch(reqs)
        self.duty_tick()
        return bool(reqs)

    def duty_tick(self) -> None:
        """Hot-reload + heartbeat: ride the gaps AFTER serving / on idle
        ticks — a swap never interleaves with a forward (single driving
        thread per lane), and NOTHING the poll raises may kill that
        thread: a dead worker strands every queued future while submit()
        keeps accepting work."""
        self._last_tick = time.monotonic()
        try:
            self.manager.poll()
        except Exception as e:
            self.manager.last_error = f"poll: {e}"
            self._log(f"serve: reload poll crashed ({e}); serving "
                      f"continues on step {self.manager.step}")
        self._beat()

    def _beat(self) -> None:
        if self.heartbeat is None:
            return
        try:
            self.heartbeat.beat(
                self.manager.step or 0,
                status="degraded" if self.manager.last_error else "ok",
                rollbacks=self.manager.swap_failures,
                queue_depth=self.batcher.depth(),
                batch_fill=round(self.fill.ratio(), 4),
                models={self.model_name: self.model_row()})
        except OSError:
            pass  # observability must not take serving down

    def model_row(self) -> Dict[str, Any]:
        """The compact per-model vitals row (heartbeats, /pod/status):
        enough for `sparknet-podview` to attribute per-model stragglers
        without shipping the whole status dict."""
        lat = self.latency.summary()
        row = {"step": self.manager.step,
                # staleness without a /metrics scrape: the rollout duty
                # reads adoption (model_step) from heartbeat rows, and
                # sparknet-podview renders freshness per replica
                "model_step": self.manager.step,
                "freshness_s": self.manager.freshness_s(),
                "step_lag": self.manager.step_lag(),
                "queue_depth": self.batcher.depth(),
                "requests_ok": self.requests_ok,
                "requests_failed": self.requests_failed,
                "requests_shed": self.batcher.shed,
                "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
                "batch_fill": round(self.fill.ratio(), 4),
                "recent_occupancy": self.fill_signal(),
                "swaps": self.manager.swaps,
                "swap_failures": self.manager.swap_failures}
        rt = reqtrace.active()
        if rt is not None:
            worst = rt.worst(self.model_name)
            if worst is not None:
                row["slow_request"] = worst
        if self.alerter is not None:
            s = self.alerter.summary()
            # a router-shared alerter carries every lane's alerts: keep
            # only THIS model's on its row
            row["slo_firing"] = [
                f for f in s["firing"]
                if f.startswith(f"{self.model_name}:")]
            br = s["budget_remaining"].get(self.model_name)
            if br is not None:
                row["slo_budget_remaining"] = round(br, 4)
        return row

    def fill_signal(self) -> Optional[float]:
        """Recent batch occupancy vs max_batch in [0,1] (None until a
        batch forms) — the router's coalesced-formation trigger. NOT
        bucket-relative fill: a fragmented trickle pads into bucket 1
        at fill 1.0, while its occupancy is 1/max_batch."""
        occ = self.fill.recent_occupancy(self.cfg.max_batch)
        return None if occ is None else round(occ, 4)

    def _serve_batch(self, reqs: List[ServeRequest]) -> None:
        # heterogeneous traffic: group by input signature so one
        # mis-shaped request fails ITS group, not the whole batch (and
        # stacked arrays are always rectangular)
        groups: Dict[tuple, List[ServeRequest]] = {}
        for r in reqs:
            sig = tuple(sorted((k, v.shape, str(v.dtype))
                               for k, v in r.payload.items()))
            groups.setdefault(sig, []).append(r)
        for group in groups.values():
            self._forward_group(group)

    def _forward_group(self, reqs: List[ServeRequest]) -> None:
        with obs_trace.span("forward", n=len(reqs)):
            self._forward_group_inner(reqs)

    @staticmethod
    def _wire_dtype(v):
        """bf16 blobs (the quantized forward's outputs) -> f32 for the
        response; everything else passes through untouched."""
        arr = np.asarray(v)
        if str(arr.dtype) == "bfloat16":
            return arr.astype(np.float32)
        return arr

    def _bucket_batch(self, reqs: List[ServeRequest], bucket: int
                      ) -> Dict[str, np.ndarray]:
        """Fill this bucket's cached buffers with the group's rows: one
        pre-sized buffer per input, request rows stacked straight into
        it, the pad tail re-zeroed. Inputs absent from the request stay
        zero (re-zeroed only when a previous batch dirtied them)."""
        n = len(reqs)
        key = (bucket, str(self._float_dtype))
        buf = self._bucket_buf.get(key)
        if buf is None:
            buf = self._bucket_buf[key] = zeros_batch(
                self.net, bucket, float_dtype=self._float_dtype)
            self._bucket_dirty[key] = set()
        payload = reqs[0].payload
        if self.preprocessor is not None:
            # batch-level decode, eval semantics: center crop + mean
            # subtract are deterministic, so per-request and batched
            # decode agree (the parity test's precondition)
            payload = self.preprocessor.convert_batch(
                {k: np.stack([r.payload[k] for r in reqs])
                 for k in payload}, train=False)
        dirty = self._bucket_dirty[key]
        for k in dirty - set(payload):
            buf[k][:] = 0  # stale rows from a batch that carried k
        dirty.intersection_update(payload)
        for k in payload:
            dst = buf.get(k)
            if dst is None:
                raise ValueError(
                    f"request field {k!r} is not a net input "
                    f"(net has {sorted(buf)})")
            if self.preprocessor is not None:
                dst[:n] = payload[k]
            else:
                rows = [r.payload[k] for r in reqs]
                try:
                    np.stack(rows, out=dst[:n])
                except TypeError:
                    # unusual-dtype payload (e.g. int rows for a float
                    # input): stack on the side, let assignment cast —
                    # the slow path the old concat always paid
                    dst[:n] = np.stack(rows)
                except ValueError as e:
                    # belt-and-suspenders: the submit door validates
                    # shapes, so this is only reachable for payloads
                    # that bypassed it — name the field and the schema
                    # instead of numpy's bare "Output array is the
                    # wrong shape"
                    raise ValueError(
                        f"request field {k!r} rows (shape "
                        f"{np.shape(rows[0])}) do not match net input "
                        f"shape {dst.shape[1:]}") from e
            dst[n:] = 0
            dirty.add(k)
        return buf

    def _forward_group_inner(self, reqs: List[ServeRequest]) -> None:
        n = len(reqs)
        bucket = next(b for b in self.buckets if b >= n)
        # queue-wait: submit -> forward start, stamped on each future so
        # the frontends can surface it on the wire (RESPONSE meta /
        # X-Queue-Wait-Ms) — the split that tells a hedging tuner
        # whether the tail is queueing or compute
        t_form = time.perf_counter()
        for r in reqs:
            r.future._spkn_queue_wait_s = t_form - r.t_enqueue
        # distributed-trace stages: one global None-check when tracing is
        # off; per-request rows only for requests carrying a context.
        # bucket/batch_n attrs are SHARED by every coalesced request in
        # the group — the trace shows who a request formed with.
        rt = reqtrace.active()
        traced = ([r for r in reqs if r.trace is not None]
                  if rt is not None else ())
        for r in traced:
            rt.stage(r.trace, "queue", rt.to_us(r.t_enqueue),
                     (t_form - r.t_enqueue) * 1e6,
                     bucket=bucket, batch_n=n)
        try:
            full = self._bucket_batch(reqs, bucket)
            # per-request named blobs (the featurizer route) widen the
            # forward's fetch set; each request still receives only the
            # names IT asked for below
            extra = set()
            for r in reqs:
                if r.outputs:
                    extra.update(r.outputs)
            t_fwd0 = time.perf_counter()
            for r in traced:
                rt.stage(r.trace, "form", rt.to_us(t_form),
                         (t_fwd0 - t_form) * 1e6,
                         bucket=bucket, batch_n=n)
            with track_compiles() as tc:
                out = self.net.forward(
                    full,
                    blob_names=list(set(self.cfg.outputs or ()) | extra))
            t_fwd1 = time.perf_counter()
            if bucket not in self._compiled_buckets:
                # this forward traced+compiled the bucket's executable;
                # cache_hit says whether the persistent compile cache
                # served it (warm replica cold-start) or XLA built it
                # fresh (utils/compile_cache.py region verdict)
                self._compiled_buckets.add(bucket)
                dt = time.perf_counter() - t_fwd0
                self._c_bucket_compiles.inc(model=self.model_name)
                self._h_bucket_compile.observe(dt, model=self.model_name)
                obs_device.note_compile("serve_bucket", dt,
                                        cache_hit=tc.cache_hit)
            # de-pad: slice each request's own row out of per-row blobs;
            # batch-AGGREGATE blobs (the zoo heads' scalar loss/accuracy
            # — averaged over padding, meaningless per request) are
            # dropped unless cfg.outputs names them explicitly
            want = set(self.cfg.outputs) if self.cfg.outputs else None
            # responses are always f32 on the wire: the quantized path
            # computes in bf16, but npz does not round-trip bf16 and
            # clients should not need ml_dtypes to read a probability
            fields = [(k, self._wire_dtype(v), getattr(v, "ndim", 0) >= 1
                       and v.shape[0] == bucket)
                      for k, v in out.items()]
            # lane defaults: cfg.outputs if configured, else every
            # per-row blob — exactly the pre-outputs-route contract
            if want is not None:
                default = [f for f in fields if f[0] in want]
            else:
                default = [f for f in fields if f[2]]
            now = time.perf_counter()
            # emitted BEFORE set_result: resolving the future runs the
            # frontend's completion callback, which finishes the trace
            # record and drains this request's parked spans
            for r in traced:
                rt.stage(r.trace, "forward", rt.to_us(t_fwd0),
                         (t_fwd1 - t_fwd0) * 1e6,
                         bucket=bucket, batch_n=n)
                rt.stage(r.trace, "depad", rt.to_us(t_fwd1),
                         (now - t_fwd1) * 1e6)
            for i, r in enumerate(reqs):
                sel = ([f for f in fields if f[0] in r.outputs]
                       if r.outputs else default)
                r.future.set_result({k: (v[i] if per_row else v)
                                     for k, v, per_row in sel})
                self.latency.add(now - r.t_enqueue)
            self.requests_ok += n
            self._c_requests.inc(n, model=self.model_name, outcome="ok")
        except Exception as e:
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            self.requests_failed += n
            self._c_requests.inc(n, model=self.model_name,
                                 outcome="failed")
            self._log(f"serve: batch of {n} failed: {e}")
        self._images += n
        self.fill.add(n, bucket)
        self.batch_log.append((n, bucket))
        if len(self.batch_log) > 10000:
            del self.batch_log[:5000]
        if self.cfg.metrics_every_batches and self.log is not None and \
                self.fill.batches % self.cfg.metrics_every_batches == 0:
            self._log_metrics_row()

    def _log_metrics_row(self) -> None:
        st = self.status()
        self.log.metrics(self.fill.batches, model=self.model_name,
                         # cumulative; offline readers (sparknet-metrics,
                         # --buckets-from) take the LAST row per model
                         batch_size_hist=st["batch_size_hist"], **{
                             k: v for k, v in st.items()
                             if isinstance(v, (int, float))
                             and v is not None})

    def _log(self, msg: str) -> None:
        if self.log is not None:
            self.log.log(msg)

    # -- status HTTP (shared obs.StatusServer) -------------------------------

    @property
    def status_address(self) -> Optional[Tuple[str, int]]:
        """(host, port) of the status HTTP server, once started."""
        return None if self._http is None else self._http.address

    def _start_http(self, port: int) -> None:
        # the SAME server class the training process runs: /metrics is
        # Prometheus text from the shared registry (one metric-name
        # schema for both roles); the old JSON vitals live at /status
        self._http = StatusServer(
            port, self.registry, host=self.cfg.status_host,
            healthz=lambda: (self.healthy(),
                             {"model_step": self.manager.step,
                              "queue_depth": self.batcher.depth()}),
            status=self.status)
        self._log(f"serve: status at http://{self._http.address[0]}:"
                  f"{self._http.address[1]}/healthz")
