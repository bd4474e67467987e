"""`sparknet-serve` — the console entry point for the inference server.

Builds a net (zoo name, .prototxt path, or an imported serialized graph —
the same three model sources the training apps accept), optionally loads a
weights file, starts the dynamic-batching server with checkpoint
hot-reload, and serves until interrupted. `--http-port` additionally opens
the HTTP/1.1 inference data plane (`serve/http_frontend.py` wire format);
`--models` switches to MULTI-MODEL mode — a `ModelRouter` serving several
zoo/prototxt models over one shared worker pool, each hot-reloading its
own checkpoint dir. `--demo N` instead self-drives N synthetic requests
through the full submit->batch->forward->depad path and prints the status
JSON — the zero-infrastructure smoke ("does this model serve?") and what
the tests exercise.

`--autoscale` (with `--models`) additionally runs the fleet control
plane (`sparknet_tpu.fleet`): SLO-burn-driven admission pressure plus
replica grow/retire through the subprocess provider.

Examples:
    sparknet-serve --model lenet --checkpoint-dir gs://bkt/run1/ck \
        --outputs prob --max-batch 32 --max-wait-ms 5 --http-port 8000 \
        --status-port 8080
    sparknet-serve --models mnist=lenet,cifar=cifar10_quick \
        --router-workers 4 --http-port 8000 --demo 16
    sparknet-serve --models mnist=lenet --binary-port 9000 \
        --slo-p99-ms 50 --autoscale --fleet-max 4 --tenant-rate 100
    sparknet-serve --model net.prototxt --weights w.caffemodel \
        --crop 227 --demo 64
    sparknet-serve --graph model.pb --weights w.npz --outputs fc7 --demo 8
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from typing import Optional

import numpy as np

from ..net_api import JaxNet
from ..utils.config import RunConfig
from ..utils.logger import Logger, default_logger
from .http_frontend import HttpFrontend
from .router import ModelRouter, RouterConfig
from .server import InferenceServer, ServeConfig, net_input_specs


def build_net(model: Optional[str], graph: Optional[str],
              weights: Optional[str], max_batch: int, n_classes: int,
              crop: Optional[int]):
    """The three model sources behind one NetInterface (mirrors
    featurizer_app's split; zoo/prototxt resolution reuses the training
    loop's resolver so the two cannot drift)."""
    if graph:
        from ..backend import GraphNet
        from ..apps.graph_common import load_graph
        net = GraphNet(load_graph(graph, None))
        if weights:
            from ..model.weights import WeightCollection
            net.set_weights(WeightCollection.load(weights))
        return net
    from ..apps.train_loop import resolve_spec
    cfg = RunConfig(model=model or "lenet", local_batch=max_batch,
                    n_classes=n_classes, crop=crop)
    net = JaxNet(resolve_spec(cfg))
    if weights:
        net.load_weights(weights)
    return net


def _demo_payload(net, seed: int = 0) -> dict:
    r = np.random.default_rng(seed)
    specs = net_input_specs(net)
    name, (shape, dtype) = next(
        (k, v) for k, v in specs.items()
        if np.issubdtype(np.dtype(v[1]), np.floating))
    return {name: r.standard_normal(shape).astype(dtype)}


def run_demo(server: InferenceServer, n: int, seed: int = 0) -> dict:
    """Drive n synthetic requests (random pixels in the net's own input
    schema) through the live server and return its status dict."""
    futures = [server.submit(_demo_payload(server.net, seed + i))
               for i in range(n)]
    for f in futures:
        f.result(timeout=60.0)
    return server.status()


def run_router_demo(router: ModelRouter, n: int, seed: int = 0) -> dict:
    """The multi-model smoke: n synthetic requests round-robined across
    every local lane, then the router status."""
    names = sorted(router.lanes)
    futures = [router.submit(
        names[i % len(names)],
        _demo_payload(router.lanes[names[i % len(names)]].net, seed + i))
        for i in range(n)]
    for f in futures:
        f.result(timeout=60.0)
    return router.status()


def parse_models_arg(spec: str):
    """--models 'name=zoo_or_prototxt[,name=...]' -> [(name, source)]."""
    out = []
    for part in spec.split(","):
        name, sep, src = part.partition("=")
        if not sep or not name or not src:
            raise SystemExit(f"--models entry {part!r} is not "
                             f"name=model_source")
        out.append((name.strip(), src.strip()))
    return out


def parse_weights_arg(spec: Optional[str]) -> dict:
    """--tenant-weights 'tenant=weight[,...]' -> {tenant: float}."""
    out = {}
    for part in (spec or "").split(","):
        if not part:
            continue
        name, sep, w = part.partition("=")
        try:
            out[name.strip()] = float(w)
        except ValueError:
            sep = ""
        if not sep or not name:
            raise SystemExit(f"--tenant-weights entry {part!r} is not "
                             f"tenant=weight")
    return out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="lenet",
                   help="zoo builder name or .prototxt path")
    p.add_argument("--model-name", default="default",
                   help="serving name for --model (metric label + "
                   "/v1/models/<name>/infer route)")
    p.add_argument("--models", default=None, metavar="N=SRC[,N=SRC...]",
                   help="multi-model mode: comma-separated name=source "
                   "pairs served by one ModelRouter over a shared pool "
                   "(sources are zoo names / .prototxt paths)")
    p.add_argument("--router-workers", type=int, default=2,
                   help="shared pool threads in --models mode")
    p.add_argument("--graph", help="serialized graph (.pb/.json) instead "
                   "of --model")
    p.add_argument("--weights", help="initial weights (.npz/.caffemodel)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="watch this train-checkpoint dir (local or "
                   "gs://|s3://) and hot-swap verified new steps. In "
                   "--models mode: a template with {model} substituted, "
                   "e.g. gs://bkt/runs/{model}/ck")
    p.add_argument("--poll-interval", type=float, default=2.0,
                   help="seconds between checkpoint-dir polls")
    p.add_argument("--poll-jitter", type=float, default=0.1,
                   help="± fraction of --poll-interval each poll "
                   "deadline is jittered by (de-synchronizes a fleet of "
                   "replicas watching one bucket; default 0.1)")
    p.add_argument("--replica-name", default="local",
                   help="fleet identity: the rollout-gate key and the "
                   "replica label on freshness gauges (providers pass "
                   "their tag)")
    p.add_argument("--rollout-gate", default=None, metavar="PATH",
                   help="obey the fleet rollout duty's ROLLOUT.json at "
                   "this path (local or gs://|s3://): only adopt "
                   "checkpoint steps approved for --replica-name. In "
                   "--models mode: a {model} template. Missing gate = "
                   "ungated polling")
    p.add_argument("--n-classes", type=int, default=10)
    p.add_argument("--crop", type=int, default=None)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="advisory per-model p99 objective (stamped into "
                   "/status and BENCH_SERVE rows); with --history it "
                   "becomes a LIVE latency SLO the burn-rate alerter "
                   "pages on")
    p.add_argument("--slo-availability", type=float, default=None,
                   metavar="FRAC",
                   help="availability objective (e.g. 0.999) evaluated "
                   "by the burn-rate alerter (needs --history)")
    p.add_argument("--history", action="store_true",
                   help="run the SLO ledger: metrics-history sampler "
                   "(multi-resolution rings, /timeseries route) and — "
                   "when an objective is declared — the burn-rate "
                   "alerter (/slo/status, fleet page escalation)")
    p.add_argument("--history-dir", default=None, metavar="DIR",
                   help="persist append-only history shards here "
                   "(`sparknet-slo DIR` builds retrospective reports)")
    p.add_argument("--buckets", default=None,
                   help="comma-separated batch buckets (default: powers "
                   "of 2 up to max-batch)")
    p.add_argument("--buckets-from", default=None, metavar="JSONL",
                   nargs="+",
                   help="derive the bucket ladder from recorded serve "
                   "metrics JSONL(s) (batch_size_hist rows) instead of "
                   "pow2: the ladder minimizing padded slots for the "
                   "traffic the files observed (per model name when the "
                   "rows carry one)")
    p.add_argument("--buckets-k", type=int, default=4,
                   help="max rungs for --buckets-from ladders (compiled "
                   "forwards per model; default 4)")
    p.add_argument("--quant", default=None, choices=("int8",),
                   help="weight-only quantized serving: int8 per-channel "
                   "weights + bf16 activations, parity-gated against the "
                   "f32 forward at every checkpoint load")
    p.add_argument("--quant-tol", type=float, default=None,
                   help="override the quant parity tolerance (sets both "
                   "rtol and atol of the load-time allclose gate)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent XLA compile-cache directory — warm "
                   "replica cold-starts skip every bucket compile. "
                   "IGNORED where $JAX_COMPILATION_CACHE_DIR is set "
                   "(that directory is the cache); default "
                   "<checkout>/.cache/jax")
    p.add_argument("--outputs", default=None,
                   help="comma-separated blob names to return "
                   "(default: the net's output schema)")
    p.add_argument("--no-canary", action="store_true",
                   help="skip the nonfinite canary forward on hot swaps")
    p.add_argument("--http-port", type=int, default=None,
                   help="serve the HTTP/1.1 inference data plane "
                   "(/v1/infer, /v1/models/<m>/infer) on this port "
                   "(0 = ephemeral)")
    p.add_argument("--http-host", default="127.0.0.1",
                   help='bind host for --http-port ("0.0.0.0" for '
                   "cross-host clients)")
    p.add_argument("--binary-port", type=int, default=None,
                   help="serve the binary frame data plane (length-"
                   "prefixed tensor frames over a selectors event loop; "
                   "serve/wire.py format) on this port (0 = ephemeral)")
    p.add_argument("--binary-host", default="127.0.0.1",
                   help='bind host for --binary-port ("0.0.0.0" for '
                   "cross-host clients)")
    p.add_argument("--no-shm", action="store_true",
                   help="disable the spkn-shm shared-memory transport "
                   "on the binary frontend (same-host peers then send "
                   "tensor payloads inline over the socket)")
    p.add_argument("--request-journal", default=None, metavar="PATH",
                   help="journal every data-plane request as JSONL "
                   "(ts, model, tenant, priority, tensor sizes, "
                   "deadline_ms, transport) — the raw material for "
                   "trace-replay benchmarks; off by default")
    p.add_argument("--hedge", action="store_true",
                   help="hedge slow requests (--models router only): "
                   "after an adaptive delay (the model's live routed-"
                   "latency quantile) re-issue an in-flight request to "
                   "a second healthy replica; first answer wins, the "
                   "loser is cancelled best-effort")
    p.add_argument("--hedge-budget", type=float, default=0.05,
                   help="max fraction of routed requests that may "
                   "hedge (default 0.05); hedging also disables "
                   "itself under admission pressure")
    p.add_argument("--coalesce", action="store_true",
                   help="coalesced batch formation (--models router "
                   "only): when every replica of a model reports "
                   "under-filled batches, focus consecutive requests "
                   "on ONE replica per formation window (rotating for "
                   "fairness) so batches actually fill")
    p.add_argument("--io-threads", type=int, default=2,
                   help="event-loop io threads for --binary-port")
    p.add_argument("--tenant-rate", type=float, default=None,
                   help="per-tenant admission: token-bucket refill rate "
                   "(requests/sec) keyed on the X-Tenant header / "
                   "binary-frame tenant field, shed 429 "
                   "error_kind=tenant_limit ahead of the queue; shared "
                   "across both data planes (default: off)")
    p.add_argument("--tenant-burst", type=float, default=None,
                   help="per-tenant bucket depth for --tenant-rate "
                   "(default: 2x the rate)")
    p.add_argument("--tenant-weights", default=None,
                   metavar="T=W[,T=W...]",
                   help="per-tenant budget weights for --tenant-rate "
                   "(scales that tenant's rate AND burst; unnamed "
                   "tenants get weight 1)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the fleet control plane (sparknet_tpu."
                   "fleet): per-model SLO burn (windowed p99 vs "
                   "--slo-p99-ms) + queue/shed pressure drive admission "
                   "tightening (low priority sheds first), replica "
                   "grow/retire through the subprocess provider, and "
                   "shared-pool resizing. Requires --models (the "
                   "controller acts on a ModelRouter)")
    p.add_argument("--fleet-min", type=int, default=1,
                   help="min replicas per model for --autoscale "
                   "(local lane included; default 1)")
    p.add_argument("--fleet-max", type=int, default=4,
                   help="max replicas per model for --autoscale "
                   "(default 4)")
    p.add_argument("--fleet-interval", type=float, default=1.0,
                   help="control-loop cadence seconds (default 1.0)")
    p.add_argument("--fleet-window", type=float, default=30.0,
                   help="sliding window seconds for the SLO-burn p99 "
                   "(default 30)")
    p.add_argument("--fleet-provider", default="subprocess",
                   choices=("subprocess", "none"),
                   help="where grown replicas come from: 'subprocess' "
                   "spawns sparknet-serve children over spkn:// on "
                   "this host; 'none' keeps only the admission + pool "
                   "levers")
    p.add_argument("--pool-max", type=int, default=None,
                   help="with --autoscale: let the controller grow the "
                   "router's shared worker pool up to this many "
                   "threads (default: --router-workers, i.e. lever "
                   "off)")
    p.add_argument("--heartbeat-every", type=float, default=10.0,
                   help="seconds between heartbeat writes for "
                   "--heartbeat (fleet children beat fast so the "
                   "staleness rule sees a kill promptly)")
    p.add_argument("--status-port", type=int, default=None,
                   help="serve /healthz and /metrics on this port "
                   "(0 = ephemeral)")
    p.add_argument("--heartbeat", default=None,
                   help="write the utils/heartbeat.py liveness file here")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="capture host-side spans (serve worker lane: "
                   "forwards, hot swaps) as Chrome-trace-event JSON — "
                   "merges on one timeline with a trainer's --trace-out")
    p.add_argument("--request-trace", default=None, metavar="DIR",
                   help="distributed per-REQUEST tracing: capture "
                   "tail-sampled request spans (admission, queue, batch "
                   "formation, forward, wire hops) as JSONL shards in "
                   "DIR; every shed/error and everything beyond the "
                   "live p95 is kept. Assemble shards from all "
                   "processes with `sparknet-trace DIR ...`")
    p.add_argument("--trace-head-sample", type=float, default=0.01,
                   metavar="P",
                   help="with --request-trace: ALSO head-sample this "
                   "fraction of ordinary requests (default 0.01) so "
                   "healthy-path traces exist to compare tails against")
    p.add_argument("--workdir", default=None,
                   help="log/JSONL directory (default $SPARKNET_TPU_HOME)")
    p.add_argument("--demo", type=int, default=None, metavar="N",
                   help="self-drive N synthetic requests, print status "
                   "JSON, exit (smoke mode)")
    args = p.parse_args(argv)

    log = default_logger(args.workdir, name="serving")
    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)
    derived: dict = {}
    if args.buckets_from:
        from .buckets import derive_buckets, size_hist_from_jsonl
        hists = size_hist_from_jsonl(args.buckets_from)
        merged: dict = {}
        for h in hists.values():
            for s, n in h.items():
                merged[s] = merged.get(s, 0) + n
        derived = {name: derive_buckets(h, args.max_batch,
                                        k=args.buckets_k)
                   for name, h in hists.items()}
        derived[None] = derive_buckets(merged, args.max_batch,
                                       k=args.buckets_k)
        log.log(f"bucket ladders derived from "
                f"{len(args.buckets_from)} JSONL(s): "
                + "; ".join(f"{n or 'merged'}={list(b)}"
                            for n, b in sorted(
                                derived.items(),
                                key=lambda kv: str(kv[0]))))
    outputs = tuple(args.outputs.split(",")) if args.outputs else None
    if args.quant_tol is not None and not args.quant:
        p.error("--quant-tol requires --quant (no parity gate exists "
                "on the f32 path)")
    quant = args.quant
    if quant and args.quant_tol is not None:
        from ..model.quant import QuantConfig
        quant = QuantConfig(mode=args.quant, rtol=args.quant_tol,
                            atol=args.quant_tol)

    def lane_cfg(name: str, checkpoint_dir: Optional[str]) -> ServeConfig:
        # explicit --buckets wins; then the model's derived ladder, then
        # the merged-traffic ladder, then pow2
        lane_buckets = buckets or derived.get(name) or derived.get(None)
        gate = (args.rollout_gate.replace("{model}", name)
                if args.rollout_gate else None)
        return ServeConfig(
            model_name=name, max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms, buckets=lane_buckets,
            slo_p99_ms=args.slo_p99_ms,
            slo_availability=args.slo_availability, outputs=outputs,
            checkpoint_dir=checkpoint_dir,
            poll_interval_s=args.poll_interval,
            poll_jitter=args.poll_jitter,
            replica_name=args.replica_name, rollout_gate=gate,
            canary=not args.no_canary, quant=quant,
            compile_cache_dir=args.compile_cache)

    from ..obs import trace as obs_trace

    if args.autoscale and not args.models:
        p.error("--autoscale requires --models (the fleet controller "
                "acts on a ModelRouter)")
    if args.tenant_weights and not args.tenant_rate:
        p.error("--tenant-weights requires --tenant-rate (weights "
                "scale the per-tenant budget)")

    # ONE admission door shared by both data planes (a tenant's budget
    # is a property of the tenant, not of the wire it arrived on) AND
    # by the fleet controller (its fast lever sets the pressure). The
    # priority-aware door runs whenever tenant budgets or the
    # controller ask for it.
    tenants = None
    if args.tenant_rate or args.autoscale:
        from .admission import PriorityAdmission
        tenants = PriorityAdmission(
            args.tenant_rate, args.tenant_burst,
            weights=parse_weights_arg(args.tenant_weights))

    # request journal (off by default): one JSONL row per data-plane
    # request, shared by both frontends — echo off, this is a data file
    journal = (Logger(jsonl_path=args.request_journal, echo=False)
               if args.request_journal else None)

    def make_frontends(backend):
        """The data planes the flags asked for: HTTP and/or binary."""
        from .binary_frontend import BinaryFrontend
        fes = []
        if args.http_port is not None:
            fes.append(HttpFrontend(backend, args.http_port,
                                    args.http_host, tenants=tenants,
                                    logger=log, journal=journal))
        if args.binary_port is not None:
            fes.append(BinaryFrontend(backend, args.binary_port,
                                      args.binary_host,
                                      io_threads=args.io_threads,
                                      tenants=tenants, logger=log,
                                      enable_shm=not args.no_shm,
                                      journal=journal))
        return fes

    def make_fleet(router, sources):
        """The --autoscale control plane over the router."""
        from ..fleet import (FleetConfig, FleetController,
                             SubprocessReplicaProvider)
        provider = None
        if args.fleet_provider == "subprocess":
            # grown children join the continuous-learning loop: same
            # checkpoint store + rollout gate as the local lanes, each
            # under its own provider tag (--replica-name)
            provider = SubprocessReplicaProvider(
                dict(sources), max_batch=args.max_batch,
                outputs=outputs or ("prob",),
                compile_cache_dir=args.compile_cache,
                checkpoint_dir=args.checkpoint_dir,
                poll_interval_s=args.poll_interval,
                poll_jitter=args.poll_jitter,
                rollout_gate=args.rollout_gate)
        cfg = FleetConfig(interval_s=args.fleet_interval,
                          window_s=args.fleet_window,
                          min_replicas=args.fleet_min,
                          max_replicas=args.fleet_max,
                          pool_max=args.pool_max,
                          slo_p99_ms=args.slo_p99_ms)
        return FleetController(router, provider=provider, cfg=cfg,
                               admission=tenants, logger=log)

    with contextlib.ExitStack() as _traces:
        if args.trace_out:
            _traces.enter_context(obs_trace.tracing(args.trace_out))
        if args.request_trace:
            from ..obs import reqtrace
            _traces.enter_context(reqtrace.request_tracing(
                args.request_trace,
                head_sample=args.trace_head_sample))
        if args.models:
            router = ModelRouter(
                RouterConfig(workers=args.router_workers,
                             status_port=args.status_port,
                             heartbeat_path=args.heartbeat,
                             heartbeat_every_s=args.heartbeat_every,
                             hedge=args.hedge,
                             hedge_budget=args.hedge_budget,
                             coalesce=args.coalesce,
                             history=args.history,
                             history_dir=args.history_dir),
                logger=log)
            if tenants is not None:
                # hedging reads the admission door's pressure: a
                # saturated fleet must not pay for duplicate requests
                router.attach_admission(tenants)
            sources = parse_models_arg(args.models)
            for name, src in sources:
                ck = (args.checkpoint_dir.format(model=name)
                      if args.checkpoint_dir else None)
                router.add_model(
                    name,
                    build_net(src, None, None, args.max_batch,
                              args.n_classes, args.crop),
                    cfg=lane_cfg(name, ck))
            fleet = make_fleet(router, sources) if args.autoscale \
                else None
            with router:
                frontends = make_frontends(router)
                if fleet is not None:
                    if router.alerter is not None:
                        # the ledger's firing pages become the fleet's
                        # fast admission-pressure input
                        fleet.attach_alerter(router.alerter)
                    fleet.start()
                try:
                    _serve_until_done(router.status, args, log,
                                      run_fn=lambda:
                                      run_router_demo(router, args.demo))
                finally:
                    if fleet is not None:
                        fleet.stop()
                    for fe in frontends:
                        fe.stop()
            return

        net = build_net(args.model, args.graph, args.weights,
                        args.max_batch, args.n_classes, args.crop)
        cfg = lane_cfg(args.model_name, args.checkpoint_dir)
        cfg.status_port = args.status_port
        cfg.heartbeat_path = args.heartbeat
        cfg.heartbeat_every_s = args.heartbeat_every
        cfg.history = args.history
        cfg.history_dir = args.history_dir
        server = InferenceServer(net, cfg, logger=log)
        with server:
            frontends = make_frontends(server)
            try:
                _serve_until_done(server.status, args, log,
                                  run_fn=lambda:
                                  run_demo(server, args.demo))
            finally:
                for fe in frontends:
                    fe.stop()


def _serve_until_done(status_fn, args, log: Logger, run_fn) -> None:
    if args.demo is not None:
        print(json.dumps(run_fn()))
        return
    log.log("serving; Ctrl-C to stop")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        log.log("interrupted; draining")
        print(json.dumps(status_fn()), file=sys.stderr)


if __name__ == "__main__":
    main()
