"""Device-level telemetry: HBM occupancy, live arrays, compile events.

Two signals the host-side registry could not see before this module:

  - **Memory.** `Device.memory_stats()` (bytes-in-use / peak / limit per
    accelerator) and the process's live jax array count, exported as
    gauges and sampled at the train loop's `log_every` flush cadence —
    the curve that answers "is this OOM a leak or a step change" without
    attaching a profiler. On backends without allocator stats (CPU
    returns None) the memory gauges simply never appear; the live-array
    gauge always does.

  - **Compiles.** XLA compilation is the serving tail-latency cliff and
    the training warm-up tax. `note_compile(what, seconds, cache_hit=...,
    stages=...)` is the process-wide record: a row of sums a `what`, and
    no list of events. Who writes it: the sink this module registers with
    `utils/compile_cache.py`'s compile log, once for EVERY executable jax
    builds or fetches, with the seconds of its three stages (tracing,
    lowering, and the backend's compile or cache fetch) and the
    persistent cache's verdict, under the jitted function's own name
    where the program named it (`what` = `train_round`, `eval_round`, a
    name given to `register_program`) and under `other` for the rest
    (jax's own small programs, a serve net's forward: executables, where
    `serve_bucket` below counts the regions that needed them);
    `CompiledNet.compile`, for spec compiles (`"net"`); the serve worker,
    for the first forward of each batch bucket (`"serve_bucket"`: the
    region's wall time and verdict).
    `attach_compile_metrics` gives a registry what was recorded so far, as
    `sparknet_compile_events_total{what,cache_hit}` +
    `sparknet_compile_seconds{what}` so a registry created AFTER the
    model was compiled (the train loop's per-run registry) still shows
    the compile that preceded it. Jit-cache CHURN — recompiles past the
    expected steady state — is then a scrapeable number with a time, and
    (`register_program(..., stamp=...)`) with the round that paid it.

    `cache_hit` says whether the event required FRESH XLA compilation:
    "true" = nothing was built from scratch (served from the persistent
    cache via `utils/compile_cache.py`, or a memoized spec compile),
    "false" = at least one executable compiled fresh with the cache absent
    or missing, "unknown" = the verdict doesn't apply (a memo-MISS spec
    compile is pure Python — no XLA to cache — and out-of-tree
    note_compile callers don't sample). A warm replica's cold start
    showing ZERO cache_hit="false" net and bucket events is the BENCH_ECON
    acceptance row; the seconds histogram records non-"true" events only,
    so memo hits never dilute real compile-cost percentiles.

  - **Start-up.** `startup_report()` puts the kept start-up spans
    (`obs.trace.startup_spans()`) and the compile log side by side: what a
    process paid before its first round, step by step. The train loop logs
    it as one line and serves it as `/status` `startup`.

The accumulator is process-global by design (compiles happen before any
registry exists); attached registries are held weakly so per-run/test
registries die normally.
"""
from __future__ import annotations

import math
import re
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ..utils.compile_cache import (compile_log, compile_log_dropped, on_entry,
                                   track_compiles)
from . import trace
from .registry import Metric, MetricsRegistry

#: compile durations span four orders of magnitude: a sub-ms cached spec
#: rebuild to a multi-minute pod-scale XLA compile
COMPILE_BUCKETS = (0.001, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0, 60.0, 120.0, 300.0)

#: the jitted functions the trainers name (`parallel.trainer.named`): a
#: compile of one is counted under its own name, with those a program
#: registered (`register_program`); every other executable jax builds (its
#: own small programs, a serve net's forward, a caller's lambdas) is counted
#: under `OTHER`, so the `what` label stays a handful of values. The compile
#: log keeps each under its own name.
NAMED_PROGRAMS = ("train_round", "eval_round")
OTHER = "other"

_lock = threading.Lock()
#: what -> {"events", "seconds", "cache_hits", "cache_misses", and where
#: events came with stages "trace_s", "lower_s", "backend_s"}: sums, so a
#: process that compiles for days holds a row a `what` and no list
_stats: Dict[str, Dict[str, float]] = {}
#: weakly-held (counter, histogram) pairs of attached registries
_attached: List[Tuple["weakref.ref[Metric]", "weakref.ref[Metric]"]] = []


def _compile_metrics(registry: MetricsRegistry) -> Tuple[Metric, Metric]:
    return (registry.counter("sparknet_compile_events_total",
                             "XLA/spec compile events by site and "
                             "persistent-cache outcome",
                             labels=("what", "cache_hit")),
            registry.histogram("sparknet_compile_seconds",
                               "seconds per FRESH compile event (cache/memo "
                               "hits excluded — real compile cost only)",
                               labels=("what",), buckets=COMPILE_BUCKETS))


#: the process's own copy of the two families, written under `_lock`: what
#: a registry attached later takes over (`Metric.absorb`)
_record = _compile_metrics(MetricsRegistry())


def _hit_label(cache_hit: Optional[bool]) -> str:
    return "unknown" if cache_hit is None else (
        "true" if cache_hit else "false")


def _count(c: Metric, h: Metric, what: str, seconds: float,
           cache_hit: Optional[bool]) -> None:
    c.inc(what=what, cache_hit=_hit_label(cache_hit))
    # the seconds histogram records REAL compile cost only: ~0-second
    # memo/cache-hit events would collapse its percentiles toward zero and
    # blind slow-compile attribution
    if cache_hit is not True:
        h.observe(seconds, what=what)


def note_compile(what: str, seconds: float,
                 cache_hit: Optional[bool] = None,
                 stages: Optional[Dict[str, float]] = None) -> None:
    """Record one compile event (`what` is the site: "net" for
    CompiledNet.compile, "serve_bucket" for a serve bucket's first
    forward, a jitted function's name or `OTHER` for an entry of the
    compile log). `cache_hit` is the persistent-cache verdict for the
    region (see module doc; None = not sampled); `stages` the seconds the
    event spent in each stage, summed a `what` into `compile_stats()`.
    Fans out to every attached registry; never raises."""
    what, seconds = str(what), float(seconds)
    cache_hit = None if cache_hit is None else bool(cache_hit)
    with _lock:
        d = _stats.setdefault(what, {"events": 0, "seconds": 0.0,
                                     "cache_hits": 0, "cache_misses": 0})
        d["events"] += 1
        d["seconds"] += seconds
        if cache_hit is not None:
            d["cache_hits" if cache_hit else "cache_misses"] += 1
        for k, v in (stages or {}).items():
            d[k] = d.get(k, 0.0) + float(v)
        _count(*_record, what, seconds, cache_hit)
        pairs = list(_attached)
    for c_ref, h_ref in pairs:
        c, h = c_ref(), h_ref()
        if c is None or h is None:
            continue
        try:
            _count(c, h, what, seconds, cache_hit)
        except Exception:
            pass  # a dying registry must not break the compile path


def attach_compile_metrics(registry: MetricsRegistry) -> None:
    """Register the compile counter + histogram into `registry`, give them
    what was recorded so far (compiles routinely PRECEDE registry
    creation), and keep feeding it (weakly held) as new events land."""
    c, h = _compile_metrics(registry)
    with _lock:
        c.absorb(_record[0])
        h.absorb(_record[1])
        _attached[:] = [(cr, hr) for cr, hr in _attached
                        if cr() is not None and hr() is not None]
        _attached.append((weakref.ref(c), weakref.ref(h)))


def compile_stats() -> Dict[str, Dict[str, float]]:
    """{what: {"events": n, "seconds": total, "cache_hits": n,
    "cache_misses": n, and for the compile log's entries "trace_s",
    "lower_s", "backend_s": their stages' sums}} — the accumulated record
    (tests, status JSON, the BENCH_ECON cold-start child). Events with an
    unknown verdict count in "events" only."""
    with _lock:
        return {what: dict(d) for what, d in _stats.items()}


def _on_compile_entry(entry: Dict[str, Any]) -> None:
    """The compile log's sink: an entry that closes is stamped with what
    the program registered for its name and counted, under its own name if
    it is one of the program's, else under `OTHER`."""
    what = entry["what"]
    for k, v in compile_stamp(what).items():
        entry.setdefault(k, v)
    stages = {k: entry[k] for k in STAGES}
    note_compile(what if what in NAMED_PROGRAMS or what in _programs
                 else OTHER, sum(stages.values()),
                 cache_hit=entry["cache"] == "hit", stages=stages)


class timed_compile:
    """Context manager stamping its wall time as one compile event, with
    the persistent-cache verdict sampled over the region (this thread's
    entries of the compile log — concurrent lanes' compiles don't
    cross-attribute)."""

    def __init__(self, what: str):
        self.what = what

    def __enter__(self):
        self._track = track_compiles()
        self._track.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._track.__exit__(*exc)
        if exc[0] is None:
            note_compile(self.what, time.perf_counter() - self._t0,
                         cache_hit=self._track.cache_hit)
        return False


# -- start-up's account -------------------------------------------------------

#: the three stages of an entry of the compile log
STAGES = ("trace_s", "lower_s", "backend_s")
#: the kept start-up spans summed under each heading of a start-up's sums
STARTUP_PARTS = {"build": ("resolve_spec", "build_trainer"),
                 "restore": ("restore",), "state": ("state_from_params",)}


def startup_report(until: Optional[float] = None) -> Dict[str, Any]:
    """What this process paid before its first round, and what it compiled
    since: `{"import_t0": the package's first import, "until", "spans": the
    kept start-up spans begun before `until`, "compiles": the compile log's
    entries closed before it, "later_compiles": how many closed since (the
    `"dropped_compiles"` the full log no longer holds among them),
    "newest_compile": the last of those (`what`, `step`, `seconds`,
    `cache`) or None}`; times on `time.perf_counter()`. `until` None (no
    round has completed yet): everything so far is start-up."""
    cut = math.inf if until is None else until
    log, dropped = compile_log(), compile_log_dropped()
    later = [e for e in log if e["t1"] >= cut]
    newest = later[-1] if later else None
    return {
        "import_t0": trace.import_stamp(), "until": until,
        "spans": [s for s in trace.startup_spans() if s["t0"] < cut],
        "compiles": [e for e in log if e["t1"] < cut],
        "later_compiles": 0 if until is None else len(later) + dropped,
        "dropped_compiles": dropped,
        "newest_compile": None if newest is None else {
            "what": newest["what"], "step": newest.get("step"),
            "seconds": sum(newest[k] for k in STAGES),
            "cache": newest["cache"]}}


def startup_sums(spans: List[Dict[str, Any]], compiles: List[Dict[str, Any]],
                 since: float, program: str = "train_round"
                 ) -> Dict[str, Any]:
    """The one arithmetic of a start-up, over kept spans and entries of the
    compile log the caller has cut already (`startup_report()`'s, or the
    benchmark's "before the window"): `import_s` from `since` to the first
    span (None with no span); `build_s`, `restore_s`, `state_s` the spans of
    each heading of `STARTUP_PARTS` that no kept span encloses
    (`trainer_init` is `build_trainer`'s time already, and the state a
    resume builds is `restore`'s); `round`: `program`'s entries, each stage
    summed, with their `entries` and the set of their `cache` verdicts;
    `other`: the `entries` and `seconds` (all three stages) of every other
    one; `cache_misses`: entries whose verdict is not `hit`."""
    first = min((s["t0"] for s in spans), default=None)
    own = [e for e in compiles if e["what"] == program]
    other = [e for e in compiles if e["what"] != program]
    out: Dict[str, Any] = {
        "import_s": None if first is None else first - since}
    for part, names in STARTUP_PARTS.items():
        out[part + "_s"] = sum(s["t1"] - s["t0"] for s in spans
                               if s["parent"] is None and s["name"] in names)
    out["round"] = {**{k: sum(e[k] for e in own) for k in STAGES},
                    "entries": len(own),
                    "cache": sorted({e["cache"] for e in own})}
    out["other"] = {"entries": len(other),
                    "seconds": sum(e[k] for e in other for k in STAGES)}
    out["cache_misses"] = sum(e["cache"] != "hit" for e in compiles)
    return out


def startup_line(report: Dict[str, Any], program: str = "train_round") -> str:
    """`startup_report()` as the one line the train loop logs when its
    first round completes: `start-up: import 14.2 s, build 3.1, restore 0,
    state 1.9, train_round compile 41.0 (trace 9.1, lower 6.3, backend
    25.6, cache miss), 2 other programs 3.3`."""
    sums = startup_sums(report["spans"], report["compiles"],
                        report["import_t0"], program)
    parts = ["import " + ("?" if sums["import_s"] is None
                          else f"{sums['import_s']:.1f} s")]
    parts += [f"{part} {sums[part + '_s']:.1f}" for part in STARTUP_PARTS]
    own = sums["round"]
    if own["entries"]:
        parts.append(
            f"{program} compile {sum(own[k] for k in STAGES):.1f} (trace "
            f"{own['trace_s']:.1f}, lower {own['lower_s']:.1f}, backend "
            f"{own['backend_s']:.1f}, cache {'/'.join(own['cache'])})")
    parts.append(f"{sums['other']['entries']} other programs "
                 f"{sums['other']['seconds']:.1f}")
    return "start-up: " + ", ".join(parts)


#: memory_stats() keys -> gauge name suffix (jaxlib's PJRT spelling; a
#: backend missing a key just skips that gauge)
_MEM_KEYS = (("bytes_in_use", "sparknet_device_hbm_bytes_in_use",
              "allocator bytes currently in use"),
             ("peak_bytes_in_use", "sparknet_device_hbm_peak_bytes",
              "allocator high-water mark"),
             ("bytes_limit", "sparknet_device_hbm_bytes_limit",
              "allocator capacity"))


class DeviceTelemetry:
    """Registers + samples the device gauges. `sample()` is called at the
    train loop's flush cadence (and is safe to call from anywhere): it
    reads `memory_stats()` for every locally-addressable device and
    counts live jax arrays; every failure degrades to a missing sample,
    never an exception — observability must not take training down."""

    def __init__(self, registry: MetricsRegistry, devices=None):
        self.registry = registry
        self._gauges = {name: registry.gauge(name, help_text,
                                             labels=("device",))
                        for _, name, help_text in _MEM_KEYS}
        self._g_live = registry.gauge(
            "sparknet_device_live_arrays",
            "live jax arrays in this process (committed device buffers)")
        if devices is None:
            try:
                import jax
                devices = jax.local_devices()
            except Exception:
                devices = []
        self.devices = list(devices)

    def sample(self) -> None:
        for d in self.devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue  # CPU/backends without allocator stats
            label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            for key, name, _ in _MEM_KEYS:
                v = stats.get(key)
                if v is not None:
                    self._gauges[name].set(float(v), device=label)
        try:
            import jax
            self._g_live.set(float(len(jax.live_arrays())))
        except Exception:
            pass



# -- a compiled program's account of itself ----------------------------------
#
# Every instruction of a compiled module carries the `jax.named_scope`s it
# was traced under as `metadata={op_name="jit(train_round)/.../tau_step/
# transpose(jvp(Convolution/conv1))/dot_general"}`, under the instruction
# name a device trace prints (`%fusion.769`). `program_report(name)` parses
# that text once, on demand, so a reader of a trace can say which layer and
# which part of the step an op belongs to without a model table.

#: name -> zero-argument provider of the program's report (the newest
#: registration: one trainer at most is kept alive by it)
_programs: Dict[str, Any] = {}
#: name -> zero-argument provider of what the compile log stamps on an
#: entry of that name (`{"step": n}`), read when the entry closes
_stamps: Dict[str, Any] = {}

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
#: the target of a Pallas kernel's `custom-call` (the compiler's own,
#: `AllocateBuffer` and the like, carry others)
PALLAS_TARGET = "tpu_custom_call"
_INT_CONSTANT = re.compile(r"\bs32\[\]\S* constant\((\d+)\)")
#: a `gather` whose slices are one element each, or a `scatter` whose updates
#: have no window: single elements fetched, or placed, one an index
_SINGLE = re.compile(r"\bslice_sizes=\{1(?:,1)*\}|\bupdate_window_dims=\{\}")
_CALLED = re.compile(
    r"\b(calls|to_apply|select|scatter|body|condition|branch_computations|"
    r"true_computation|false_computation)=\{?(%?[\w.\-]+(?:,\s*%?[\w.\-]+)*)")
_LAYER = re.compile(r"(?:^|[/(])([A-Z][A-Za-z0-9]*)/([A-Za-z0-9_.\-]+)")
_SHAPE = re.compile(r"\b(pred|bf16|[a-z]\d+\w*)\[([\d,]*)\]")
#: what is no device op of its own, or moves nothing: an `-done` is counted
#: at its `-start`
_NO_BYTES = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "iota", "after-all", "partition-id", "replica-id")
#: instructions that run a computation of their own: their time is their
#: body's, listed instruction by instruction
CONTAINERS = ("while", "conditional", "call")
#: the keys under which an instruction names a computation that is NOT a
#: sequence of device ops of its own (a fusion's body, a reduction's adder)
_INLINED = ("calls", "to_apply", "select", "scatter")
STEP_SCOPE, OPTIMIZER_SCOPE = "tau_step", "solver_update"
PHASES = ("forward", "backward", "optimizer", "outside_step")
#: what jax puts in the path of an op of a recomputation block's forward
#: pass made again for its backward
REMATTED = "rematted_computation"


def _shapes(types: str) -> List[Tuple[int, Tuple[int, ...]]]:
    """[(bytes an element, dimensions)] of every array in a result's type as
    the compiled text prints it (one array, or a tuple of them)."""
    out = []
    for dtype, dims in _SHAPE.findall(types):
        bits = re.search(r"\d+", dtype)
        out.append((max(1, int(bits.group()) // 8) if bits else 1,
                    tuple(int(d) for d in dims.split(",") if d)))
    return out


def _nbytes(shapes) -> int:
    return sum(itemsize * math.prod(dims) for itemsize, dims in shapes)


def register_program(name: str, provider, stamp=None) -> None:
    """Make `program_report(name)` answer from `provider()` — called by a
    trainer when it compiles (the latest registration wins). `stamp()`, if
    given, returns what every entry of the compile log under this name
    carries besides its stages (`{"step": the round being dispatched}`):
    read by the listener when such an entry closes, so on the compiling
    thread, inside the call that compiled."""
    _programs[name] = provider
    if stamp is not None:
        _stamps[name] = stamp
    else:
        _stamps.pop(name, None)


def compile_stamp(name: str) -> Dict[str, Any]:
    """What the program registered for entries called `name`, now; {} for
    a name nobody registered. Never raises: a compile must not fail for
    its record."""
    stamp = _stamps.get(name)
    if stamp is None:
        return {}
    try:
        return dict(stamp())
    except Exception:
        return {}


on_entry(_on_compile_entry)


def program_report(name: str) -> Optional[Dict[str, Any]]:
    """The compiled program's account of itself, by program name
    (`"train_round"`): `{"memory": {"argument", "output", "alias", "temp"}
    (bytes per device, XLA's memory analysis), "ops": {"%fusion.769":
    {"scope", "phase", "recomputed", "layer_type", "layer", "opcode",
    "computation"},
    ...}, "recompute": {"attn_core": {"kernel", "step_bodies", "forward",
    "backward", "kept_bytes"}, ...}, "attention_moves": {"instructions",
    "bytes", "gathers_scatters"}, "routing_moves": {"instructions", "bytes",
    "row_gathers", "rows_gathered", "row_scatters", "rows_scattered",
    "slot_scalar_moves", "slot_scalars_moved"},
    "delta_rule": {"loops", "trips",
    "kernel_calls", "shape_kernel_calls", "carried_bytes", "instructions",
    "bytes", "kept_bytes"}, "eva": {"layers", "core_forward_calls",
    "core_backward_calls", "keys_per_query", "blocks_visited", "blocks",
    "summary_instructions", "summary_bytes"}, "ssm": {"layers", "loops",
    "trips", "kernel_calls", "carried_bytes", "instructions", "bytes"},
    "ssd": {"kernel_calls", "chunk", "heads_per_program", "programs_per_group",
    "layers_under_documents"},
    "window": {"layers", "windowed_layers", "blocks_visited",
    "blocks_causal"}}` — see
    `parse_hlo_ops` for the
    attribution rule, `recompute_report` for what the recomputation blocks
    keep ({} for a net whose blocks name nothing, or without blocks),
    `attention_moves` for what a step's attention moves without
    computing, `routing_moves` for what its expert layers move around
    their products, `delta_rule` for how its delta rules were compiled and
    `eva` for how its EVA attention layers were, `ssm` for how its
    state-space scans were, `ssd_kernels` for which kernels they run as and
    `window` for what its attention cores under
    sliding windows visit
    (each {} for a net without such layers; the first five call
    `moves_under`).
    None when no such
    program is registered or it has not been dispatched yet.

    NEVER on the round path: the first call lowers and compiles the program
    again (a persistent-compile-cache hit where the cache is on, a second
    compile where it is not) and parses its text; nothing calls it in an
    untraced run. The memory numbers then show as the gauges
    `sparknet_<name>_{temp,argument,output}_bytes`, and the kept values'
    kernels that still run in the backward pass as
    `sparknet_<name>_recompute_core_forward_in_backward`, and what its
    attention moves as `sparknet_<name>_attention_moves_*`, on the registries
    `attach_program_gauges` was given; all three in `program_part(key)`."""
    provider = _programs.get(name)
    report = provider() if provider is not None else None
    if report is not None:
        _program_parts[name] = {k: report.get(k, {}) for k in REPORT_PARTS}
    return report


def scope_of(op_name: str) -> Dict[str, Any]:
    """`{"scope", "phase", "recomputed", "layer_type", "layer"}` of one
    `op_name` path. Inside `tau_step`: under `solver_update` is optimizer, a
    path through `transpose(` is backward, everything else (the loss's own
    arithmetic with it) forward; outside `tau_step` (the scan's slicing of
    the stack, the peeled step's copy, `tau_boundary`, the health reductions)
    is `outside_step`. The layer is the `<Type>/<name>` scope `CompiledNet.
    apply` opened.

    `recomputed`: a backward path with `rematted_computation` among its
    components -- the forward pass a recomputation block (`LayerSpec.block`,
    `jax.checkpoint`) makes again for the backward it serves, and what a
    layer's own checkpoints make again inside it (`KDAttention`'s rows). Such
    an op stays `phase` backward: the flag splits that phase, it is no fifth.
    It is read of the op's ATTRIBUTED path (`parse_hlo_ops`' rules), so a
    fusion that mixes a block's recomputed elementwise work with a product
    of the backward pass proper belongs to the product and is not
    recomputed (and the other way about). What a kernel's own backward makes
    again inside itself (`ssd_chunk_bwd`'s chunk states, `delta_scan_bwd`'s
    chunk-start states, the splash backward's scores) and what a
    `custom_vjp`'s backward makes again in plain jax
    (`delta_rule.segment_states`) is backward proper: no block's doing, and
    no kept name removes it. A net without blocks flags nothing."""
    parts = op_name.split("/")
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    scope = "/".join(parts[:-1])  # the last component is the primitive
    if STEP_SCOPE not in parts:
        phase = "outside_step"
    elif OPTIMIZER_SCOPE in parts:
        phase = "optimizer"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    m = _LAYER.search(scope)
    return {"scope": scope, "phase": phase,
            "recomputed": phase == "backward" and REMATTED in parts,
            "layer_type": m.group(1) if m else None,
            "layer": m.group(2) if m else None}


def parse_hlo_ops(text: str) -> Dict[str, Dict[str, Any]]:
    """Every instruction that runs as a device op of its own (those of the
    entry computation, of loop bodies and conditions and of called
    computations — not the insides of a fusion), by instruction name, with
    the scope it is attributed to. The rule:

      * an instruction belongs to the scope of its own `op_name`;
      * a fusion that contains a `convolution` or a `dot` belongs to THAT
        instruction's scope — the matmul sets its time, whatever XLA fused
        behind it (a bias add, the solver's subtract); where it holds
        several, the first in the fused computation's order wins and
        `"matmuls"` says how many there were;
      * any other fusion belongs to the scope of its root (the fusion's
        own `op_name`, which XLA takes from its root; the root's where the
        fusion has none);
      * an instruction with no `op_name` of its own (a copy or a prefetch
        the compiler put in) takes that of the nearest instruction of its
        computation that made one of its operands, else of the nearest that
        uses it, else `outside_step` with no layer.
    """
    # an instruction may run over several lines (a Pallas kernel's metadata
    # holds line breaks, and a line of it starts with "}"): a computation
    # ends at a line that is "}" alone, and a line inside one that starts no
    # instruction continues the instruction before it
    raw: Dict[str, List[List[Any]]] = {}  # computation -> [root, name, text]
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and "=" not in line.split("(", 1)[0]:
                current = raw.setdefault(m.group(1).lstrip("%"), [])
            continue
        if line.rstrip() == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            current.append([bool(m.group(1)), m.group(2), m.group(3)])
        elif current:
            current[-1][2] += " " + line.strip()
    comps: Dict[str, List[Dict[str, Any]]] = {}
    for cname, lines in raw.items():
        current = comps.setdefault(cname, [])
        for root, iname, rest in lines:
            body = rest.split(", metadata=", 1)[0]
            op = _OPCODE.search(" " + body)
            name = _OP_NAME.search(rest)
            paren = body.find("(", op.start()) if op else -1
            trips, const = _TRIPS.search(rest), _INT_CONSTANT.search(body)
            target = _TARGET.search(body)
            current.append({
                "target": target.group(1) if target else None,
                "trips": int(trips.group(1)) if trips else None,
                "const": int(const.group(1)) if const else None,
                "single": bool(op and op.group(1) in ("gather", "scatter")
                               and _SINGLE.search(body)),
                "name": "%" + iname.lstrip("%"), "root": root,
                "opcode": op.group(1) if op else "",
                "shapes": _shapes(body[:op.start()]) if op else [],
                "op_name": name.group(1) if name else None,
                "called": {k: [c.strip().lstrip("%") for c in v.split(",")]
                           for k, v in _CALLED.findall(body)},
                "operands": re.findall(r"%[\w.\-]+",
                                       body[paren:].split(")")[0])
                if paren >= 0 else []})
    for instructions in comps.values():
        for i in instructions:
            # a loop whose trip count the text does not state: the one
            # integer its condition compares the counter with
            if i["opcode"] == "while" and i["trips"] is None:
                bounds = [c["const"] for c in comps.get(
                    (i["called"].get("condition") or [""])[0], [])
                    if c["const"] is not None]
                i["trips"] = bounds[0] if len(bounds) == 1 else None
    inlined = {c for ins in comps.values() for i in ins
               for k, cs in i["called"].items() if k in _INLINED
               and i["opcode"] not in CONTAINERS for c in cs}
    ops: Dict[str, Dict[str, Any]] = {}
    for cname, instructions in comps.items():
        if cname in inlined:
            continue
        by_name = {i["name"]: i for i in instructions}
        users: Dict[str, List[str]] = {}
        own: Dict[str, Optional[str]] = {}   # by the first three rules
        extras: Dict[str, Dict[str, Any]] = {}
        for i in instructions:
            for o in i["operands"]:
                users.setdefault(o, []).append(i["name"])
            op_name = i["op_name"]
            fused = [i]
            if i["opcode"] == "fusion":
                fused = comps.get((i["called"].get("calls") or [""])[0], [])
                mm = [f for f in fused
                      if f["opcode"] in ("convolution", "dot")
                      and f["op_name"]]
                root = [f for f in fused if f["root"] and f["op_name"]]
                if mm:
                    op_name = mm[0]["op_name"]
                    if len(mm) > 1:
                        extras[i["name"]] = {"matmuls": len(mm)}
                elif op_name is None and root:
                    op_name = root[0]["op_name"]
            own[i["name"]] = op_name
            extras.setdefault(i["name"], {}).update(
                _moves(i, by_name, fused, _with_nested(fused, comps)))
        for i in instructions:
            if i["opcode"] == "parameter":
                continue
            op_name = (own[i["name"]]  # else the compiler's own: a copy
                       or _inherit(i, by_name, own, lambda x: x["operands"])
                       or _inherit(i, by_name, own,
                                   lambda x: users.get(x["name"], [])))
            ops[i["name"]] = {**scope_of(op_name or ""),
                              "opcode": i["opcode"], "computation": cname,
                              **extras.get(i["name"], {})}
    return ops


def _with_nested(fused, comps) -> List[Dict[str, Any]]:
    """`fused` and, to any depth, the instructions of the fusions among
    them."""
    return list(fused) + [n for f in fused if f["opcode"] == "fusion"
                          for n in _with_nested(comps.get(
                              (f["called"].get("calls") or [""])[0], []), comps)]


def _moves(instruction, by_name, fused, nested) -> Dict[str, Any]:
    """What `attention_moves` reads of one device op: `"bytes"` (its
    operands' and results', as the text gives their shapes; 0 for what is no
    op or moves nothing), `"matmul"` (it is, or its fusion `fused` holds, a
    `dot` or a `convolution`) and, where it is or holds any, `"indexed"`: the
    dimensions of every `gather`'s and `scatter`'s operands and result,
    `"gathered"`: the dimensions of every `gather`'s result alone,
    `"scattered"`: of every `scatter`, the dimensions of (the array it adds
    into, the updates it adds), and `"scalars"`: how many elements each
    `gather` or `scatter` among them that moves single elements (`_SINGLE`)
    fetches or places. The TPU compiler wraps a scatter in a fusion
    of its own inside the fusion that sorts its indices and fetches its
    updates in that order: `nested` is `fused` with what such inner fusions
    hold, and the scatters are looked for there."""
    opcode = instruction["opcode"]
    result = instruction["shapes"]
    if opcode in _NO_BYTES or opcode in CONTAINERS or opcode.endswith("-done"):
        nbytes = 0
    else:
        if opcode.endswith("-start"):  # (the result, the operand again, ...)
            result = result[:1]
        nbytes = _nbytes(result) + sum(
            _nbytes(by_name[o]["shapes"]) for o in instruction["operands"]
            if o in by_name)
    out = {"bytes": nbytes, "matmul": any(
        f["opcode"] in ("convolution", "dot") for f in fused)}
    if opcode == "while":  # what `delta_rule` reads of a loop
        out["loop"] = {"trips": instruction["trips"],
                       "carried_bytes": _nbytes(result)}
    if instruction["target"] == PALLAS_TARGET:  # and of a kernel
        out["pallas"] = True
    inside = {f["name"]: f["shapes"] for f in nested}
    dims_of = lambda o: next((dims for _, dims in inside.get(o) or by_name.get(
        o, {}).get("shapes", [])), ())
    indexed = []
    for f in fused:
        if f["opcode"] in ("gather", "scatter"):
            arrays = f["shapes"] + [s for o in f["operands"]
                                    for s in inside.get(o, [])]
            indexed.append(tuple(d for _, dims in arrays for d in dims))
    if indexed:
        out["indexed"] = indexed
        out["gathered"] = [dims for f in fused if f["opcode"] == "gather"
                           for _, dims in f["shapes"][:1]]
    # scatter(arrays..., indices, updates...): the first array's result
    # beside the first of as many updates
    scatters = [f for f in nested if f["opcode"] == "scatter"
                and f["shapes"] and len(f["operands"]) >= 3]
    scattered = [(f["shapes"][0][1],
                  dims_of(f["operands"][len(f["operands"]) // 2 + 1]))
                 for f in scatters]
    if scattered:
        out["scattered"] = scattered
    # the elements fetched or placed one an index (the compiler's own fetch
    # of a scatter's updates in sorted order, in a fusion inside, is none of
    # the program's)
    scalars = [math.prod(f["shapes"][0][1]) for f in fused
               if f["opcode"] == "gather" and f["single"] and f["shapes"]] + [
        math.prod(updates) for f, (_, updates) in zip(scatters, scattered)
        if f["single"]]
    if scalars:
        out["scalars"] = scalars
    return out


def _inherit(instruction, by_name, own, neighbours) -> Optional[str]:
    """The attributed `op_name` (`own`) of the nearest instruction reached
    from `instruction` along `neighbours` (its operands, or its users) that
    has one, first neighbour first; None when the walk ends at parameters
    or the root."""
    seen, queue = {instruction["name"]}, [instruction]
    while queue:
        for name in neighbours(queue.pop(0)):
            nxt = by_name.get(name)
            if nxt is None or name in seen or nxt["opcode"] == "parameter":
                continue
            if own[name]:
                return own[name]
            seen.add(name)
            queue.append(nxt)
    return None


def _named_bytes(jaxpr, name: str) -> int:
    """Bytes of the values `checkpoint_name` marks `name` in one pass over
    `jaxpr`: what it names itself and in what it calls, or in one turn of
    the loop body that names most, whichever is more (a round's scanned
    steps and its peeled one each name a step's values)."""
    from jax.core import jaxprs_in_params
    here = in_a_loop = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name" and eqn.params["name"] == name:
            here += sum(v.aval.size * v.aval.dtype.itemsize
                        for v in eqn.outvars)
        inner = max((_named_bytes(sub, name)
                     for sub in jaxprs_in_params(eqn.params)), default=0)
        if eqn.primitive.name in ("scan", "while"):
            in_a_loop = max(in_a_loop, inner)
        else:
            here += inner
    return max(here, in_a_loop)


def recompute_report(ops: Dict[str, Dict[str, Any]],
                     kept_makers: Dict[str, str],
                     jaxpr=None) -> Dict[str, Dict[str, Any]]:
    """What the program's recomputation blocks keep, by kept name
    (`kept_makers`: name -> what marks the ops that make its values, a part
    of their scope matched as a prefix -- a Pallas kernel's name, or the
    named scope a layer runs its plain products under;
    `CompiledNet.kept_makers()`): `{"maker", "step_bodies": the
    computations that hold such an op (a loop's body, the peeled step),
    "forward" / "backward": the kernel calls, custom calls, sorts (a `top_k`
    is a custom call on a CPU, a sort on a TPU) and matrix products so marked
    on a forward path and on a recomputed one (`recomputed` of `scope_of`: a
    block's forward made again; the products of the backward pass proper
    run under the same scope and do not count) in the step body that has
    most, "kept_bytes": of the named values in one step (`_named_bytes` of
    the program's `jaxpr`; None without one)}`. The mechanism is engaged where "backward" is 0. Off the
    chip no kernel runs and a kernel's counts are both 0."""
    out = {}
    for name, maker in kept_makers.items():
        count: Dict[str, Dict[str, int]] = {}   # computation -> phase -> n
        for op in ops.values():
            made = (op["opcode"] in ("custom-call", "sort")
                    or op["matmul"]) and any(
                part.startswith(maker) for part in op["scope"].split("/"))
            if made and (op["recomputed"] or op["phase"] == "forward"):
                body = count.setdefault(op["computation"], {})
                body[op["phase"]] = (body.get(op["phase"], 0)
                                     + op.get("matmuls", 1))
        out[name] = {
            "maker": maker, "step_bodies": len(count),
            **{phase: max((b.get(phase, 0) for b in count.values()),
                          default=0) for phase in ("forward", "backward")},
            "kept_bytes": None if jaxpr is None
            else _named_bytes(jaxpr, name)}
    return out


def moves_under(ops: Dict[str, Dict[str, Any]], belongs,
                sums: Dict[str, Any]) -> Dict[str, int]:
    """The one query the per-mechanism counters are calls of: of the device
    ops that move bytes and for which `belongs(op, its scope's parts)` holds,
    by the computation that runs them (a loop's body, the peeled step),
    `{"instructions", "bytes": their operands' and results' together, **{key:
    the sum of sums[key](op)}}` -- in the body that moves most bytes."""
    zero = {"instructions": 0, "bytes": 0, **{key: 0 for key in sums}}
    bodies: Dict[str, Dict[str, int]] = {}
    for op in ops.values():
        if not op["bytes"] or not belongs(op, op["scope"].split("/")):
            continue
        body = bodies.setdefault(op["computation"], dict(zero))
        body["instructions"] += 1
        body["bytes"] += op["bytes"]
        for key, of in sums.items():
            body[key] += of(op)
    return max(bodies.values(), key=lambda b: b["bytes"], default=zero)


def attention_moves(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, str],
                    positions: int) -> Dict[str, int]:
    """What a step's attention moves without computing: of the device
    ops under its scopes (`scopes`: layer type -> the scope under the layer's
    own that holds its attention, "" for the whole layer;
    `CompiledNet.attention_scopes()`) that hold neither a matmul nor a kernel
    -- slices, copies, transposes, concatenations, elementwise passes --
    `{"instructions", "bytes": their operands' and results' together,
    "gathers_scatters": the `gather` and `scatter` instructions in them that
    index an activation (one with the step's `positions`, or the half the
    TPU compiler splits them into, among its operands' or result's
    dimensions)}`, in the step body that moves most. {} for a net without
    such layers. The layout is decided on the weights where
    "gathers_scatters" is 0."""
    if not scopes:
        return {}
    along = (positions, positions // 2)

    def belongs(op, parts):
        sub = scopes.get(op["layer_type"])
        return (sub is not None and (not sub or sub in parts)
                and not op["matmul"] and op["opcode"] != "custom-call")

    return moves_under(ops, belongs, {"gathers_scatters": lambda op: sum(
        any(d in along for d in dims) for dims in op.get("indexed", ()))})


def routing_moves(ops: Dict[str, Dict[str, Any]], scopes: Tuple[str, ...],
                  width: int) -> Dict[str, int]:
    """What a step's expert layers move to choose experts and to carry rows
    to and from them: of the device ops under their routing scopes (`scopes`,
    `width`: `CompiledNet.routing_scopes()`), `{"instructions", "bytes",
    "row_gathers": the `gather` instructions among them whose result's minor
    dimension is the model's `width` (rows of activations, not scalars),
    "rows_gathered": the rows those fetch, "row_scatters": the `scatter`
    instructions among them that add rows into an array as wide as its
    updates, of that width or of a slab of its columns, "rows_scattered":
    the rows of the width those add (a slab of c columns adds c / width of a
    row), "slot_scalar_moves": the `gather` and `scatter` instructions among
    them that fetch or place single elements (a weight, a score or a
    gradient of one: a TPU moves those one at a time, 7-10 ns each),
    "slot_scalars_moved": the elements those move}`, in the step body that
    moves most. {} for a net without such layers. Every data pass walks the
    buffer's rows where "rows_gathered" counts no tokens x top-k; a weighted
    sum by token walked them as a scatter-add, a slab of columns at a time,
    where "rows_scattered" counts them (`seq_layers.sum_walks_buffer`); and
    no per-slot scalar travels by an index over tokens x top-k where
    "slot_scalars_moved" counts a few times the buffer's rows -- a row's
    weight fetched, its `dw` placed -- and no tokens x top-k: the router
    selects its chosen scores from the experts' columns
    (`seq_layers.chosen_scores`)."""
    if not scopes:
        return {}
    rows = lambda op: [math.prod(dims[:-1]) for dims in op.get("gathered", ())
                       if dims and dims[-1] == width]
    added = lambda op: [math.prod(updates)  # the elements a row scatter adds
                        for into, updates in op.get("scattered", ())
                        if into and updates and into[-1] == updates[-1] <= width]
    out = moves_under(
        ops, lambda op, parts: any(s in parts for s in scopes),
        {"row_gathers": lambda op: len(rows(op)),
         "rows_gathered": lambda op: sum(rows(op)),
         "row_scatters": lambda op: len(added(op)),
         "rows_scattered": lambda op: sum(added(op)),
         "slot_scalar_moves": lambda op: len(op.get("scalars", ())),
         "slot_scalars_moved": lambda op: sum(op.get("scalars", ()))})
    return {**out, "rows_scattered": out["rows_scattered"] // width}


def delta_rule(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, str],
               kept_bytes: int = 0) -> Dict[str, int]:
    """How a program's delta rules were compiled: of the device ops under
    their scopes (`scopes`: layer type -> the scope under the layer's own
    that holds its rule; `CompiledNet.delta_scopes()`), `{"loops": the
    `while` instructions among them in the WHOLE program (the `jnp` form's
    scans over segments and chunks, forward, made again and transposed; a
    layer's rows go through loops of their own, so a step's are spread over
    several computations; a round holds a step twice, as the scanned body
    and as the peeled last step; where the kernels ran the walk over a
    row's chunks is `ops.pallas_delta_scan`'s grid, the state in VMEM, and
    the loops left are the backward pass's walk for the state every segment
    started from, a scan of scans a layer and step body),
    "trips": their trip counts together (a loop whose count the text does
    not give counts 1), "kernel_calls": the Pallas kernels' `custom-call`
    instructions among them in the whole program (the chunk stage's,
    `ops.pallas_delta_rule`, and the walk's, `ops.pallas_delta_scan`, each
    forward, forward again by the row and backward: six a layer and step
    body; 0 where the `jnp` form was taken), "shape_kernel_calls": those of such layers in the whole
    program under the scope of the stage before the rule, which shapes q,
    k, v and the decay (`ops.kda_shape.SCOPE`, the scope its kernel pair
    runs under: `ops.pallas_kda_shape`; 0 where its `jnp` form was taken; a
    kernel elsewhere in such a layer counts in neither), "carried_bytes": the most one of
    them carries a trip (its state and what it walks), "instructions",
    "bytes": of the ops that hold neither a matmul nor a kernel, in the
    computation that moves most (a call of `moves_under`: one row's segment
    of chunks), "kept_bytes": what the recomputation blocks keep of such
    layers for the backward pass (`kept_bytes`: the caller's count of the
    values they name)}`. {} for a net without such layers."""
    if not scopes:
        return {}
    from ..ops.kda_shape import SCOPE
    before = [op for op in ops.values() if op["layer_type"] in scopes
              and SCOPE in op["scope"].split("/")]
    return {**_scan_account(ops, scopes),
            "shape_kernel_calls": sum(op.get("pallas", False) for op in before),
            "kept_bytes": kept_bytes}


def _scan_account(ops: Dict[str, Dict[str, Any]],
                  scopes: Dict[str, str]) -> Dict[str, int]:
    """What `delta_rule` and `ssm` both read of the device ops under a
    sequential scan's scope (`scopes`: layer type -> the scope under the
    layer's own): the `while` instructions in the whole program, their
    trips, the Pallas calls, the most one loop carries a trip, and
    `moves_under` of the ops that hold neither a matmul nor a kernel."""
    under = lambda op, parts: scopes.get(op["layer_type"]) in parts
    here = [op for op in ops.values() if under(op, op["scope"].split("/"))]
    loops = [op["loop"] for op in here if "loop" in op]
    moves = moves_under(ops, lambda op, parts: under(op, parts)
                        and not op["matmul"] and op["opcode"] != "custom-call",
                        {})
    return {"loops": len(loops), "trips": sum(l["trips"] or 1 for l in loops),
            "kernel_calls": sum(op.get("pallas", False) for op in here),
            "carried_bytes": max((l["carried_bytes"] for l in loops), default=0),
            **moves}


def ssm(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, str]
        ) -> Dict[str, int]:
    """How a program's state-space scans were compiled: of the device ops
    under their scopes (`scopes`: layer type -> the scope under the layer's
    own that holds its scan; `CompiledNet.ssd_scopes()`), `{"layers": the
    layers that hold one, "loops": the `while` instructions among them in
    the WHOLE program (the `jnp` form's scan over chunks forward, made
    again, and its transpose; a round holds a step twice, as the scanned
    body and as the peeled last step; 0 where the kernels ran: their grid
    walks the chunks), "trips": their trip counts together (a loop whose
    count the text does not give counts 1), "kernel_calls": the Pallas
    kernels' `custom-call` instructions among them (`ops.pallas_ssd`: the
    forward, the forward made again with its chunk states and the backward,
    three a layer and step body; 0 where `ops.ssd` took its `jnp` form: any
    backend but the TPU, narrow heads), "carried_bytes": the most one of the
    loops carries a trip (the float32 state and what it walks), "instructions", "bytes": of the
    ops that hold neither a matmul nor a kernel, in the computation that
    moves most (a call of `moves_under`)}`. {} for a net without such
    layers."""
    if not scopes:
        return {}
    layers = {op["layer"] for op in ops.values()
              if scopes.get(op["layer_type"]) in op["scope"].split("/")}
    return {"layers": len(layers), **_scan_account(ops, scopes)}


def ssd_kernels(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, str],
                layers: Dict[str, Dict[str, int]]) -> Dict[str, int]:
    """Which kernels a program's state-space scans run as: `{"kernel_calls":
    the Pallas `custom-call` instructions under the scans' scopes (`ssm`'s
    count), "chunk": the positions a chunk they walk, "heads_per_program":
    the heads of a group one program works, "programs_per_group",
    "layers_under_documents": the layers whose scan is cut by document ids}`
    -- the shape `ops.ssd.program_heads` gives the net's layers (`layers`:
    `CompiledNet.ssd_kernels()`; 0s where it is not the kernels' and the
    `jnp` form runs, the widest layer's where the layers differ). {} for a
    net without such layers."""
    if not layers:
        return {}
    widest = max(layers.values(), key=lambda s: s.get("heads_per_program", 0))
    return {"kernel_calls": _scan_account(ops, scopes)["kernel_calls"],
            **{k: widest.get(k, 0) for k in ("chunk", "heads_per_program",
                                             "programs_per_group")},
            "layers_under_documents": sum(s["documents"] for s in layers.values())}


def eva(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, Tuple[str, str]],
        core: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """How a program's EVA attention layers were compiled: of the device ops
    of such layers (`scopes`: layer type -> the scopes under the layer's own
    that hold its chunk summaries and its core; `core`: what a layer's core
    is given -- both `CompiledNet.eva_scopes()`), `{"layers": the layers of
    these types, "core_forward_calls" / "core_backward_calls": the Pallas
    kernels' `custom-call` instructions under the core scope on a forward
    path and on a backward one (`phase` of `scope_of`: a forward kernel run
    again for the backward counts with the backward's), in the step body
    that has most (one forward and one backward a layer where the kernel
    ran and the block kept its output; 0 and 0 off the chip),
    "keys_per_query": the key columns a query row is given (the row's
    positions and one summary a chunk), "blocks_visited" / "blocks": the
    key blocks a layer's forward kernel visits over the key blocks there
    are, all query blocks together (the mask's density as the kernel's
    tables have it), "summary_instructions" / "summary_bytes": the device
    ops under the summaries scope and their operands' and results' bytes,
    in the step body that moves most (a call of `moves_under`)}`. {} for a
    net without such layers."""
    if not scopes:
        return {}
    of = lambda op, which: scopes.get(op["layer_type"], (None, None))[which]
    calls: Dict[str, Dict[str, int]] = {}   # computation -> phase -> n
    for op in ops.values():
        if op.get("pallas") and of(op, 1) in op["scope"].split("/"):
            body = calls.setdefault(op["computation"], {})
            body[op["phase"]] = body.get(op["phase"], 0) + 1
    most = max(calls.values(), key=lambda b: sum(b.values()), default={})
    moves = moves_under(
        ops, lambda op, parts: of(op, 0) is not None and of(op, 0) in parts, {})
    return {"layers": len({op["layer"] for op in ops.values()
                           if op["layer_type"] in scopes}),
            "core_forward_calls": most.get("forward", 0),
            "core_backward_calls": most.get("backward", 0),
            **{key: (core or {}).get(key, 0)
               for key in ("keys_per_query", "blocks_visited", "blocks")},
            "summary_instructions": moves["instructions"],
            "summary_bytes": moves["bytes"]}


def window(ops: Dict[str, Dict[str, Any]], scopes: Dict[str, str],
           layers: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """How a program's attention layers under sliding windows were compiled
    (`scopes`: layer type -> the scope under the layer's own that holds its
    core; `layers`: layer -> its window, or None, and the key blocks its
    forward kernel visits under its mask beside those a causal mask over
    every key would -- both `CompiledNet.window_scopes()`): `{"layers":
    {layer: {"window", "blocks_visited", "blocks_causal",
    "core_forward_calls" / "core_backward_calls": the Pallas kernels'
    `custom-call` instructions under the layer's core scope on a forward
    path and on a backward one, in the step body that has most (one and one
    where the kernel ran and the block kept its output; 0 and 0 off the
    chip)}}, "windowed_layers": those with a window, "blocks_visited" /
    "blocks_causal": all the layers' together}`. {} for a net none of whose
    layers attends under a window."""
    if not scopes:
        return {}
    calls: Dict[str, Dict[str, Dict[str, int]]] = {}  # layer -> body -> phase
    for op in ops.values():
        if (op.get("pallas") and op["layer"] in layers
                and scopes.get(op["layer_type"]) in op["scope"].split("/")):
            body = calls.setdefault(op["layer"], {}).setdefault(
                op["computation"], {})
            body[op["phase"]] = body.get(op["phase"], 0) + 1
    out = {}
    for name, given in layers.items():
        most = max(calls.get(name, {}).values(),
                   key=lambda b: sum(b.values()), default={})
        out[name] = {**given, "core_forward_calls": most.get("forward", 0),
                     "core_backward_calls": most.get("backward", 0)}
    return {"layers": out,
            "windowed_layers": sum(l["window"] is not None
                                   for l in layers.values()),
            **{key: sum(l[key] for l in layers.values())
               for key in ("blocks_visited", "blocks_causal")}}


def report_of_compiled(compiled, kept_makers: Optional[Dict[str, str]] = None,
                       jaxpr=None, attention=({}, 0),
                       routing=((), 0), delta=({}, ()),
                       eva_layers=({}, None), ssd=None,
                       windows=({}, {}), ssd_layers=None) -> Dict[str, Any]:
    """The report of one `jax.stages.Compiled` (what a program's provider
    returns): its memory analysis, `parse_hlo_ops` of its text, for the
    names its net's recomputation blocks keep `recompute_report`, for
    its attention layers (`attention`: their scopes and positions)
    `attention_moves`, for its expert layers (`routing`: their routing
    scopes and the model's width) `routing_moves`, for its delta-rule
    layers (`delta`: their scopes and the names their blocks keep)
    `delta_rule`, for its EVA attention layers (`eva_layers`: their
    scopes and what a core is given) `eva`, for its state-space mixers
    (`ssd`: their scans' scopes) `ssm` and (`ssd_layers`: what each walks)
    `ssd_kernels`, and for its attention layers under sliding windows
    (`windows`: their cores' scopes and what each visits) `window`."""
    mem = compiled.memory_analysis()
    ops = parse_hlo_ops(compiled.as_text())
    return {"memory": {k: int(getattr(mem, f"{k}_size_in_bytes"))
                       for k in ("argument", "output", "alias", "temp")},
            "ops": ops,
            "recompute": recompute_report(ops, kept_makers or {}, jaxpr),
            "attention_moves": attention_moves(ops, *attention),
            "routing_moves": routing_moves(ops, *routing),
            "delta_rule": delta_rule(ops, delta[0], sum(
                _named_bytes(jaxpr, name) for name in delta[1]
                if jaxpr is not None)),
            "eva": eva(ops, *eva_layers), "ssm": ssm(ops, ssd or {}),
            "ssd": ssd_kernels(ops, ssd or {}, ssd_layers or {}),
            "window": window(ops, *windows)}


#: program -> these parts of its report, once `program_report` has run
REPORT_PARTS = ("memory", "recompute", "attention_moves", "routing_moves",
                "delta_rule", "eva", "ssm", "ssd", "window")
_program_parts: Dict[str, Dict[str, Dict[str, Any]]] = {}


def attach_program_gauges(registry: MetricsRegistry,
                          name: str = "train_round") -> None:
    """Show `sparknet_<name>_{temp,argument,output}_bytes`,
    `sparknet_<name>_recompute_core_forward_in_backward` and
    `sparknet_<name>_attention_moves_{bytes,gathers_scatters}` on this
    registry's /metrics: live-read gauges with no sample until
    `program_report(name)` has run (they never ask for it themselves)."""
    part = lambda key: _program_parts[name][key]
    for key in ("temp", "argument", "output"):
        registry.gauge(
            f"sparknet_{name}_{key}_bytes",
            f"the compiled {name} program's {key} bytes per device (XLA "
            f"memory analysis, read by program_report)"
        ).set_fn(lambda key=key: part("memory")[key])
    registry.gauge(
        f"sparknet_{name}_recompute_core_forward_in_backward",
        f"kernels and products of values the {name} program's recomputation "
        f"blocks are to keep that run again in a step's backward pass (0: "
        f"every named value is kept; read by program_report)"
    ).set_fn(lambda: sum(r["backward"] for r in part("recompute").values()))
    for key, what in (("bytes", "operand and result bytes"),
                      ("gathers_scatters",
                       "gathers and scatters that index an activation")):
        registry.gauge(
            f"sparknet_{name}_attention_moves_{key}",
            f"{what} of the device ops under the {name} program's latent "
            f"attention, a step, that hold neither a matmul nor a kernel "
            f"(read by program_report)"
        ).set_fn(lambda key=key: part("attention_moves")[key])


def attach_round_counter_gauges(registry: MetricsRegistry, trainer) -> None:
    """Show the counters the trainer's net returns with its round's scalars
    (an expert layer's `slots_landed`, `slots_dropped`, `expert_tokens_max`,
    `expert_tokens_min`: sums over a round's steps and workers) as gauges
    `sparknet_moe_<counter>{layer=...}` -- a Mamba-2 mixer's
    `doc_boundaries` as `sparknet_ssd_doc_boundaries{layer=...}` --:
    live-read from `trainer.counter_values()`, which waits for nothing. No
    gauge for a net whose layers count nothing."""
    from ..model.seq_layers import SSD_COUNTERS
    for blob, names in getattr(trainer, "counter_blobs", {}).items():
        layer = blob[:-len("_counters")] if blob.endswith("_counters") else blob
        family = "ssd" if tuple(names) == SSD_COUNTERS else "moe"
        for name in names:
            registry.gauge(
                f"sparknet_{family}_{name}",
                f"a layer's {name}, summed over the last finished "
                f"round's steps and workers", labels=("layer",)
            ).set_fn(lambda blob=blob, name=name:
                     trainer.counter_values()[blob][name], layer=layer)


def program_part(key: str) -> Dict[str, Dict[str, Any]]:
    """{program: the `key` part of its report (one of `REPORT_PARTS`)}
    for every program whose report has been asked for
    so far — a read of what is cached, never a compile (the /status
    route)."""
    return {name: parts[key] for name, parts in _program_parts.items()}
