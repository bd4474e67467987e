"""Host-side span tracer: one `span()`, two sinks, one clock.

`jax.profiler` (utils/profiling.py) answers "what did the DEVICE do";
this module answers "where did the host's wall clock go" across the threads
this codebase actually runs: the round loop, the one-deep prefetch thread
(`round-prep`), the async checkpoint writer (`ckpt-write`), and the serve
worker. This tracer is that cross-thread picture, in the Dapper tradition
of named spans: code wraps its interesting sections in `span("name")`
context managers (the PhaseTimers phases emit spans automatically), each
completed span becomes one Chrome `"X"` (complete) event with `ts`/`dur`
in microseconds and the recording thread as its `tid`, and `write()`
produces a JSON file loadable in Perfetto / chrome://tracing — side by
side with the device trace if both were captured.

Timestamps are EPOCH-anchored (epoch_at_start + perf_counter elapsed), so
traces from different processes (a trainer and a server watching its
checkpoints) merge on one timeline — the same reason the metrics JSONL now
carries a wall-clock `ts` field.

The second sink is the profiler itself. While a `jax.profiler` session is
live — whoever started it: `RunConfig.profile_dir` (utils/profiling.py), the
benchmark's `--trace 1`, an operator's `jax.profiler.start_trace` — every
span also opens `jax.profiler.TraceAnnotation("sparknet:" + name, **args)`,
so it lies on the PROFILER's clock in the same xplane as the device's ops
(host plane, the recording thread's line), and is kept in a bounded
in-memory record of that session on `time.perf_counter()`: name, start,
end, thread name, an id and the id of the span that encloses it on the same
thread (`round` / `step` in `args` is the identifier the spans of one round
share). `session_spans()` reads the live or the last session's record; it
stays readable after the session ends, until the next one begins. So a
`profile_dir` capture now holds this module's events (and the layers'
`named_scope`s on the device ops), and `trace_out` is the host-only,
profiler-free view of the same spans.

A third record is always on: the steps that run ONCE A BUILD (`startup_span`:
`resolve_spec`, `build_trainer` with `compile_net` and `trainer_init` inside
it, `state_from_params`, `restore`) are kept whether or not a tracer or a
profiler is live, in one small bounded record (`MAX_STARTUP_SPANS`) that
`startup_spans()` reads in the shape `session_spans()` returns, beside
`import_stamp()`, the process's first import of the package on the same clock.
The round's compile is not a span: it is the `train_round` entry of the compile
log (`utils/compile_cache.compile_log()`), `t0` to `t1` on this clock too.

Tracing is off by default. `span()` is on when a tracer was started
(`start_tracing`) OR a profiler session is live
(`TraceAnnotation.is_enabled()`): no configuration field, flag or environment
variable of its own, and this module alone decides. Off, a span costs one
None-check and one `is_enabled()` call (the <= 2% telemetry-overhead budget
in BENCH_OBS.json includes it ON). One process-wide active tracer: spans are
emitted by library code (checkpoint writer, serve worker) that cannot know
which run is being traced, so activation is global — `start_tracing()` /
`stop_tracing()`, or the `tracing(path)` context manager the train loop uses
for `--trace-out`.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

#: prefix of this module's annotations in a profiler trace
ANNOTATION_PREFIX = "sparknet:"

#: events kept per tracer; beyond this new spans are counted but dropped
#: (a runaway soak must not OOM the host to produce a trace)
MAX_EVENTS = 500_000


#: start-up spans kept a process (a handful a trainer built; a process that
#: rebuilds trainers for days stops adding, and counts, at this many)
MAX_STARTUP_SPANS = 512


class Tracer:
    """Collects span events; thread-safe; one instance per capture."""

    def __init__(self, max_events: int = MAX_EVENTS):
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._thread_names: Dict[int, str] = {}
        self.dropped = 0
        self.pid = os.getpid()
        # epoch-anchored monotonic clock: ts = (_epoch0 + perf_counter) µs
        self._epoch0 = time.time() - time.perf_counter()

    def now_us(self) -> float:
        return self.us(time.perf_counter())

    def us(self, t: float) -> float:
        """A `time.perf_counter()` reading on this tracer's epoch clock."""
        return (self._epoch0 + t) * 1e6

    def add_complete(self, name: str, t0_us: float, dur_us: float,
                     args: Optional[Dict[str, Any]] = None,
                     **extra: Any) -> None:
        """One complete ("X") event on the calling thread's lane; `extra`
        keys ride on the event (a session record's span carries its
        perf_counter times, id and parent there)."""
        th = threading.current_thread()
        ev = {"name": name, "ph": "X", "cat": "host",
              "ts": round(t0_us, 3), "dur": round(dur_us, 3),
              "pid": self.pid, "tid": th.ident, **extra}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._thread_names.setdefault(th.ident, th.name)
            self._events.append(ev)

    def instant(self, name: str, **args: Any) -> None:
        """A zero-duration mark (scope: thread) — e.g. a log flush or a
        hot swap decision."""
        th = threading.current_thread()
        ev: Dict[str, Any] = {"name": name, "ph": "i", "s": "t",
                              "cat": "host", "ts": round(self.now_us(), 3),
                              "pid": self.pid, "tid": th.ident}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._thread_names.setdefault(th.ident, th.name)
            self._events.append(ev)

    def events(self) -> List[Dict[str, Any]]:
        """Snapshot: span events plus thread-name metadata (`"M"`) records
        so each lane is labeled (MainThread / round-prep_0 / ckpt-write_0 /
        serve-worker) instead of a bare thread id."""
        with self._lock:
            evs = list(self._events)
            names = dict(self._thread_names)
        meta = [{"name": "thread_name", "ph": "M", "pid": self.pid,
                 "tid": tid, "args": {"name": name}}
                for tid, name in sorted(names.items())]
        meta.append({"name": "process_name", "ph": "M", "pid": self.pid,
                     "args": {"name": f"sparknet_tpu pid {self.pid}"}})
        return meta + evs

    def write(self, path: str) -> int:
        """Write the Chrome trace JSON object form; returns event count."""
        evs = self.events()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                       "otherData": {"dropped_events": self.dropped}}, f)
        return len(evs)


_active: Optional[Tracer] = None

#: the record of the live profiler session, or of the last one (a Tracer:
#: bounded by MAX_EVENTS, thread-safe); replaced when the next session begins
_session: Optional[Tracer] = None
_session_open = False
_session_lock = threading.Lock()
_ids = itertools.count(1)
_tls = threading.local()


def active_tracer() -> Optional[Tracer]:
    return _active


def start_tracing(tracer: Optional[Tracer] = None) -> Tracer:
    """Install `tracer` (or a fresh one) as the process-wide span sink."""
    global _active
    _active = tracer or Tracer()
    return _active


def stop_tracing() -> Optional[Tracer]:
    """Uninstall and return the active tracer (None when none was on)."""
    global _active
    t, _active = _active, None
    return t


def _session_record(live: bool) -> Optional[Tracer]:
    """The record spans go to while a profiler session is live (a fresh one
    at the first span that sees the session), else None. The last record is
    kept when its session ends."""
    global _session, _session_open
    if not live:
        if _session_open:
            _session_open = False
        return None
    if not _session_open:
        with _session_lock:
            if not _session_open:
                _session = Tracer()
                _session_open = True
    return _session


def _kept_spans(rec: Optional[Tracer]) -> List[Dict[str, Any]]:
    if rec is None:
        return []
    return [{"name": e["name"], "t0": e["t0"], "t1": e["t1"],
             "thread": e["thread"], "id": e["id"], "parent": e["parent"],
             "args": e.get("args", {})}
            for e in rec.events() if e["ph"] == "X"]


def session_spans() -> List[Dict[str, Any]]:
    """The host spans of the live profiler session, or of the last one:
    `{"name", "t0", "t1", "thread", "id", "parent", "args"}` each, times on
    `time.perf_counter()`, `parent` the id of the span that enclosed it on
    the same thread (None at the top), in order of completion. Empty when no
    span ever ran inside a profiler session."""
    return _kept_spans(_session)


#: the start-up spans of this process (a Tracer: bounded, thread-safe)
_startup = Tracer(MAX_STARTUP_SPANS)


def startup_spans() -> List[Dict[str, Any]]:
    """The spans `startup_span` kept, in the shape of `session_spans()`, in
    order of completion (an enclosing span after those inside it)."""
    return _kept_spans(_startup)


def import_stamp() -> float:
    """`time.perf_counter()` at the process's first import of the package:
    from here to the first start-up span is imports and backend start-up."""
    from .. import IMPORT_T0
    return IMPORT_T0


class _recorded:
    """The with-block as one span of `rec`: an id, the id of the span of
    this record that encloses it on this thread (`_tls.<stack_name>`), its
    two stamps on `time.perf_counter()` (`.t0`, and `.t1` once the block
    has ended). A class, not a generator: it is `span()`'s cost a round
    while a profiler session is live."""
    __slots__ = ("rec", "stack", "name", "args", "sid", "parent", "t0", "t1")

    def __init__(self, rec: Tracer, stack_name: str, name: str,
                 args: Dict[str, Any]):
        stack = getattr(_tls, stack_name, None)
        if stack is None:
            stack = []
            setattr(_tls, stack_name, stack)
        self.rec, self.stack, self.name, self.args = rec, stack, name, args

    def __enter__(self) -> "_recorded":
        self.sid = next(_ids)
        self.parent = self.stack[-1] if self.stack else None
        self.stack.append(self.sid)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t0, t1 = self.t0, time.perf_counter()
        self.t1 = t1
        self.stack.pop()
        self.rec.add_complete(
            self.name, self.rec.us(t0), (t1 - t0) * 1e6, self.args or None,
            t0=t0, t1=t1, id=self.sid, parent=self.parent,
            thread=threading.current_thread().name)
        return False


@contextmanager
def startup_span(name: str, **args: Any) -> Iterator[None]:
    """A step that runs once a build: kept in the start-up record whether or
    not anything is tracing, and an ordinary `span()` besides. `args` are
    plain numbers and strings (the record outlives the call: no array).
    Usable as a decorator. Never on the per-round path."""
    with _recorded(_startup, "startup_stack", name, args), span(name, **args):
        yield


@contextmanager
def span(name: str, **args: Any) -> Iterator[None]:
    """The program's one host span: the with-block as one complete event on
    the current thread's lane of the active tracer, and — while a
    `jax.profiler` session is live — as a `sparknet:<name>` annotation in
    the profiler's own trace plus one entry of the session record (module
    docstring). Near-free when both are off (one global read + None check,
    one `is_enabled()` call)."""
    tr = _active
    rec = _session_record(_Annotation.is_enabled())
    if rec is None:
        if tr is None:
            yield
            return
        t0 = tr.now_us()
        try:
            yield
        finally:
            # re-read: a tracer stopped mid-span (loop teardown while the
            # checkpoint writer drains) must not resurrect into the report
            if _active is tr:
                tr.add_complete(name, t0, tr.now_us() - t0, args or None)
        return
    kept = _recorded(rec, "stack", name, args)
    try:
        with kept, _Annotation(ANNOTATION_PREFIX + name, **args):
            yield
    finally:
        if tr is not None and _active is tr:
            tr.add_complete(name, tr.us(kept.t0), (kept.t1 - kept.t0) * 1e6,
                            args or None)


@contextmanager
def tracing(path: Optional[str] = None) -> Iterator[Tracer]:
    """Capture spans for the with-block; write to `path` on exit when
    given. The train loop's `--trace-out` wrapper."""
    tr = start_tracing()
    try:
        yield tr
    finally:
        stop_tracing()
        if path:
            tr.write(path)
