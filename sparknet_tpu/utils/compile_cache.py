"""Persistent XLA compilation cache, and the process's record of every
executable jax builds or fetches.

  - `init_compile_cache(dir)` — point jax at a persistent on-disk cache.
    The directory is placed from OUTSIDE first: where
    `$JAX_COMPILATION_CACHE_DIR` is set, that directory is the cache, and
    no argument, config field or flag moves it (the path is part of the
    cache's key, and whoever runs the process — a pod launcher, a
    benchmark driver — has to find the entries again). Unset, an explicit
    argument is used if given; otherwise the cache lives at
    `<checkout>/.cache/jax`, resolved from this package's own path — a
    fixed place, never a temp name, so a second run hits what the first
    one compiled. The entry-size / min-compile-time floors are dropped to
    "cache everything": serve-bucket forwards on small nets compile in
    well under jax's default 1 s floor, and those are exactly the
    compiles a replica cold-start repays.

  - **The compile log** (`compile_log()`): one entry an executable built
    or fetched, process-wide, bounded: the FIRST `MAX_ENTRIES` are kept (a
    start-up's entries are never pushed out by a process that compiles for
    days), later ones are counted (`compile_log_dropped()`, in `/status`
    `startup`) and only the newest `_NEWEST` of them held, for
    `track_compiles` and the operator's "newest compile". jax reports the stages of a compile through `jax.monitoring`, ALL ON THE
    COMPILING THREAD and each with the jitted function's name:
    `jaxpr_trace_duration` (`fun_name="train_round"`),
    `jaxpr_to_mlir_module_duration` and `backend_compile_duration`
    (`fun_name="jit(train_round)"`), and between the last two, where the
    persistent cache is consulted, `compile_requests_use_cache`, then
    `cache_hits` with `cache_retrieval_time_sec` and
    `compile_time_saved_sec`, or `cache_misses` once the new entry is
    written. The two listeners gather them by thread, and an entry closes
    when its `backend_compile_duration` arrives:

        {"what": "train_round", "thread": "MainThread", "tid": ...,
         "seq": 41, "t0": ..., "t1": ...,   # time.perf_counter()
         "trace_s": 9.1, "lower_s": 6.3, "backend_s": 25.6,
         "cache": "miss",                 # "hit" | "miss" | "off"
         "retrieval_s": None, "saved_s": None,   # set on a hit
         "step": 0}                       # what the program stamps, below

    `what` is jax's name for the function without the `jit(...)` around
    it. A jitted function traced inside another reports a
    `jaxpr_trace_duration` of its own within the outer one's; an entry
    takes the stages that carry ITS name and never sums the nested ones.
    `backend_s` is the XLA compile or, on a hit, the key, the retrieval
    and the load; `cache` is "off" where jax did not consult the cache for
    this executable (its flags are reset where jax starts to consult it,
    so a verdict no compile followed never lands on the next entry). `t0`
    is where the entry's first stage began, so `t0`..`t1` is the call that
    compiled: a traced or lowered stage counts only if it ended where the
    next stage began (within `_contiguous`), so what a `.lower()` or an
    `eval_shape` left behind long ago is dropped, not summed. `seq` is the
    entry's number among all ever closed (`track_compiles`' mark). Before
    an entry is kept it is handed to the sink (`on_entry(fn)`):
    `obs.device` registers one that stamps it with what the program
    registered for its name (`register_program(name, report, stamp=...)`:
    the trainer stamps `train_round` with the `step` being dispatched, so a
    recompile in round 5,000 is an entry with `step` 5000) and counts it
    (`note_compile`), so
    `sparknet_compile_events_total{what="train_round",cache_hit=...}` and
    `compile_stats()["train_round"]` exist with no call site in the
    program. This module imports nothing of `obs`.

  - `track_compiles()` — a context manager: a VIEW of the log, the entries
    this thread closed inside the region. `obs.device.timed_compile` and
    the serve bucket first-forward wrap their compile regions with it and
    stamp the verdict as the `cache_hit` label on
    `sparknet_compile_events_total`: a region that did no fresh XLA work
    (everything served from the persistent cache, or no XLA compile at
    all — e.g. a memoized spec compile) is a HIT; a region that built at
    least one executable from scratch is a MISS. "Zero cache_hit=false
    events on a warm replica cold-start" is then a scrapeable acceptance
    number (BENCH_ECON). Entering is one read of a counter; leaving a
    region in which nothing compiled is one more, and no copy of the log
    either way. A region that closed more than `_NEWEST` entries sees the
    newest of them.
"""
from __future__ import annotations

import collections
import itertools
import os
import re
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_USED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

#: entries the log keeps: the first; later ones are counted and dropped (a
#: process that compiles for days must not grow, nor lose its start-up)
MAX_ENTRIES = 4096
#: the newest entries, kept besides whether or not the log is full: what
#: `track_compiles` views and `newest_compile` reads
_NEWEST = 256
#: stages a thread may hold for names no compile has closed yet (functions
#: traced and never compiled): beyond this they are forgotten
_MAX_PENDING = 1024

_lock = threading.Lock()
_listening = False
_cache_dir: Optional[str] = None
_tls = threading.local()
_log: List[Dict[str, Any]] = []
_newest: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=_NEWEST)
_closed = 0  # entries ever closed, on any thread: the log's `seq` and mark
_sink: Optional[Callable[[Dict[str, Any]], None]] = None  # `on_entry`'s
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


class _Gathering:
    """What one thread has been told since its last entry closed."""
    __slots__ = ("stages", "consulted", "hit", "miss", "retrieval_s",
                 "saved_s")

    def __init__(self):
        #: what -> {"trace" | "lower": (seconds, perf_counter at its end)}
        self.stages: Dict[str, Dict[str, tuple]] = {}
        self.reset_cache()

    def reset_cache(self) -> None:
        self.consulted = self.hit = self.miss = False
        self.retrieval_s = self.saved_s = None


def _gathering() -> _Gathering:
    g = getattr(_tls, "gathering", None)
    if g is None:
        g = _tls.gathering = _Gathering()
    return g


def _what(fun_name: str) -> str:
    """jax's name of a function without the `jit(...)` the lowering and the
    backend stage put around it."""
    m = _WRAPPED.match(fun_name)
    return m.group(1) if m else fun_name


def _contiguous(stage: Optional[tuple], next_start: float) -> bool:
    """Did this pending stage end where the next one began? Between two
    stages of one compile jax spends a fraction of a second (0.44 s in all
    for a 192 s compile, on the chip); a stage left by a `.lower()` or an
    `eval_shape` that no compile followed ended long before."""
    if stage is None:
        return False
    seconds, end = stage
    return abs(next_start - end) <= max(1.0, 0.05 * seconds)


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_USED_EVENT:
        g = _gathering()
        g.reset_cache()  # a verdict no compile followed is not this one's
        g.consulted = True
    elif event == _CACHE_HIT_EVENT:
        _gathering().hit = True
    elif event == _CACHE_MISS_EVENT:
        _gathering().miss = True


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        g = _gathering()
        if len(g.stages) >= _MAX_PENDING:
            g.stages.clear()
        own = g.stages.setdefault(_what(str(kw.get("fun_name", "?"))), {})
        now = time.perf_counter()
        if event == _TRACE_EVENT:
            own.pop("lower", None)  # of an earlier trace of this name
            own["trace"] = (float(duration), now)
        else:
            if not _contiguous(own.get("trace"), now - duration):
                own.pop("trace", None)
            own["lower"] = (float(duration), now)
    elif event == _BACKEND_COMPILE_EVENT:
        _close(_what(str(kw.get("fun_name", "?"))), float(duration))
    elif event == _RETRIEVAL_EVENT:
        _gathering().retrieval_s = float(duration)
    elif event == _SAVED_EVENT:
        _gathering().saved_s = float(duration)


def _close(what: str, backend_s: float) -> None:
    """An executable is built or fetched: its entry, from the stages that
    carry its name and led up to this one, and the cache events since jax
    began to consult the cache."""
    global _closed
    t1 = time.perf_counter()
    g = _gathering()
    own = g.stages.pop(what, {})
    # the stage before the backend's: the lowering, or the tracing where
    # the lowering was found cached; an earlier trace was held to the
    # lowering's start when that arrived
    last = own.get("lower") or own.get("trace")
    if not _contiguous(last, t1 - backend_s):
        own = {}
    trace_s, trace_end = own.get("trace", (0.0, None))
    lower_s, lower_end = own.get("lower", (0.0, None))
    t0 = (trace_end - trace_s if trace_end is not None
          else lower_end - lower_s if lower_end is not None
          else t1 - backend_s)
    # what this thread traced since `t0` was traced inside this function
    g.stages = {k: v for k, v in g.stages.items()
                if max(end for _, end in v.values()) < t0}
    cache = ("hit" if g.hit else
             "miss" if g.miss or g.consulted else "off")
    entry = {"what": what, "thread": threading.current_thread().name,
             "tid": threading.get_ident(), "t0": t0, "t1": t1,
             "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
             "cache": cache, "retrieval_s": g.retrieval_s,
             "saved_s": g.saved_s}  # jax reports the two on a hit alone
    g.reset_cache()
    if _sink is not None:
        try:
            _sink(entry)
        except Exception:
            pass  # a compile must not fail for its record
    with _lock:
        _closed += 1
        entry["seq"] = _closed
        if len(_log) < MAX_ENTRIES:
            _log.append(entry)
        _newest.append(entry)


def on_entry(sink: Callable[[Dict[str, Any]], None]) -> None:
    """Hand every entry to `sink(entry)` when it closes, before it is kept:
    on the compiling thread, inside the call that compiled. The sink (one a
    process: `obs.device`'s) may add keys of its own (the program's stamp,
    `step`)."""
    global _sink
    ensure_listeners()
    _sink = sink


def _kept() -> List[Dict[str, Any]]:
    # under `_lock`: the first MAX_ENTRIES, then the newest of what came after
    last = _log[-1]["seq"] if _log else 0
    return _log + [e for e in _newest if e["seq"] > last]


def compile_log() -> List[Dict[str, Any]]:
    """The log's entries, oldest first (copies: a reader may keep them):
    the first `MAX_ENTRIES` and, past a gap of `compile_log_dropped()`, the
    newest `_NEWEST`."""
    with _lock:
        return [dict(e) for e in _kept()]


def compile_log_dropped() -> int:
    """Entries closed and not in `compile_log()`: all of them later than
    the log's first `MAX_ENTRIES`."""
    with _lock:
        return _closed - len(_kept())


def ensure_listeners() -> None:
    """Register the jax.monitoring listeners once per process (idempotent,
    cheap). Called by init and by every track_compiles — compile counting
    works even when no persistent cache is configured."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax.monitoring as mon
        mon.register_event_listener(_on_event)
        mon.register_event_duration_secs_listener(_on_duration)
        _listening = True


#: the variable jax itself binds `jax_compilation_cache_dir` to; read at
#: call time so whoever launches the process decides where the cache is
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed in-checkout default (`.gitignore` lists `/.cache/`)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")

def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Where the persistent cache goes: `$JAX_COMPILATION_CACHE_DIR` when
    set (an explicit `cache_dir` is then ignored, and a warning says so —
    once per calling site, by the warnings filter's default), else
    `cache_dir`, else `DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env and cache_dir:
        warnings.warn(
            f"compile cache: ${CACHE_DIR_ENV}={env!r} is set, so the "
            f"explicit directory {str(cache_dir)!r} is ignored",
            RuntimeWarning, stacklevel=3)
    return os.path.abspath(os.path.expanduser(
        str(env or cache_dir or DEFAULT_CACHE_DIR)))


def init_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Initialize the persistent compilation cache (idempotent; safe to
    call from the train loop, the serve CLI, the benchmarks and tests
    alike). Returns the active cache directory (`resolve_cache_dir`)."""
    ensure_listeners()
    import jax

    global _cache_dir
    d = resolve_cache_dir(cache_dir)
    with _lock:
        if _cache_dir is not None:
            # FIRST caller wins: the cache is process-global jax state,
            # and repointing it mid-flight would abandon every lane's
            # warm entries (reset_for_tests() exists for tests that
            # genuinely need a fresh dir)
            return _cache_dir
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        # cache EVERYTHING: the default floors (1 s compile time, 4 KiB
        # entries) skip exactly the small serve-bucket executables whose
        # re-compilation a replica cold-start is made of
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax latches cache-off on the first compile that runs without a
        # dir configured; a server/CLI initializing AFTER model build
        # (any jax touch) would silently get no cache. reset_cache()
        # drops the latch so the next compile re-reads the config.
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
        _cache_dir = d
    return d


class track_compiles:
    """Context manager: this THREAD's entries of the compile log closed
    inside the region (`.entries`, after exit).

    After exit: `.xla_compiles` (executables built or fetched),
    `.cache_hits`, `.cache_misses`, and the verdict `.cache_hit` — True iff
    the region required no fresh XLA compilation (no backend compile at
    all, or every compile request was served from the persistent cache).
    With no cache configured, any XLA compile in the region is by
    definition a miss."""

    xla_compiles = 0
    cache_hits = 0
    cache_misses = 0
    entries: tuple = ()

    def __enter__(self) -> "track_compiles":
        ensure_listeners()
        self._mark = _closed
        return self

    def __exit__(self, *exc) -> bool:
        if _closed != self._mark:  # something compiled, on some thread
            me = threading.get_ident()
            with _lock:
                newest = list(itertools.islice(reversed(_newest),
                                               _closed - self._mark))
            self.entries = tuple(e for e in reversed(newest)
                                 if e["tid"] == me)
            self.xla_compiles = len(self.entries)
            self.cache_hits = sum(e["cache"] == "hit" for e in self.entries)
            self.cache_misses = sum(e["cache"] == "miss"
                                    for e in self.entries)
        return False

    @property
    def cache_hit(self) -> bool:
        if self.xla_compiles == 0:
            return True  # nothing was compiled fresh
        # fresh XLA work happened: a hit requires the persistent cache
        # to have actually been CONSULTED for it with zero misses. An
        # initialized directory alone is not enough — a configured cache
        # jax is not consulting (e.g. switched off by
        # `jax_enable_compilation_cache`) would otherwise read as a hit
        # exactly when the cache silently failed.
        return (self.cache_misses == 0
                and self.cache_hits + self.cache_misses > 0)


def reset_for_tests() -> None:
    """Clear the active-dir latch so tests can re-init against their own
    tmp dirs (the jax config itself is process-global either way)."""
    global _cache_dir
    with _lock:
        _cache_dir = None
