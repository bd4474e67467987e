"""Persistent XLA compilation cache — cold-start-to-zero for serve + train.

Every replica cold-start, checkpoint hot-swap retrace, serve-bucket first
forward, and elastic `trainer_factory` rebuild pays a fresh XLA compile
today; PR 5's compile-event telemetry (`obs/device.py`) measures exactly
what that costs but nothing SAVES it. This module wires jax's persistent
compilation cache (`jax_compilation_cache_dir`) through one init point and
gives the telemetry the `cache_hit` signal:

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

  - `track_compiles()` — a context manager counting the fresh XLA backend
    compiles and persistent-cache hits/misses that happen INSIDE the
    region, on this thread. `obs.device.timed_compile` and the serve
    bucket first-forward wrap their compile regions with it and stamp the
    verdict as the `cache_hit` label on `sparknet_compile_events_total`:
    a region that did no fresh XLA work (everything served from the
    persistent cache, or no XLA compile at all — e.g. a memoized spec
    compile) is a HIT; a region that built at least one executable from
    scratch is a MISS. "Zero cache_hit=false events on a warm replica
    cold-start" is then a scrapeable acceptance number (BENCH_ECON).

Counting rides `jax.monitoring`: jax records
`/jax/core/compile/backend_compile_duration` around every
compile-or-fetch and `/jax/compilation_cache/cache_{hits,misses}` when
the persistent cache is consulted, all ON THE COMPILING THREAD — so
thread-local counters attribute a region's compiles to the thread that
ran it (the serve lane's single-writer worker, the trainer's dispatch
thread) even while other lanes compile concurrently.
"""
from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_listening = False
_cache_dir: Optional[str] = None
_tls = threading.local()


def _counts():
    c = getattr(_tls, "counts", None)
    if c is None:
        c = _tls.counts = {"xla": 0, "hit": 0, "miss": 0}
    return c


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _counts()["hit"] += 1
    elif event == _CACHE_MISS_EVENT:
        _counts()["miss"] += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _counts()["xla"] += 1


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
    """Context manager: counts this THREAD's fresh XLA backend compiles
    and persistent-cache hits/misses inside the region.

    After exit: `.xla_compiles`, `.cache_hits`, `.cache_misses`, and the
    verdict `.cache_hit` — True iff the region required no fresh XLA
    compilation (no backend compile at all, or every compile request was
    served from the persistent cache). With no cache configured, any XLA
    compile in the region is by definition a miss."""

    xla_compiles = 0
    cache_hits = 0
    cache_misses = 0

    def __enter__(self) -> "track_compiles":
        ensure_listeners()
        c = _counts()
        self._t0 = (c["xla"], c["hit"], c["miss"])
        return self

    def __exit__(self, *exc) -> bool:
        c = _counts()
        self.xla_compiles = c["xla"] - self._t0[0]
        self.cache_hits = c["hit"] - self._t0[1]
        self.cache_misses = c["miss"] - self._t0[2]
        return False

    @property
    def cache_hit(self) -> bool:
        if self.xla_compiles == 0:
            return True  # nothing was compiled fresh
        # fresh XLA work happened: a hit requires the persistent cache
        # to have actually been CONSULTED for it (hit/miss events fired)
        # with zero misses. An initialized directory alone is not enough — a
        # configured cache jax is not consulting (e.g. switched off by
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
