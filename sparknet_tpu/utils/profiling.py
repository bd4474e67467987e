"""jax.profiler capture hook (SURVEY §5.1 tracing/profiling subsystem).

The reference had per-phase wall timers only (`apps/CifarApp.scala` logged
driver-side elapsed times); PhaseTimers reproduces those. This adds the
device-level view the reference could not see: a TensorBoard-loadable XLA
trace (op-by-op device timeline, HBM usage) captured around a bounded window
of work. Use `RunConfig.profile_dir` to trace one mid-training round, or
`bench.py --profile DIR` to trace the benchmark's timed section.

The capture also holds the program's own host spans and needs no code here
for it: `obs.trace.span` sees that a profiler session is live and writes
each span as a `sparknet:<name>` annotation on the profiler's clock (host
plane, the recording thread's line), and every device op carries its
layer's `named_scope` (`Convolution/conv1` … inside `tau_step`). The
`trace_out` Chrome file is the host-only, profiler-free view of the same
spans.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace into `trace_dir` for the with-block;
    no-op when trace_dir is falsy. View with TensorBoard's profile plugin
    (`tensorboard --logdir <trace_dir>`) or xprof. The program's
    `obs.trace.span`s inside the block land in it as `sparknet:`
    annotations (module docstring)."""
    if not trace_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
