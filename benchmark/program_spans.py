"""The program's own host spans of the traced stretch: what
`sparknet_tpu.obs.trace.span` kept in memory while the profiler session was
live (name, start and end on `time.perf_counter()`, thread, parent, and the
`step` the spans of one dispatched round share). Nothing to read from a
program that keeps no such record, and in an untraced run.
"""
from __future__ import annotations

import json

_noted: set = set()   # id(run) of the runs whose note line is out


def per_round(spans: list, name: str):
    """The arithmetic alone: the mean over rounds of the time in spans
    called `name`, milliseconds, a round being the spans that share a
    `step`; the session's first round is dropped (it holds the stall of
    starting the profiler, as trace_reduce drops the first period). None
    where no later round has such a span."""
    stepped = [s for s in spans if "step" in s["args"]]
    if not stepped:
        return None
    first = min(s["args"]["step"] for s in stepped)
    by_step: dict = {}
    for s in stepped:
        if s["name"] == name and s["args"]["step"] != first:
            by_step[s["args"]["step"]] = by_step.get(
                s["args"]["step"], 0.0) + (s["t1"] - s["t0"])
    if not by_step:
        return None
    return 1e3 * sum(by_step.values()) / len(by_step)


def span_ms_per_round(run, name: str):
    if run.trace is None:
        return None
    try:
        from sparknet_tpu.obs.trace import session_spans
    except ImportError:  # a program from before its spans had a record
        return None
    spans = session_spans()
    if id(run) not in _noted:  # every span of the session, once a run
        _noted.add(id(run))
        print(json.dumps({"note": "program_spans", "ms_per_round": {
            n: per_round(spans, n) for n in sorted({s["name"] for s in spans})
        }}), flush=True)
    return per_round(spans, name)
